"""What the drivers share: the run's context, logging to stderr, trace
annotations, the traced stretch, the memory reading."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "perfbench_out")


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_file(kind: str, name: str):
    """The module ``perfbench/<kind>/<name>.py``, by path: a name may
    hold dots, which ``import`` would read as packages."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reference(config: dict):
    return load_file("reference", config["reference"])


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    started: float          # time.perf_counter() at process start

    def window_opens(self) -> float:
        """Called by the driver the moment set-up is over; returns
        ``setup_s``."""
        return time.perf_counter() - self.started


def annotate(name: str, on: bool):
    """A ``jax.profiler.TraceAnnotation`` in a traced run, nothing in
    an end-to-end run."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def traced(ctx: Context, body) -> dict:
    """Run ``body()`` under the profiler, inside one
    ``perfbench.window`` annotation, and reduce the trace. Python
    frames are not traced: they cost more than what they show."""
    import jax

    from perfbench import trace_reduce

    trace_dir = os.path.join(OUT, "trace", ctx.cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            body()
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    summary = trace_reduce.reduce(
        trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    log(f"trace reduced in {time.perf_counter() - t0:.1f}s: window "
        f"{summary['window_s']:.3f}s busy {summary['busy_s']:.3f}s")
    for name, seconds in sorted(summary["op_self_s"].items(),
                                key=lambda kv: -kv[1])[:30]:
        log(f"  op {seconds:9.5f}s  {name}")
    return summary


def memory_peaks() -> dict:
    """Peak bytes on the fullest chip, as the runtime reports them, in
    two parts that are kept apart: ``in_use``, the arrays that were
    alive (weights, optimizer state, KV pool, batches:
    ``peak_bytes_in_use``), and ``reserved``, what the loaded programs
    held for their temporaries (``peak_bytes_reserved``). On this
    libtpu the first leaves the second out (``gpt2s.train1``: 1.52 GB,
    its float32 state alone, beside 9.50 GB), and ``bytes_limit`` less
    both is ``largest_free_block_bytes`` to within a few MB (my chip
    runs, PR 24)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    log(f"memory_stats of device 0: {stats[0]}")
    peaks = [{"in_use": int(s.get("peak_bytes_in_use", 0)),
              "reserved": int(s.get("peak_bytes_reserved", 0))}
             for s in stats]
    return max(peaks, key=lambda p: p["in_use"] + p["reserved"])
