"""Device idle time by the program's own ``serving.*`` spans.

``trace_reduce.load`` keeps the host events the benchmark itself wrote
(``perfbench.*``); the program writes its own, inside ``Engine.step``
and around the server's loop (``telemetry/events.py::phase``), and a
device idle gap inside one of them is that part's to answer for. This
reads them from the same ``.xplane.pb``, with ``trace_reduce``'s
interval arithmetic: every instant of the traced window belongs to the
innermost ``serving.*`` event that covers it, and an event's idle
seconds are its seconds less device 0's busy seconds inside them.

A program without such spans (the parent of the PR that added them)
gives a table with ``UNCOVERED`` alone, and the readers report nothing.
"""

from __future__ import annotations

import functools
import glob
import os

import numpy as np

from perfbench import common, trace_reduce

PREFIX = "serving."
UNCOVERED = "_no_span_"
# What Engine.step opens, and what ServingServer._engine_loop_inner
# opens around it.
ENGINE_SPANS = ("serving.step", "serving.admit", "serving.pack",
                "serving.launch", "serving.fetch", "serving.emit")
SERVER_SPANS = ("serving.control", "serving.mailbox",
                "serving.dispatch", "serving.idle_sleep")


def _process_started() -> float:
    """When this process started, on the clock file times are on:
    field 22 of ``/proc/self/stat`` (clock ticks since boot) after the
    boot time of ``/proc/stat``."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rpartition(")")[2].split()[19])
    with open("/proc/stat") as f:
        boot = next(int(line.split()[1]) for line in f
                    if line.startswith("btime "))
    return boot + ticks / os.sysconf("SC_CLK_TCK")


def this_runs_xplane() -> str | None:
    """The newest ``.xplane.pb`` under ``perfbench_out/trace/`` that
    was written since this process started (``obs`` carries no path,
    and an older run's trace of another cell may lie beside it)."""
    paths = glob.glob(os.path.join(
        common.OUT, "trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    started = _process_started()
    paths = [p for p in paths if os.path.getmtime(p) >= started - 1.0]
    return max(paths, key=os.path.getmtime) if paths else None


@functools.cache
def idle_by_span(path: str, prefix: str = PREFIX) -> dict:
    """``{"window_s": ..., "idle_s": {span name: seconds}}`` for the
    window of ``path`` (the ``perfbench.window`` event, else the span
    of device 0's operations): device 0's idle seconds by the innermost
    ``prefix*`` host event covering them, ``UNCOVERED`` for the idle no
    such event covers. The values sum to the window's idle seconds."""
    from jax.profiler import ProfileData

    devices: dict = {}
    spans: list = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    at = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == trace_reduce.WINDOW:
                        window = window or at
                    elif ev.name.startswith(prefix):
                        spans.append((ev.name, *at))
    if not devices:
        raise ValueError("the trace holds no device plane with an "
                         f"{trace_reduce.OPS_LINE!r} line")
    ops = np.array(devices[min(devices)], float).reshape(-1, 2)
    lo, hi = window or (ops[:, 0].min(), ops[:, 1].max())
    busy = trace_reduce.union(
        np.clip(ops[(ops[:, 1] > lo) & (ops[:, 0] < hi)], lo, hi))
    idle: dict = {}
    pieces = trace_reduce.self_intervals(
        [(n, max(s, lo), min(e, hi)) for n, s, e in spans
         if e > lo and s < hi])
    if pieces:
        at = np.array([(s, e) for _n, s, e in pieces], float)
        gaps = (at[:, 1] - at[:, 0]) - trace_reduce.covered(
            busy, at[:, 0], at[:, 1])
        for (name, _s, _e), ns in zip(pieces, gaps):
            idle[name] = idle.get(name, 0.0) + float(ns) / 1e9
    total = ((hi - lo) - float((busy[:, 1] - busy[:, 0]).sum())) / 1e9
    idle[UNCOVERED] = max(0.0, total - sum(idle.values()))
    return {"window_s": (hi - lo) / 1e9, "idle_s": idle}


@functools.cache
def _logged(path: str, prefix: str) -> dict:
    table = idle_by_span(path, prefix)
    common.log(f"device 0 idle by {prefix}* span over "
               f"{table['window_s']:.3f}s of {os.path.basename(path)} "
               f"({os.path.getsize(path)} bytes):")
    for name, seconds in sorted(table["idle_s"].items(),
                                key=lambda kv: -kv[1]):
        common.log(f"  idle {seconds:9.5f}s  {name}")
    return table


def idle_share(obs: dict, names: tuple,
               prefix: str = PREFIX) -> float | None:
    """Percent of this run's traced window in which device 0 was idle
    inside the spans ``names`` (which start with ``prefix``); None
    where the run was not traced or the program opened none of them.
    The first call of a run logs the whole table."""
    if not obs.get("trace"):
        return None
    path = this_runs_xplane()
    if path is None:
        return None
    table = _logged(path, prefix)
    found = [table["idle_s"][n] for n in names if n in table["idle_s"]]
    if not found:
        return None
    return 100.0 * sum(found) / table["window_s"]
