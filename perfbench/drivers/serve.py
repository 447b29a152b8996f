"""Driver for traffic of kind ``serve_closed`` and ``serve_open``: the
program's ``Engine`` behind its ``ServingServer``, loaded over loopback
HTTP by ``perfbench/loadgen.py`` in a process of its own."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from perfbench import common, traffic, yardstick


def build(ctx):
    """Model at the configuration's sizes, bfloat16 weights made on the
    device in one jitted call from the seed, the engine at the
    configuration's geometry, warmed (``Engine.warmup`` compiles every
    program the engine has), behind a server on a free port."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.serving.engine import (Engine,
                                                         EngineConfig)
    from distributed_training_tpu.serving.server import ServingServer

    prog = ctx.config["program"]
    model = build_model(prog["build_model"], dtype="bfloat16",
                        **prog["kwargs"])
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)))(
            jax.random.PRNGKey(ctx.seed))
    engine = Engine(model, params,
                    EngineConfig(**ctx.config["serving"]["engine"]))
    counts = engine.warmup()
    if ctx.trace:
        for name, label in (("step", "perfbench.engine_step"),
                            ("_fetch_host", "perfbench.fetch_host")):
            setattr(engine, name, _annotated(getattr(engine, name), label))
    server = ServingServer(engine, port=0)
    if server.start() is None:
        raise RuntimeError("ServingServer did not start")
    return model, params, engine, server, counts


def _annotated(fn, label: str):
    import jax

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(label):
            return fn(*args, **kwargs)
    return wrapped


class Sink:
    """In-memory telemetry sink for a traced run: every record the
    program emits (``serving`` step records, ``serving_trace`` request
    records), stamped with the monotonic clock on arrival. The
    program's ``Telemetry`` records only when it has a file to write,
    so it gets one under ``perfbench_out/``."""

    def __init__(self):
        from distributed_training_tpu import telemetry

        self.records: list = []
        self._telemetry = telemetry.install(telemetry.Telemetry(
            events_jsonl=os.path.join(common.OUT, "events.jsonl")))
        self._telemetry.add_observer(
            lambda rec: self.records.append((time.monotonic(), rec)))

    def close(self) -> None:
        from distributed_training_tpu import telemetry

        telemetry.uninstall()
        self._telemetry.close()

    def of_kind(self, kind: str, lo: float, hi: float) -> list:
        return [r for t, r in self.records
                if r.get("kind") == kind and lo <= t <= hi]


class Load:
    """The load generator child and its pipe."""

    def __init__(self, port: int, plan: dict):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(common.HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.proc.stdin.write(json.dumps({"port": port, "plan": plan})
                              + "\n")
        self.proc.stdin.flush()
        self.t0 = self._expect("STARTED")

    def _expect(self, word: str) -> float:
        line = self.proc.stdout.readline()
        if not line.startswith(word + " "):
            raise RuntimeError(f"load generator said {line[:200]!r}, "
                               f"want {word}")
        return float(line.split()[1])

    def filled(self) -> float:
        return self._expect("FILLED")

    def stop(self) -> list:
        try:
            self.proc.stdin.write("stop\n")
            self.proc.stdin.flush()
            out = json.loads(self.proc.stdout.readline())
        finally:
            self.kill()
        return out["records"]

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def sleep_until(t: float) -> None:
    while (left := t - time.monotonic()) > 0:
        time.sleep(min(left, 0.2))


def deliveries(records: list, gap_s: float) -> list:
    """Every streamed token's arrival, grouped into deliveries: arrivals
    closer than ``gap_s`` belong to one hand-over of the engine's.
    Returns ``[(time of the delivery's last token, tokens), ...]``."""
    times = sorted(t for r in records for t in r["times"])
    out: list = []
    for t in times:
        if out and t - out[-1][0] <= gap_s:
            out[-1] = [t, out[-1][1] + 1]
        else:
            out.append([t, 1])
    return out


def out_tok_s(records: list, lo: float, hi: float, gap_s: float) -> dict:
    """Tokens delivered after the first delivery at or after ``lo``, up
    to and including the last delivery before ``hi``, over the time
    between those two deliveries."""
    inside = [d for d in deliveries(records, gap_s) if lo <= d[0] <= hi]
    if len(inside) < 2:
        raise RuntimeError(f"{len(inside)} deliveries in the window")
    tokens = sum(n for _t, n in inside[1:])
    span = inside[-1][0] - inside[0][0]
    return {"tokens": tokens, "span_s": span, "deliveries": len(inside) - 1,
            "value": tokens / span}


def sound(rec: dict, finished_only: bool = False) -> bool:
    """A request that was not refused, did not err, and streamed what
    it was asked for: all of it if it is done (and the final record
    repeats the stream), no more than it if it was still streaming."""
    if rec["error"] or rec["sent"] is None:
        return False
    if rec["done"] is None:
        return not finished_only and len(rec["tokens"]) <= rec["asked"]
    return (len(rec["tokens"]) == rec["asked"]
            and rec["final"] == rec["tokens"])


def check_against_reference(ctx, model, params, records: list,
                            plan_prompts: dict) -> dict:
    """For a seeded sample of requests, the reference's full-context
    float32 forward over prompt + streamed tokens must put every
    streamed token within ``logit_tolerance`` of its maximum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t = ctx.traffic
    ref = common.load_reference(ctx.config)
    n_head = ctx.config["n_head"]
    pool = [r for r in records if r["done"] is not None and sound(r)]
    pool += [r for r in records if r["done"] is None and sound(r)
             and len(r["tokens"]) >= 8]
    rng = np.random.default_rng(ctx.seed)
    picks = [pool[i] for i in rng.permutation(len(pool))[:t["check_requests"]]]
    limit = ctx.config["n_positions"]
    forward = jax.jit(lambda p, ids: ref.logits(p, ids, n_head))
    ref_params = jax.jit(ref.from_program)(params)
    worst, exact, total = 0.0, 0, 0
    for rec in picks:
        prompt = plan_prompts[rec["id"]]
        seq = prompt + rec["tokens"]
        ids = np.zeros(limit, np.int32)   # one padded shape, one compile
        ids[:len(seq) - 1] = seq[:-1]
        rows = np.asarray(forward(ref_params, jnp.asarray(ids)))[
            len(prompt) - 1:len(seq) - 1]
        for row, tok in zip(rows, rec["tokens"]):
            worst = max(worst, float(row.max() - row[tok]))
            exact += int(row.argmax() == tok)
            total += 1
    return {"requests": [r["id"] for r in picks], "tokens": total,
            "argmax_equal": exact, "worst_logit_gap": worst,
            "tolerance": t["logit_tolerance"],
            "ok": len(picks) == t["check_requests"]
            and worst <= t["logit_tolerance"]}


def run(ctx) -> dict:
    t = ctx.traffic
    closed = t["kind"] == "serve_closed"
    vocab = ctx.config["program"]["token_vocab"]
    tail_s = t["trace_seconds"] + 5.0 if ctx.trace else 0.0
    if closed:
        plan = traffic.closed_plan(t, ctx.seed, vocab)
        prompts = {r["id"]: r["prompt_ids"]
                   for lane in plan["lanes"] for r in lane}
    else:
        plan = traffic.open_plan(
            t, ctx.seed, vocab,
            t["fill_seconds"] + ctx.seconds + t["drain_seconds"] + tail_s)
        prompts = {r["id"]: r["prompt_ids"] for r in plan["requests"]}
    sink = Sink() if ctx.trace else None
    model, params, engine, server, counts = build(ctx)
    load = None
    try:
        load = Load(server.port, plan)
        lo = load.t0 + t["fill_seconds"]
        if closed:
            lo = max(lo, load.filled())
        sleep_until(lo)
        setup_s = ctx.window_opens()
        hi = lo + ctx.seconds
        sleep_until(hi)
        in_flight = engine.in_flight
        trace = None
        if ctx.trace:
            trace = common.traced(ctx, lambda: time.sleep(
                t["trace_seconds"]))
        if not closed:
            # Arrivals go on, so that the requests of the window end
            # under the load they started under.
            sleep_until(hi + min(t["drain_seconds"], 0.5 * ctx.seconds))
        records = load.stop()
    finally:
        if load is not None:
            load.kill()
        server.stop()
        if sink is not None:
            sink.close()
    recompiled = engine.compile_counts() != counts
    memory = common.memory_peaks()
    late = [r["sent"] - r["due"] for r in records
            if r["due"] is not None and r["sent"] is not None]
    if late:
        common.log(f"generator lateness: p50 "
                   f"{1e3 * yardstick.percentile(late, 50):.2f} ms, max "
                   f"{1e3 * max(late):.2f} ms over {len(late)} sends")

    if closed:
        last = {lane[-1]["id"] for lane in plan["lanes"]}
        if any(r["id"] in last and r["done"] is not None
               and r["done"] < hi for r in records):
            raise RuntimeError("a client ran out of requests before the "
                               "window closed: raise requests_per_client")
        # Served in the window: streaming at its open or started in it.
        mine = [r for r in records if r["sent"] is not None
                and r["sent"] <= hi
                and (r["done"] is None or r["done"] >= lo)]
        rate = out_tok_s(records, lo, hi, t["delivery_gap_ms"] / 1e3)
        common.log(f"window: {rate}")
        end_to_end = {"serve_out_tok_s": rate["value"]}
        failed = [r for r in mine if not sound(r)]
    else:
        mine = [r for r in records if r["due"] is not None
                and lo <= r["due"] < hi]
        failed = [r for r in mine if not sound(r, finished_only=True)]
        good = [r for r in mine if sound(r, finished_only=True)]
        ttft = [1e3 * (r["times"][0] - r["due"]) for r in good]
        tpot = [1e3 * (r["times"][-1] - r["times"][0]) / (r["asked"] - 1)
                for r in good if r["asked"] > 1]
        end_to_end = {"ttft_p95_ms": yardstick.percentile(ttft, 95),
                      "tpot_p95_ms": yardstick.percentile(tpot, 95)}
    for r in failed[:5]:
        common.log(f"failed request {r['id']}: error={r['error']!r} "
                   f"streamed {len(r['tokens'])}/{r['asked']} "
                   f"done={r['done'] is not None}")
    if server.leaked_threads:
        raise RuntimeError(f"{server.leaked_threads} server thread(s) "
                           f"outlived stop()")
    server_error = server.engine_error
    steps = sink.of_kind("serving", lo, hi) if sink else []
    traces = sink.of_kind("serving_trace", lo, hi + 3600) if sink else []
    max_batch = engine.cfg.max_batch
    # The pool goes before the reference's float32 weights come. Handler
    # threads of abandoned streams still hold the engine, so the buffers
    # are freed by name.
    engine.cache.k_pages.delete()
    engine.cache.v_pages.delete()
    check = check_against_reference(ctx, model, params, records, prompts)
    common.log(f"reference check {check}; recompiled {recompiled}; "
               f"in flight at close {in_flight}")
    return {
        "correct": (check["ok"] and not failed and not recompiled
                    and server_error is None),
        "attempted": len(mine), "failed": len(failed),
        "setup_s": setup_s, "memory": memory, "trace": trace,
        "end_to_end": end_to_end,
        "obs": {"requests": mine, "window": (lo, hi),
                "engine_steps": steps, "serving_traces": traces,
                "max_batch": max_batch, "check": check},
    }
