#!/usr/bin/env python3
"""Four chips, one host: does every layout really use all of them?

    python3 benchmarks/chip_multichip.py [ONE_CHIP_METRICS_JSONL]

The multi-device companion of ``chip_smoke.py`` (run it through the
chip tool on the four-chip host). Through the normal training CLI —
one process driving four devices — it takes a few steps of gpt2_125m
(global batch 32, seq 1024, bf16, synthetic data from a seed) under

- ``ddp``   dp 4,
- ``fsdp``  mesh.fsdp=4,
- ``tp``    mesh.tp=2 mesh.fsdp=2,

and reads each run's own ``events.jsonl`` / ``metrics.jsonl``: every
device must hold state (``hbm`` events — a layout that put everything
on device 0 fails), the Pallas flash kernels must be in the partitioned
program (``collectives`` event, ``pallas_calls`` — the ``shard_map``
wrapper in models/transformer.py executing for real), and the losses
must agree across layouts and, when ``ONE_CHIP_METRICS_JSONL``
(chip_smoke's ``chiprun_out/chip_smoke/train/metrics.jsonl``) is given,
with the one-chip run on the same data, to bf16 tolerance. Then the
dp-sharded serving engine (dp 4, params replicated) must return the
same greedy tokens as the one-chip engine for the same prompts (a
stream that parts ways must do so at a bf16 near-tie of the reference
forward's logits, and is reported).

This parent never imports jax: a chip belongs to one process, so each
run is a child of its own, one after another. Exit 0 only if every
check held; results land in ``chiprun_out/multichip/summary.json``.
Times printed are smoke output, never benchmark results.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Same configuration, data, seeds and tolerances as the one-chip smoke
# (importing it touches no jax: its jax imports are inside functions).
from chip_smoke import (  # noqa: E402
    ENGINE_GEOMETRY, NEW_TOKENS, PAGES_PER_SEQ, STEPS, TIE_TOL,
    load_jsonl, make_reference, serving_fixture, train_argv)

OUT = os.path.join(REPO, "chiprun_out", "multichip")
LOSS_TOL = 3e-2     # bf16: same data, same init, another reduction order

LAYOUTS = {
    "ddp": ["train.parallel_strategy=ddp"],
    "fsdp": ["train.parallel_strategy=fsdp", "mesh.fsdp=4"],
    "tp": ["train.parallel_strategy=tp", "mesh.tp=2", "mesh.fsdp=2"],
}


def say(msg: str) -> None:
    print(f"[chip_multichip] {msg}", flush=True)


def losses_of(metrics_path: str) -> list:
    return [r["loss"] for r in load_jsonl(metrics_path) if "loss" in r]


def run_layout(name: str, overrides: list) -> dict:
    argv = [sys.executable, "-m", "distributed_training_tpu.train",
            *train_argv(OUT, name, "train.save_every=0", *overrides)]
    say(f"{name}: {' '.join(argv[1:])}")
    t0 = time.perf_counter()
    with open(os.path.join(OUT, f"{name}.log"), "w") as log:
        rc = subprocess.run(argv, cwd=REPO, stdout=log,
                            stderr=subprocess.STDOUT,
                            timeout=1500).returncode
    wall = time.perf_counter() - t0
    if rc != 0:
        raise AssertionError(f"trainer exited {rc}; see {name}.log")
    run_dir = os.path.join(OUT, name)
    losses = losses_of(os.path.join(run_dir, "metrics.jsonl"))
    if len(losses) != STEPS or any(x != x or abs(x) == float("inf")
                                   for x in losses):
        raise AssertionError(f"want {STEPS} finite losses: {losses}")
    events = load_jsonl(os.path.join(run_dir, "events.jsonl"))
    audit = next((e for e in events if e.get("kind") == "collectives"),
                 None)
    if audit is None:
        raise AssertionError("no `collectives` event (audit failed)")
    if audit["pallas_calls"] < 2:
        raise AssertionError(
            f"pallas_calls={audit['pallas_calls']}: the partitioned "
            f"step took the naive attention path")
    hbm = [e for e in events if e.get("kind") == "hbm"][-1]
    in_use = [(d.get("stats") or {}).get("bytes_in_use", 0)
              for d in hbm["devices"]]
    estimate = hbm.get("estimate_bytes", 0)
    # Every device holds at least the state the layout assigns it
    # (estimate_bytes is the per-device params + optimizer share).
    if len(in_use) != 4 or min(in_use) < 0.9 * estimate:
        raise AssertionError(
            f"per-device bytes_in_use {in_use} vs per-device state "
            f"estimate {estimate}: not every device holds its share")
    rec = {
        "ok": True, "losses": losses, "mesh": audit.get("mesh"),
        "pallas_calls": audit["pallas_calls"],
        "collectives": {k: v["count"]
                        for k, v in audit["by_kind"].items()},
        "collective_bytes_per_step": audit["bytes_per_step"],
        "reshard_warnings": audit.get("spmd_reshard_warnings"),
        "device_bytes_in_use": in_use,
        "state_bytes_per_device_estimate": estimate,
        "smoke_wall_s": round(wall, 1),
    }
    say(f"{name}: ok mesh={rec['mesh']} losses="
        f"{[round(x, 4) for x in losses]} pallas_calls="
        f"{rec['pallas_calls']} collectives={rec['collectives']} "
        f"MiB in use per device="
        f"{[round(b / 2**20) for b in in_use]} (state estimate "
        f"{estimate / 2**20:.0f}) smoke wall {wall:.0f}s")
    return rec


def engine_child() -> int:
    """One process, four devices: the one-chip engine (mesh=None) and
    the dp-4 engine answer the same prompts; greedy tokens must match.
    Prints one JSON line."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_training_tpu.runtime import (MeshSpec, build_mesh,
                                                  enable_compile_cache)
    from distributed_training_tpu.serving.engine import (
        Engine, EngineConfig, Request)

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != 4:
        raise AssertionError(f"want 4 TPU devices, found {devices}")
    model, _f32, params, prompts = serving_fixture(
        (211, 333, 450, 97, 260, 180, 399, 64))

    def answers(eng):
        counts = eng.warmup()
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"r{i}",
                               prompt=np.asarray(p, np.int32),
                               max_new_tokens=NEW_TOKENS))
        eng.run_until_drained()
        if eng.compile_counts() != counts:
            raise AssertionError("recompiled after warm-up")
        done = {r["id"]: r for r in eng.completed}
        return ([[int(t) for t in done[f"r{i}"]["tokens"]]
                 for i in range(len(prompts))],
                sorted({r.get("group", 0) for r in done.values()}))

    one, _ = answers(Engine(model, params, EngineConfig(
        max_batch=8, num_pages=8 * PAGES_PER_SEQ + 1,
        **ENGINE_GEOMETRY), mesh=None))
    mesh = build_mesh(MeshSpec(dp=4), devices)
    placed = jax.device_put(params, NamedSharding(mesh, P()))
    eng4 = Engine(model, placed, EngineConfig(
        max_batch=8, num_pages=2 * PAGES_PER_SEQ + 1,
        **ENGINE_GEOMETRY), mesh=mesh)
    four, groups = answers(eng4)
    pool_devices = sorted(
        s.device.id for s in eng4.cache.k_pages.addressable_shards)
    same = sum(a == b for a, b in zip(one, four))
    # Two slot tables (8 x 1 vs 2 x 4) tile their bf16 matmuls
    # differently, so a greedy stream may part ways at a near-tie.
    # Where two answers differ, the training-path forward over the
    # shared prefix says whether the two candidate tokens ARE tied to
    # bf16 tolerance; anything wider is a real disagreement.
    reference = make_reference(model, params)
    ties = []
    for i, (a, b) in enumerate(zip(one, four)):
        if a == b:
            continue
        j = next(k for k, (x, y) in enumerate(zip(a, b)) if x != y)
        seq = prompts[i] + a[:j]
        (row,) = reference(seq, len(seq) - 1)
        ties.append({"request": i, "position": j,
                     "tokens": [a[j], b[j]],
                     "logit_gap": round(float(abs(row[a[j]]
                                                  - row[b[j]])), 4),
                     "gap_to_argmax": round(float(
                         row.max() - min(row[a[j]], row[b[j]])), 4)})
    ok = (groups == [0, 1, 2, 3]
          and all(t["gap_to_argmax"] <= TIE_TOL for t in ties))
    print(json.dumps({
        "ok": ok, "requests_equal": [same, len(prompts)],
        "near_tie_divergences": ties,
        "dp_groups_used": groups, "kv_pool_devices": pool_devices,
        "first_answer_one_chip": one[0], "first_answer_dp4": four[0],
    }), flush=True)
    return 0


def run_engine() -> dict:
    say("engine: one-chip engine vs dp-4 engine, same prompts")
    t0 = time.perf_counter()
    with open(os.path.join(OUT, "engine.log"), "w") as log:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "engine-child"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=log, text=True,
            timeout=1500)
    if proc.returncode != 0:
        raise AssertionError(
            f"engine child exited {proc.returncode}; see engine.log")
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    rec["smoke_wall_s"] = round(time.perf_counter() - t0, 1)
    say(f"engine: {rec}")
    if not rec["ok"]:
        raise AssertionError(f"dp-4 engine disagrees: {rec}")
    return rec


def main(argv: list) -> int:
    if argv[1:] == ["engine-child"]:
        return engine_child()
    reference = losses_of(argv[1]) if len(argv) > 1 else None
    os.makedirs(OUT, exist_ok=True)
    summary: dict = {}
    failed = []
    for name, overrides in LAYOUTS.items():
        try:
            summary[name] = run_layout(name, overrides)
        except Exception as e:  # noqa: BLE001 — run every layout, fail at the end
            summary[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"[:600]}
            say(f"{name}: FAILED {summary[name]['error']}")
            failed.append(name)
    try:
        summary["engine_dp4"] = run_engine()
    except Exception as e:  # noqa: BLE001
        summary["engine_dp4"] = {
            "ok": False, "error": f"{type(e).__name__}: {e}"[:600]}
        say(f"engine: FAILED {summary['engine_dp4']['error']}")
        failed.append("engine_dp4")

    curves = {n: summary[n]["losses"] for n in LAYOUTS
              if summary[n].get("ok")}
    if reference is not None:
        curves["one_chip"] = reference[:STEPS]
    base_name = "one_chip" if reference is not None else next(
        iter(curves), None)
    agreement = {}
    for n, curve in curves.items():
        if n == base_name:
            continue
        gap = max(abs(a - b) for a, b in zip(curve, curves[base_name]))
        agreement[f"{n}_vs_{base_name}"] = round(gap, 5)
        if gap > LOSS_TOL:
            failed.append(f"loss:{n}")
    summary["max_loss_gap"] = agreement
    say(f"max per-step loss gap (tolerance {LOSS_TOL}): {agreement}")
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if failed:
        say(f"FAILED: {failed}")
        return 1
    say("all four-chip checks held")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
