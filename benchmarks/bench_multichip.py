#!/usr/bin/env python
"""Measured multichip benchmark against the committed sharding plan.

Promotes the MULTICHIP dryruns to a MEASURED entry: where
``__graft_entry__.dryrun_multichip`` runs one step to prove the
program compiles and executes, this runs a warmup (compile) step plus
N timed steps of the REAL trainer on the plan's mesh and records
tokens/s, step time, and MFU — the multichip number that sits in the
bench ledger (``MULTICHIP_r06.json``) next to the 0.4392 single-chip
headline. The parallelism decision is not hand-picked: the committed
auto-parallelism plan (``conf/plans/`` — parallel/planner.py) supplies
mesh shape, remat policy, per-shard batch, and the sharding-map-by-
name the trainer compiles against; the entry embeds the plan's
provenance (name, fingerprint, search evidence) and the compiled
step's reshard-warning count, which must be ZERO.

Off-TPU the mesh is fake CPU devices (the driver's
``--xla_force_host_platform_device_count`` discipline) and MFU is
computed against the nominal CPU peak from utils/metrics.py — an
honest relative number, not a TPU claim; the ``device_kind`` field
says what was measured. On a real slice the same command measures the
hardware.

    python benchmarks/bench_multichip.py                 # plan multichip_8dev
    python benchmarks/bench_multichip.py --steps 50 --out MULTICHIP_r06.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The single-chip figure this entry sits next to: builder-measured on
# an earlier machine through a plug-in that is no longer installed,
# and older than the code (docs/performance.md). bench.py owns
# re-measuring it on a live chip.
SINGLE_CHIP_HEADLINE = {
    "metric": "gpt2_125m_train_mfu_single_chip",
    "mfu": 0.4392,
    "device_kind": "TPU v5 lite",
}


def bench(plan_name: str, steps: int, warmup: int = 3,
          overlap_flags: bool = True) -> dict:
    import jax

    from distributed_training_tpu.config import Config
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               SyntheticLMDataset)
    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.parallel import overlap as overlap_lib
    from distributed_training_tpu.parallel import planner
    from distributed_training_tpu.runtime import fake_cpu_runtime
    from distributed_training_tpu.train.trainer import Trainer
    from distributed_training_tpu.utils.metrics import compute_mfu

    plan = planner.load_plan(plan_name)

    cfg = Config()
    cfg.train.sharding_plan = plan_name
    cfg.train.parallel_strategy = plan.base_strategy
    cfg.train.batch_size = plan.batch_per_shard
    cfg.train.optimizer = plan.inputs.get("optimizer", "adamw")
    cfg.train.dtype = plan.inputs.get("model_kwargs", {}).get(
        "dtype", "float32")
    cfg.train.min_shard_elems = plan.inputs.get("min_shard_elems", 1)
    cfg.train.log_every = 0
    cfg.train.collectives_audit = False  # audited explicitly below

    if jax.default_backend() == "cpu":
        rt = fake_cpu_runtime(plan.devices,
                              **{a: s for a, s in plan.mesh.items()
                                 if a != "dp"})
    else:  # pragma: no cover - real-slice path
        from distributed_training_tpu.runtime import initialize_runtime
        plan_applied = planner.apply_plan_to_config(cfg)
        del plan_applied
        rt = initialize_runtime(cfg)
    planner.check_plan_runtime(plan, rt.spec)

    model = build_model("transformer", **planner.model_kwargs_for(plan))
    ds = SyntheticLMDataset(
        size=max(plan.global_batch * 2, 64), seq_len=plan.seq_len,
        vocab_size=model.cfg.vocab_size, seed=0)
    loader = ShardedDataLoader(ds, rt,
                               batch_size=plan.batch_per_shard,
                               shuffle=False)
    trainer = Trainer(cfg, rt, model, loader)

    batches = iter(loader.epoch(0))
    first = next(batches)
    t_compile0 = time.perf_counter()
    metrics = trainer.train_step(first)
    loss_first = float(metrics["loss"])
    compile_s = time.perf_counter() - t_compile0
    for _ in range(warmup - 1):
        metrics = trainer.train_step(next(batches, first))

    t0 = time.perf_counter()
    for _ in range(steps):
        metrics = trainer.train_step(next(batches, first))
    # One deliberate drain at the end of the measured region: steps
    # dispatch async, so the clock must stop only when the LAST step's
    # result is real (the once-per-measurement sync, not per-step).
    loss_last = float(metrics["loss"])
    elapsed = time.perf_counter() - t0

    tokens_per_step = loader.global_batch * plan.seq_len
    tokens_per_sec = tokens_per_step * steps / elapsed
    flops_per_sec_per_chip = (
        model.flops_per_token(plan.seq_len) * tokens_per_sec
        / rt.num_devices)
    mfu = compute_mfu(flops_per_sec_per_chip, rt.device_kind)

    # Reshard cleanliness of the program that was JUST measured: the
    # same fd-capture parse the SPMD audit ratchet gates on.
    coll = trainer.collectives_report(
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                 sharding=trainer.batch_sharding)
         for k, v in first.items()})

    if not (loss_last == loss_last and loss_first == loss_first):
        raise RuntimeError("measured run produced NaN loss")

    return {
        "schema": 1,
        "metric": "multichip_planned_train",
        "dryrun": False,
        "n_devices": rt.num_devices,
        "device_kind": rt.device_kind,
        "platform": rt.platform,
        "mesh": {a: s for a, s in rt.spec.as_dict().items() if s > 1},
        "steps_measured": steps,
        "warmup_steps": warmup,
        "compile_s": round(compile_s, 2),
        "step_time_ms": round(1e3 * elapsed / steps, 3),
        "tokens_per_step": tokens_per_step,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "tokens_per_sec_per_chip": round(
            tokens_per_sec / rt.num_devices, 1),
        "mfu": round(mfu, 4),
        "loss_first": round(loss_first, 4),
        "loss_last": round(loss_last, 4),
        "spmd_reshard_warnings": coll["spmd_reshard_warnings"],
        "collective_bytes_per_step": coll["bytes_per_step"],
        # Scheduler/overlap provenance (docs/performance.md): the
        # flags THIS measurement ran under, so r06-vs-r07 style
        # comparisons are attributable to the schedule, not folklore.
        "xla_overlap_flags": {
            "enabled": overlap_flags,
            "derived": plan.xla_overlap_flags(rt.platform),
            "active": overlap_lib.active_in_env(
                plan.xla_overlap_flags(rt.platform)),
            "xla_flags_env": os.environ.get("XLA_FLAGS", ""),
        },
        # Which cost model scored the plan (measured calibration
        # table vs nominal constants) — parallel/planner.py
        # provenance, embedded so the ledger entry stands alone.
        "calibration": plan.provenance.get(
            "calibration", {"source": "nominal", "fingerprint": None}),
        "plan": {
            "name": plan.name,
            "fingerprint": plan.fingerprint(),
            "base_strategy": plan.base_strategy,
            "remat": plan.remat,
            "batch_per_shard": plan.batch_per_shard,
            "seq_len": plan.seq_len,
            "score": plan.provenance.get("score", {}).get("score"),
            "ranking_size": len(plan.provenance.get("ranking", [])),
        },
        "single_chip_headline": SINGLE_CHIP_HEADLINE,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Measured multichip benchmark from the committed "
                    "auto-parallelism plan")
    ap.add_argument("--plan", default="multichip_8dev",
                    help="committed plan name or path")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the ledger entry here "
                         "(default: stdout only)")
    ap.add_argument("--no-overlap-flags", action="store_true",
                    help="measure WITHOUT the plan-derived XLA "
                         "latency-hiding flags (reproduces the "
                         "pre-r07 unscheduled behavior)")
    ap.add_argument("--compare", default=None, metavar="ENTRY",
                    help="embed a comparison block against an "
                         "existing ledger entry (e.g. "
                         "MULTICHIP_r06.json)")
    args = ap.parse_args(argv)

    # Device-less-friendly defaults: CPU backend with enough fake
    # devices for the plan, forced before the first backend init
    # (a real-TPU run sets JAX_PLATFORMS=tpu explicitly).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from distributed_training_tpu.parallel import overlap, planner
    plan = planner.load_plan(args.plan)
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count"
                f"={plan.devices}").strip()
    if not args.no_overlap_flags:
        # Scheduled comms/compute overlap: must land in XLA_FLAGS
        # before the first backend init so the trainer's implicit
        # step compile runs the latency-hiding schedule.
        applied = overlap.apply_to_env(
            plan.xla_overlap_flags(overlap.platform_from_env("cpu")))
        if applied:
            print(f"[bench_multichip] overlap flags: {applied}",
                  file=sys.stderr)
    entry = bench(args.plan, steps=args.steps, warmup=args.warmup,
                  overlap_flags=not args.no_overlap_flags)
    if args.compare:
        with open(args.compare, encoding="utf-8") as f:
            ref = json.load(f)
        entry["compared_to"] = {
            "entry": os.path.basename(args.compare),
            "step_time_ms": ref.get("step_time_ms"),
            "tokens_per_sec": ref.get("tokens_per_sec"),
            "mesh": ref.get("mesh"),
            "step_time_speedup": (
                round(ref["step_time_ms"] / entry["step_time_ms"], 4)
                if ref.get("step_time_ms") else None),
        }
    text = json.dumps(entry, indent=1, sort_keys=True) + "\n"
    sys.stdout.write(text)
    if entry["spmd_reshard_warnings"]:
        print("[bench_multichip] FAIL: measured program has "
              f"{entry['spmd_reshard_warnings']} involuntary-reshard "
              "warning(s)", file=sys.stderr)
        return 1
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text)
        print(f"[bench_multichip] wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
