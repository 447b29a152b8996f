#!/usr/bin/env python
"""Measured tokens/sec for the BASELINE 1B path on ONE chip.

Runs the FULL transformer_1b (24 layers, d=2048, untied rope — not the
shrunken test variant) on a single v5e with adafactor (factored second
moment ~2% of params — AdamW's 10.5 GiB of fp32 moments cannot share
16 GiB HBM with 5.3 GiB params + 5.3 GiB grads at step peak). fsdp=1
is expected on one chip; the deliverable is the measured config path,
not scale.

Prints one JSON line for the FIRST attempt in the best-first ladder
that survives: lighter remat policies / larger batch before the
r4-measured full-remat batch-1 safety net, then seq_len 1024 → 512,
then adafactor → SGD (each fallback is recorded). Compile the ladder
device-less first (benchmarks/precompile_points.py) — what is left is
allocator-level OOM risk.

    python benchmarks/bench_1b_single_chip.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _is_oom(e: Exception) -> bool:
    """Real device-OOM signatures only — a bare "allocat" substring
    would match any message mentioning "allocate" and reroute
    deterministic failures down the ladder."""
    msg = str(e).lower()
    return ("resource_exhausted" in msg
            or "out of memory" in msg
            or "ran out of memory" in msg
            or "failed to allocate" in msg
            or "allocation failure" in msg
            or ("hbm" in msg and "exceed" in msg))


# Best-first: r4 measured 0.320 MFU with batch 1 + full remat — which
# re-runs the whole block forward every backward (~+33% step FLOPs).
# The estimator prices the lighter policies INSIDE 15.75 GiB (params
# 5.27 + grads 5.27 + adafactor 0.11 fixed): mlp@batch2 = 14.0 GiB,
# mlp_pre@batch1 = 13.3, mlp@batch1 = 12.5, full@batch1 = 11.4 (the
# measured r4 config, now the safety net). Each OOM falls through.
ATTEMPTS = [
    dict(seq_len=1024, optimizer="adafactor", offload=False,
         batch=2, remat_policy="mlp"),
    dict(seq_len=1024, optimizer="adafactor", offload=False,
         batch=1, remat_policy="mlp_pre"),
    dict(seq_len=1024, optimizer="adafactor", offload=False,
         batch=1, remat_policy="mlp"),
    dict(seq_len=1024, optimizer="adafactor", offload=False),
    dict(seq_len=512, optimizer="adafactor", offload=False),
    dict(seq_len=512, optimizer="sgd", offload=False),
]
STEPS = max(1, int(os.environ.get("DTT_1B_STEPS", "5")))
WARMUP = max(1, int(os.environ.get("DTT_1B_WARMUP", "2")))

# First rung of the safety net: the r4-measured full-remat batch-1
# config (no remat_policy override) and everything after it. Rungs
# BEFORE it are speculative, never-chip-measured configs — a non-OOM
# failure there (a compile that dies near the memory ceiling) must not
# forfeit the run before the known-good rung was even attempted, so
# they fall through on ANY exception; the hard break is reserved for
# non-OOM errors on the safety net itself.
SAFETY_NET_FROM = next(i for i, a in enumerate(ATTEMPTS)
                       if "remat_policy" not in a)


def run(seq_len: int, optimizer: str, offload: bool,
        model_name: str = "transformer_1b",
        model_kwargs: dict | None = None,
        vocab_size: int = 50304, batch: int = 1,
        remat_policy: str = "full") -> dict:
    """``model_name``/``model_kwargs``/``vocab_size`` exist so tests
    can drive the EXACT measurement path (adafactor + remat + bf16 +
    Trainer) at toy scale on CPU; production callers use the
    ATTEMPTS ladder's values."""
    import jax

    from distributed_training_tpu.config import Config
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               SyntheticLMDataset)
    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.runtime import initialize_runtime
    from distributed_training_tpu.train.trainer import Trainer
    from distributed_training_tpu.utils.metrics import peak_flops_per_chip

    cfg = Config()
    cfg.train.batch_size = batch
    cfg.train.optimizer = optimizer
    cfg.train.learning_rate = 2e-4
    cfg.train.dtype = "bfloat16"
    cfg.train.log_every = 0
    cfg.train.parallel_strategy = "ddp"
    cfg.train.offload_opt_state = offload

    rt = initialize_runtime(cfg)
    model = build_model(model_name, dtype="bfloat16",
                        remat=True, remat_policy=remat_policy,
                        **(model_kwargs or {}))
    ds = SyntheticLMDataset(size=8, seq_len=seq_len,
                            vocab_size=vocab_size, seed=0)
    loader = ShardedDataLoader(ds, rt, batch_size=batch, shuffle=False)
    trainer = Trainer(cfg, rt, model, loader)
    # batch_data, NOT batch: rebinding the int parameter here would
    # put jax.Arrays into the result dict's "batch" field and crash
    # json.dumps AFTER a successful chip measurement (caught in
    # review before it could burn a window).
    batch_data = next(iter(loader.epoch(0)))

    t0 = time.perf_counter()
    for _ in range(WARMUP):
        metrics = trainer.train_step(batch_data)
    jax.block_until_ready(metrics["loss"])
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(STEPS):
        metrics = trainer.train_step(batch_data)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec = STEPS * loader.global_batch * seq_len / dt
    mfu = (tokens_per_sec * model.flops_per_token(seq_len)
           / rt.num_devices / peak_flops_per_chip(rt.device_kind))
    return {
        "metric": "transformer_1b_train_single_chip",
        "tokens_per_sec_per_chip": round(
            tokens_per_sec / rt.num_devices, 1),
        "mfu": round(float(mfu), 4),
        "step_time_ms": round(1000 * dt / STEPS, 1),
        "seq_len": seq_len,
        "batch": batch,
        "optimizer": optimizer,
        "offload_opt_state": offload,
        "remat_policy": remat_policy,
        "compile_plus_warmup_s": round(compile_s, 1),
        "device_kind": rt.device_kind,
        "loss": round(float(metrics["loss"]), 4),
    }


def main() -> int:
    errors = []
    for i, att in enumerate(ATTEMPTS):
        try:
            rec = run(**att)
            rec["fallbacks"] = errors
            print(json.dumps(rec), flush=True)
            return 0
        except Exception as e:  # noqa: BLE001 — fall through the ladder
            errors.append({"attempt": att,
                           "error": f"{type(e).__name__}: {e}"[:300]})
            if i >= SAFETY_NET_FROM and not _is_oom(e):
                break
    print(json.dumps({"metric": "transformer_1b_train_single_chip",
                      "error": errors}), flush=True)
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
