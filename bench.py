#!/usr/bin/env python
"""GPT-2 125M causal-LM training throughput on the accelerator JAX finds.

One process, in-process measurement. Prints ONE JSON line on stdout:
``{"metric": ..., "value": N, "unit": "mfu", "vs_baseline": N,
"platform": ..., "device_kind": ..., "num_devices": N, "detail": {...}}``
— or fails with a non-zero exit code and no result line: no accelerator,
an out-of-memory batch, a non-finite loss and any other error are all
failures, never a smaller or an older number.

``vs_baseline`` is value / 0.4 — the BASELINE.json north-star MFU target
(the reference publishes no numbers of its own; SURVEY.md §6).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEQ_LEN = 1024
BATCH = 32
# "mlp" remat drops only the (B, S, 4D) MLP hidden tensors — the
# residual class that does not fit a 16 GB chip at batch 16/32 (six
# 1.12 GiB stacked buffers); recompute is wi-matmul + gelu, ~+4% step
# FLOPs. Sweeps override via measure(..., remat=False, ...).
HEADLINE_MODEL_KWARGS = {"remat": True, "remat_policy": "mlp"}
WARMUP_STEPS = 3
TIMED_STEPS = 20


def _phase(name: str, **kv) -> None:
    extra = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[bench] phase={name} {extra}".rstrip(), file=sys.stderr,
          flush=True)


def measure(batch_size: int, seq_len: int = SEQ_LEN,
            warmup_steps: int = WARMUP_STEPS,
            timed_steps: int = TIMED_STEPS,
            phase=_phase, **model_kwargs) -> dict:
    """The measurement core (shared with benchmarks/sweep_mfu.py so the
    sweep times exactly what the bench reports): build the gpt2_125m
    trainer at ``batch_size``, warm up, time ``timed_steps`` steps, and
    return mfu/throughput detail."""
    import jax
    import numpy as np

    from distributed_training_tpu.config import Config
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               SyntheticLMDataset)
    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.runtime import initialize_runtime
    from distributed_training_tpu.train.trainer import Trainer
    from distributed_training_tpu.utils.metrics import peak_flops_per_chip

    cfg = Config()
    cfg.train.batch_size = batch_size
    cfg.train.optimizer = "adamw"
    cfg.train.learning_rate = 6e-4
    cfg.train.dtype = "bfloat16"
    cfg.train.log_every = 0
    cfg.train.parallel_strategy = "ddp"

    model_kwargs = {**HEADLINE_MODEL_KWARGS, **model_kwargs}
    phase("init_runtime")
    rt = initialize_runtime(cfg)
    phase("build_model", batch=batch_size, seq_len=seq_len,
          **model_kwargs)
    model = build_model("gpt2_125m", dtype="bfloat16", **model_kwargs)
    ds = SyntheticLMDataset(
        size=max(64, batch_size * rt.data_shard_count),
        seq_len=seq_len, vocab_size=50257, seed=0)
    loader = ShardedDataLoader(ds, rt, batch_size=batch_size,
                               shuffle=False)
    trainer = Trainer(cfg, rt, model, loader)
    batch = next(iter(loader.epoch(0)))

    phase("compile_and_warmup", steps=warmup_steps)
    t_compile = time.perf_counter()
    for _ in range(warmup_steps):
        metrics = trainer.train_step(batch)
    jax.block_until_ready(metrics["loss"])
    phase("warmup_done",
          seconds=round(time.perf_counter() - t_compile, 1))

    phase("measure", steps=timed_steps)
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        metrics = trainer.train_step(batch)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    steps_per_sec = timed_steps / dt
    tokens_per_sec = steps_per_sec * loader.global_batch * seq_len
    mfu = (tokens_per_sec * model.flops_per_token(seq_len)
           / rt.num_devices / peak_flops_per_chip(rt.device_kind))
    return {
        "mfu": float(mfu),
        "tokens_per_sec_per_chip": round(
            tokens_per_sec / rt.num_devices, 1),
        "step_time_ms": round(1000 * dt / timed_steps, 2),
        "batch": batch_size,
        "seq_len": seq_len,
        "platform": rt.platform,
        "device_kind": rt.device_kind,
        "num_devices": rt.num_devices,
        "loss_finite": bool(np.isfinite(float(metrics["loss"]))),
        # Effective (merged) kwargs — the model actually measured, so
        # sweep rows are never confounded by the headline defaults.
        "model_kwargs": dict(model_kwargs),
    }


def main() -> int:
    import jax

    from distributed_training_tpu.runtime import enable_compile_cache

    platform = jax.default_backend()
    if platform == "cpu":
        print("[bench] no accelerator: JAX's default backend is the "
              "CPU; this benchmark measures a chip and reports "
              "nothing without one", file=sys.stderr)
        return 1
    enable_compile_cache()
    m = measure(BATCH)
    if not m["loss_finite"]:
        print(f"[bench] non-finite loss after the timed steps: {m}",
              file=sys.stderr)
        return 1
    mfu = m.pop("mfu")
    print(json.dumps({
        "metric": "gpt2_125m_train_mfu_single_chip",
        "value": round(mfu, 4),
        "unit": "mfu",
        "vs_baseline": round(mfu / 0.4, 4),
        "platform": m.pop("platform"),
        "device_kind": m.pop("device_kind"),
        "num_devices": m.pop("num_devices"),
        "detail": m,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
