#!/usr/bin/env python
"""Benchmark harness for the five BASELINE.json configs.

The reference publishes no numbers (BASELINE.md), so this harness
*establishes* the baseline: for each named config it trains for a bounded
number of steps and emits one JSON record with the loss curve,
samples/sec/chip, tokens/sec/chip (LM configs), step time, and MFU.

    python benchmarks/run.py --config mlp_cpu
    python benchmarks/run.py --config gpt2_125m_ddp --steps 30
    python benchmarks/run.py --all --out results.json

Configs (BASELINE.json "configs", adapted to the hardware present —
axis sizes shrink to the local device count):

  mlp_cpu        toy MLP, synthetic regression (reference default run)
  resnet18_ddp   ResNet-18, synthetic CIFAR-10 shapes, 8-way DP
  gpt2_125m_ddp  GPT-2 125M, synthetic LM corpus, DP
  tf1b_fsdp      1B-class transformer, FSDP param+optimizer sharding
  tf7b_fsdp      7B-class transformer, FSDP + remat + bf16

On one chip the big configs use scaled-down layer counts unless
--full-size is given (a single v5e cannot hold 7B params + Adam state).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _base(overrides: dict) -> dict:
    cfg = {
        "train.log_every": 0,
        "train.shuffle": False,
        "train.save_every": 0,
    }
    cfg.update(overrides)
    return cfg


CONFIGS: dict = {
    "mlp_cpu": {
        "desc": "toy MLP, synthetic dataset (reference default run: "
                "Linear 20->1, batch 32, SGD 1e-3)",
        "device": "cpu",
        "model": ("mlp", {}),
        "overrides": _base({
            "train.batch_size": 32,
            "train.dataset": "synthetic",
            "train.dataset_kwargs": {"size": 2048, "kind": "linear"},
            "train.learning_rate": 1e-3,
            "train.parallel_strategy": "ddp",
        }),
        "sample_unit": "samples",
    },
    "resnet18_ddp": {
        "desc": "ResNet-18, CIFAR-10-shaped synthetic data, DP",
        "model": ("resnet18", {"num_classes": 10}),
        "overrides": _base({
            "train.batch_size": 64,
            "train.dataset": "synthetic_images",
            "train.dataset_kwargs": {"size": 2048},
            "train.optimizer": "adamw",
            "train.learning_rate": 1e-3,
            "train.parallel_strategy": "ddp",
            "train.dtype": "bfloat16",
        }),
        "sample_unit": "images",
    },
    "gpt2_125m_ddp": {
        "desc": "GPT-2 125M, synthetic LM corpus, DP (same tuned "
                "config as the headline bench.py: batch 32 + "
                "remat_policy='mlp' — see docs/performance.md)",
        "model": ("gpt2_125m", {"attention_impl": "auto",
                                "remat": True, "remat_policy": "mlp"}),
        "seq_len": 1024,
        "overrides": _base({
            "train.batch_size": 32,
            "train.dataset": "synthetic_lm",
            "train.dataset_kwargs": {"size": 128, "seq_len": 1024,
                                     "vocab_size": 50257},
            "train.optimizer": "adamw",
            "train.learning_rate": 6e-4,
            "train.parallel_strategy": "ddp",
            "train.dtype": "bfloat16",
        }),
        "sample_unit": "tokens",
    },
    "tf1b_fsdp": {
        "desc": "1B-class transformer, FSDP full param+optimizer shard",
        "model": ("transformer_1b", {"attention_impl": "auto",
                                     "remat": True}),
        "seq_len": 1024,
        "scaled_kwargs": {"n_layers": 4},
        "overrides": _base({
            "train.batch_size": 4,
            "train.dataset": "synthetic_lm",
            "train.dataset_kwargs": {"size": 64, "seq_len": 1024,
                                     "vocab_size": 50257},
            "train.optimizer": "adamw",
            "train.learning_rate": 3e-4,
            "train.parallel_strategy": "fsdp",
            "train.dtype": "bfloat16",
        }),
        "sample_unit": "tokens",
    },
    "bytes_lm_real": {
        "desc": "byte-level LM on REAL text (this repo's source/docs "
                "prepared into a uint8 memmap shard via data/prepare.py "
                "— the hermetic real-data path; BASELINE config 3's "
                "real-corpus analogue)",
        "model": ("gpt2_125m", {"vocab_size": 256, "d_model": 512,
                                "n_layers": 8, "n_heads": 8,
                                "max_seq_len": 512}),
        "seq_len": 512,
        "prepare_bytes": True,  # build the corpus shard if missing
        "overrides": _base({
            "train.batch_size": 16,
            "train.dataset": "bytes",
            "train.dataset_kwargs": {"path": "", "seq_len": 512},
            "train.optimizer": "adamw",
            "train.learning_rate": 6e-4,
            "train.parallel_strategy": "ddp",
            "train.dtype": "bfloat16",
        }),
        "sample_unit": "tokens",
    },
    "tf7b_fsdp": {
        "desc": "7B-class transformer, FSDP + remat + bf16 "
                "(BASELINE config 5)",
        "model": ("transformer_7b", {"attention_impl": "auto",
                                     "remat": True}),
        "seq_len": 2048,
        "scaled_kwargs": {"n_layers": 2},
        "overrides": _base({
            "train.batch_size": 2,
            "train.dataset": "synthetic_lm",
            "train.dataset_kwargs": {"size": 32, "seq_len": 2048,
                                     "vocab_size": 50257},
            "train.optimizer": "adamw",
            "train.learning_rate": 3e-4,
            "train.parallel_strategy": "fsdp",
            "train.dtype": "bfloat16",
            "train.grad_accum_steps": 1,
        }),
        "sample_unit": "tokens",
    },
}


def run_config(name: str, steps: int, warmup: int,
               full_size: bool) -> dict:
    import jax

    from distributed_training_tpu.config import Config
    from distributed_training_tpu.data import build_dataset
    from distributed_training_tpu.data.loader import ShardedDataLoader
    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.runtime import (enable_compile_cache,
                                                  initialize_runtime)
    from distributed_training_tpu.train.trainer import Trainer
    from distributed_training_tpu.utils.metrics import peak_flops_per_chip

    enable_compile_cache()
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    spec = CONFIGS[name]
    from distributed_training_tpu.config import override_config
    groups: dict = {}
    for path, val in spec["overrides"].items():
        group, leaf = path.split(".", 1)
        groups.setdefault(group, {})[leaf] = val
    cfg = override_config(Config(), **groups)
    if spec.get("device"):
        cfg.train.device = spec["device"]

    if spec.get("prepare_bytes"):
        # Real-text shard: rebuilt each run (sub-second) from this
        # repo's own source/docs — deterministic, hermetic, never
        # stale, and repo-local (a fixed world-readable /tmp name
        # could be pre-created by another user).
        from distributed_training_tpu.data.prepare import prepare_bytes
        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        shard = os.path.join(repo, "benchmarks", "_build",
                             "bench_corpus.bin")
        prepare_bytes(shard, [
            os.path.join(repo, "distributed_training_tpu",
                         "**", "*.py"),
            os.path.join(repo, "docs", "*.md"),
            os.path.join(repo, "*.md"),
        ])
        cfg.train.dataset_kwargs["path"] = shard

    rt = initialize_runtime(cfg)
    model_name, model_kwargs = spec["model"]
    model_kwargs = dict(model_kwargs)
    if not full_size:
        model_kwargs.update(spec.get("scaled_kwargs", {}))
    model = build_model(model_name, dtype=cfg.train.dtype,
                        **model_kwargs)

    ds = build_dataset(cfg.train.dataset, **cfg.train.dataset_kwargs)
    loader = ShardedDataLoader(ds, rt, batch_size=cfg.train.batch_size,
                               shuffle=False)
    trainer = Trainer(cfg, rt, model, loader)

    batches = []
    it = loader.epoch(0)
    for _ in range(max(2, min(steps, len(loader)))):
        try:
            batches.append(next(it))
        except StopIteration:
            break

    losses = []
    for i in range(warmup):
        m = trainer.train_step(batches[i % len(batches)])
    if warmup:
        jax.block_until_ready(m["loss"])

    t0 = time.perf_counter()
    for i in range(steps):
        m = trainer.train_step(batches[i % len(batches)])
        losses.append(m["loss"])
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    losses = [float(x) for x in losses]

    samples_per_step = loader.global_batch
    result = {
        "config": name,
        "desc": spec["desc"],
        "platform": rt.platform,
        "device_kind": rt.device_kind,
        "num_devices": rt.num_devices,
        "full_size": full_size,
        "step_time_ms": round(1000 * dt, 2),
        "samples_per_sec_per_chip": round(
            samples_per_step / dt / rt.num_devices, 2),
        "loss_first": losses[0],
        "loss_last": losses[-1],
        "loss_curve": [round(x, 5) for x in losses],
    }
    seq_len = spec.get("seq_len")
    if seq_len:
        toks = samples_per_step * seq_len / dt / rt.num_devices
        result["tokens_per_sec_per_chip"] = round(toks, 1)
        if hasattr(model, "flops_per_token"):
            mfu = (toks * model.flops_per_token(seq_len)
                   / peak_flops_per_chip(rt.device_kind))
            result["mfu"] = round(float(mfu), 4)
    elif hasattr(model, "flops_per_sample"):
        fps = (samples_per_step / dt / rt.num_devices
               * model.flops_per_sample())
        result["mfu"] = round(
            float(fps / peak_flops_per_chip(rt.device_kind)), 6)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", choices=sorted(CONFIGS), default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--full-size", action="store_true",
                   help="full layer counts (needs a pod, not one chip)")
    p.add_argument("--out", default=None, help="write JSON here too")
    args = p.parse_args(argv)

    names = sorted(CONFIGS) if args.all else [args.config]
    if names == [None]:
        p.error("pass --config NAME or --all")
    if len(names) > 1:
        # One subprocess per config, run one after another: a shared
        # process would leak each config's device allocations into the
        # next measurement (and mlp_cpu's cpu-device selection would
        # poison later TPU configs' backend choice). This parent must
        # never import jax — a chip belongs to one process, and a
        # parent that touched the backend would hold it against its
        # own children.
        import subprocess
        results = []
        timeout_s = 1800
        for n in names:
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--config", n, "--steps", str(args.steps),
                   "--warmup", str(args.warmup)]
            if args.full_size:
                cmd.append("--full-size")
            try:
                proc = subprocess.run(cmd, capture_output=True,
                                      text=True, timeout=timeout_s)
            except subprocess.TimeoutExpired:
                # One hung config must not hang the suite or discard
                # completed results.
                results.append({"config": n, "error":
                                f"timeout after {timeout_s}s"})
                continue
            if proc.returncode != 0:
                results.append({"config": n, "error":
                                proc.stderr.strip()[-300:]})
                continue
            try:
                results.append(json.loads(proc.stdout))
            except ValueError:
                results.append({"config": n, "error":
                                "non-JSON child output: "
                                + proc.stdout.strip()[-200:]})
        payload = results
    else:
        payload = run_config(names[0], args.steps, args.warmup,
                             args.full_size)
    text = json.dumps(payload, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
