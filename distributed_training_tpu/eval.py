"""Offline evaluation CLI: score a trained checkpoint on a dataset.

Completes the train → eval → generate loop (the reference evaluates
nothing; its loss is the degenerate single-logit xent — SURVEY.md §8
B5). The model is rebuilt from the run's resolved_config.yaml, params
restore topology-free from the newest (or a named) step, and the
dataset defaults to the run's own training dataset — override it to
score held-out corpora:

    python -m distributed_training_tpu.eval --run-dir outputs/default
    python -m distributed_training_tpu.eval --run-dir outputs/byte \
        --dataset bytes_file --dataset-kwargs '{"path": "corpus.txt",
        "seq_len": 256}' --batch-size 8 --max-batches 50

Prints ONE JSON line: {"loss": ..., "perplexity": ..., "tokens": ...,
"batches": ..., "step": ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dtt-eval",
        description="Score a trained checkpoint on a dataset")
    p.add_argument("--run-dir", required=True,
                   help="training run dir (resolved_config.yaml + "
                        "checkpoints)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: newest)")
    p.add_argument("--dataset", default=None,
                   help="dataset registry name (default: the run's "
                        "train.dataset)")
    p.add_argument("--dataset-kwargs", default=None,
                   help="JSON dict (default: the run's "
                        "train.dataset_kwargs)")
    p.add_argument("--batch-size", type=int, default=None,
                   help="default: the run's train.batch_size")
    p.add_argument("--max-batches", type=int, default=0,
                   help="0 = the whole dataset")
    p.add_argument("--device", default="auto",
                   help="platform for scoring (auto|tpu|cpu) — the "
                        "run's trained topology is NOT required; eval "
                        "replicates params over whatever is local")
    p.add_argument("--events-jsonl", default=None,
                   help="write telemetry spans/events here (default: "
                        "off; the summarizer CLI reads the stream)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_argparser().parse_args(argv)

    import jax
    import numpy as np

    from distributed_training_tpu import telemetry as telemetry_lib
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               build_dataset)
    from distributed_training_tpu.generate import (
        _build_model_from_cfg, _load_run_config, _restore_params)
    from distributed_training_tpu.runtime import (enable_compile_cache,
                                                  initialize_runtime)

    enable_compile_cache()
    if args.events_jsonl:
        # fresh=False: the natural target is the run's own
        # events.jsonl — eval must append after a run_start marker,
        # never truncate the training run's telemetry.
        telemetry_lib.install(telemetry_lib.Telemetry(
            events_jsonl=args.events_jsonl, fresh=False))

    cfg = _load_run_config(args.run_dir)
    model = _build_model_from_cfg(cfg)
    params, step = _restore_params(args.run_dir,
                                   cfg.train.snapshot_path, args.step)

    # Score on whatever is LOCAL: the run's trained topology (device
    # kind, mesh shape) is frozen in its resolved config and generally
    # does not exist on the scoring machine — reset to a plain
    # data-parallel mesh over the local devices.
    from distributed_training_tpu.config import MeshConfig
    cfg.mesh = MeshConfig()
    cfg.train.device = args.device
    rt = initialize_runtime(cfg)
    if hasattr(model, "bind_mesh"):
        model.bind_mesh(rt.mesh)
    # Params restored single-device; the loader yields mesh-sharded
    # batches — replicate params across the runtime mesh so the jitted
    # score sees one consistent device set.
    from jax.sharding import NamedSharding, PartitionSpec
    params = jax.device_put(
        params, NamedSharding(rt.mesh, PartitionSpec()))
    ds_name = args.dataset or cfg.train.dataset
    # A dataset override starts from EMPTY kwargs: the run's
    # dataset_kwargs belong to its own dataset and are generally
    # invalid for a different one (a silent carry-over would score
    # the wrong corpus parameters).
    if args.dataset_kwargs is not None:
        ds_kwargs = json.loads(args.dataset_kwargs)
    elif args.dataset:
        ds_kwargs = {}
    else:
        ds_kwargs = dict(cfg.train.dataset_kwargs)
    dataset = build_dataset(
        ds_name,
        _defaults={"size": cfg.train.dataset_size,
                   "seed": cfg.train.seed},
        **ds_kwargs)
    # The loader wrap-pads a short final batch to keep shapes static;
    # duplicate rows would bias a held-out score, so only FULL batches
    # are scored — unless the whole dataset is smaller than one global
    # batch (then the padded batch is scored and the output SAYS so).
    batch_size = args.batch_size or cfg.train.batch_size
    loader = ShardedDataLoader(dataset, rt, batch_size=batch_size,
                               shuffle=False)
    full_steps = loader.sampler.num_samples // batch_size
    padded = full_steps == 0
    score_steps = max(full_steps, 1)
    if args.max_batches:
        score_steps = min(score_steps, args.max_batches)

    rng = jax.random.PRNGKey(0)

    @jax.jit
    def score(params, batch):
        loss, _metrics = model.loss(params, batch, rng, train=False)
        return loss

    losses = []
    tokens = 0
    with telemetry_lib.span("eval", run_dir=args.run_dir, step=step):
        for i, batch in enumerate(loader.epoch(0)):
            if i >= score_steps:
                break
            losses.append(float(score(params, batch)))
            first = next(iter(batch.values()))
            tokens += int(np.prod(first.shape))
    if not losses:
        raise ValueError("dataset yielded no batches")
    mean = float(np.mean(losses))
    rec = {
        "loss": round(mean, 6),
        "perplexity": round(float(np.exp(mean)), 4),
        "tokens": tokens,
        "batches": len(losses),
        "step": step,
    }
    if padded:
        rec["padded"] = True  # dataset < one global batch; rows repeat
    telemetry_lib.event("eval_result", **rec)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
