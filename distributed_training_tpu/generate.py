"""Generation CLI: sample from a trained checkpoint.

No counterpart exists in the reference (its models are Linear
regressors, src/distributed_trainer.py:199); this closes the loop the
transformer families open — train with the trainer CLI, then:

    # Byte-level models (vocab 256): the prompt is literal UTF-8 —
    # no tokenizer download, nothing to install.
    python -m distributed_training_tpu.generate \
        --run-dir outputs/default --prompt "def main(" \
        --max-new-tokens 128 --temperature 0.8 --top-k 40

    # Token models: ids in, ids out.
    python -m distributed_training_tpu.generate \
        --run-dir outputs/gpt2 --prompt-ids 50256,318 -n 32

The model is rebuilt from the run's own ``resolved_config.yaml`` (the
exact architecture that trained) and params come from the newest step
under the run's checkpoint dir — or pass ``--artifact`` for a
consolidated single-file export (checkpoint/export.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _load_run_config(run_dir: str):
    from distributed_training_tpu.config import config_from_dict

    import yaml

    path = os.path.join(run_dir, "resolved_config.yaml")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{path} not found — point --run-dir at a training run "
            "directory (<run.output_dir>/<run.experiment_name>)")
    with open(path) as f:
        return config_from_dict(yaml.safe_load(f))


def _build_model_from_cfg(cfg):
    """Rebuild the exact trained architecture from a run's resolved
    config (shared by the generate and eval CLIs — the dtype-pop rule
    must not drift between them)."""
    from distributed_training_tpu.models import build_model

    model_kwargs = dict(cfg.model.kwargs)
    model_dtype = model_kwargs.pop("dtype", cfg.train.dtype)
    return build_model(cfg.model.name, loss=cfg.train.loss,
                       dtype=model_dtype, **model_kwargs)


def _restore_params(run_dir: str, snapshot_path: str,
                    step: int | None):
    """Newest (or given) step's params onto the local default device
    (checkpoint/export.py::restore_step_local). ``snapshot_path`` was
    anchored absolute on the TRAINING machine; when a copied run dir
    no longer has it, fall back to the checkpoint dir inside
    ``run_dir`` itself (the host-side-sampling use case)."""
    from distributed_training_tpu.checkpoint.export import (
        restore_step_local,
    )

    ckpt_dir = snapshot_path
    if not os.path.isdir(ckpt_dir):
        local = os.path.join(run_dir,
                             os.path.basename(snapshot_path.rstrip(
                                 os.sep)) or "checkpoints")
        if not os.path.isdir(local):
            raise FileNotFoundError(
                f"no checkpoint dir at {snapshot_path} (from the "
                f"run's resolved config) nor at {local}")
        ckpt_dir = local
    state, step = restore_step_local(ckpt_dir, step)
    return state["params"], step


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dtt-generate",
        description="Sample from a trained checkpoint")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--run-dir",
                     help="training run dir (holds resolved_config."
                          "yaml + checkpoints)")
    src.add_argument("--artifact",
                     help="consolidated single-file export "
                          "(checkpoint/export.py); artifacts written "
                          "by this framework carry the architecture "
                          "in their meta — --model-name/--model-kwargs "
                          "override or fill in for foreign artifacts")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: newest)")
    prompt = p.add_mutually_exclusive_group(required=True)
    prompt.add_argument("--prompt",
                        help="UTF-8 text prompt (byte-vocab models)")
    prompt.add_argument("--prompt-ids",
                        help="comma-separated token ids")
    p.add_argument("-n", "--max-new-tokens", type=int, default=64)
    p.add_argument("--decode", choices=("paged", "fused"),
                   default="paged",
                   help="greedy decode path: 'paged' (default) runs "
                        "the serving KV-cache decode step "
                        "(serving/engine.py — token-for-token equal "
                        "to the full-context path, pinned by test); "
                        "'fused' keeps the model's dense-cache "
                        "generate loop. Sampling (temperature > 0) "
                        "always uses 'fused' for rng-stream "
                        "stability.")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--model-name", default=None)
    p.add_argument("--model-kwargs", default="{}",
                   help="JSON dict (with --artifact)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_argparser().parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.runtime import enable_compile_cache

    enable_compile_cache()

    if args.run_dir:
        cfg = _load_run_config(args.run_dir)
        model = _build_model_from_cfg(cfg)
        params, step = _restore_params(args.run_dir,
                                       cfg.train.snapshot_path,
                                       args.step)
    else:
        if args.step is not None:
            raise ValueError(
                "--step selects a step inside a run dir; a "
                "consolidated artifact holds exactly one step "
                "(re-export with checkpoint/export.py --step N)")
        from distributed_training_tpu.checkpoint.consolidate import (
            load_consolidated,
        )
        state, meta = load_consolidated(args.artifact)
        name = args.model_name or meta.get("model_name")
        if not name:
            raise ValueError(
                "--artifact carries no architecture meta (foreign or "
                "pre-r4 export) — pass --model-name and "
                "--model-kwargs")
        # Meta fills in, explicit CLI flags win per-key ("override or
        # fill in") — regardless of which of the two flags was given.
        kwargs = dict(meta.get("model_kwargs") or {})
        kwargs.setdefault("dtype", meta.get("model_dtype", "float32"))
        kwargs.setdefault("loss", meta.get("loss", "auto"))
        kwargs.update(json.loads(args.model_kwargs))
        model = build_model(name, **kwargs)
        params = jax.tree.map(jnp.asarray, state["params"])
        step = meta.get("step", -1)

    if not hasattr(model, "generate"):
        raise ValueError(
            f"model family '{type(model).__name__}' has no "
            "autoregressive decode path — generation needs a "
            "transformer-family checkpoint")
    vocab = model.cfg.vocab_size
    if args.prompt is not None:
        if vocab != 256:
            raise ValueError(
                f"--prompt is UTF-8 bytes, which needs a byte-vocab "
                f"(256) model; this one has vocab {vocab} — pass "
                "--prompt-ids instead")
        ids = np.frombuffer(args.prompt.encode("utf-8"),
                            dtype=np.uint8).astype(np.int32)
    else:
        ids = np.asarray([int(t) for t in
                          args.prompt_ids.split(",")], np.int32)
        if ids.size and (ids.min() < 0 or ids.max() >= vocab):
            raise ValueError(
                f"prompt ids must be in [0, {vocab}), got "
                f"[{ids.min()}, {ids.max()}]")
    if ids.size == 0:
        raise ValueError("empty prompt")

    paged = (args.decode == "paged" and args.temperature <= 0
             and hasattr(model, "serving_block")
             and getattr(model.cfg, "moe_num_experts", 0) == 0)
    if paged:
        # The serving decode path: a one-slot continuous-batching
        # engine over the paged KV cache — each token reads only the
        # cache, never the full context (the serving subsystem's
        # step, reused; parity with the full-context argmax is
        # pinned in tests/test_generate_cli.py).
        from distributed_training_tpu.serving.engine import (
            Engine, EngineConfig)
        page = 16
        total = int(ids.size) + args.max_new_tokens
        # Pool capacity: pages for the whole request, capped at the
        # model's window FLOORED to a page multiple (the cache
        # requires it). A request that only fits the un-floored
        # window takes the fused path below instead of failing.
        model_cap = model.cfg.max_seq_len // page * page
        max_len = min(-(-total // page) * page, model_cap)
        if total > max_len:
            paged = False
        else:
            # One request: nothing to share a prefix with.
            eng = Engine(model, params, EngineConfig(
                max_batch=1, page_size=page,
                num_pages=-(-max_len // page) + 1,
                max_seq_len=max_len,
                prefill_chunk=min(64, max_len),
                prefix_sharing=False))
            out_ids = np.asarray(
                eng.generate(ids, args.max_new_tokens), np.int32)
    if not paged:
        prompt = jnp.asarray(ids)[None, :]
        rng = jax.random.PRNGKey(args.seed)
        out = model.generate(params, prompt,
                             max_new_tokens=args.max_new_tokens,
                             temperature=args.temperature,
                             top_k=args.top_k, rng=rng)
        out_ids = np.asarray(out[0])
    print(f"# step={step} prompt_tokens={ids.size} "
          f"sampled={out_ids.size}", file=sys.stderr)
    if vocab == 256:
        print(bytes(out_ids.astype(np.uint8)).decode(
            "utf-8", errors="replace"))
    else:
        print(",".join(str(int(t)) for t in out_ids))
    return 0


if __name__ == "__main__":
    sys.exit(main())
