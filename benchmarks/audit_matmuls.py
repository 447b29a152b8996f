#!/usr/bin/env python
"""Offline matmul/dtype audit of the exact headline train step.

Walks the jaxpr of the full jitted train step (fwd + bwd + optimizer,
the same program ``bench.py`` times) and enumerates every
``dot_general`` — including those inside ``scan`` bodies (multiplied by
trip count), remat'd regions, custom-VJP calls, and Pallas kernels
(multiplied by their grid) — reporting operand dtypes, shapes, and
estimated FLOPs per dot.

Why it exists: on TPU the MXU runs bf16 x bf16 -> f32 at full rate;
an operand left (or upcast) in f32 silently drops the matmul to the
fractional f32 rate. The r4 chip window measured identical tok/s at
batch 8 and batch 32 — a per-token efficiency wall — and this audit is
the zero-chip-time way to find dots that waste MXU rate. It found the
flash-backward dp/dv f32 upcasts (fixed: ops/flash_attention.py keeps
MXU operands in the input dtype).

Runs on CPU (no chip needed):

    JAX_PLATFORMS=cpu python benchmarks/audit_matmuls.py --batch 32 \
        --model-kwargs '{"remat": true, "remat_policy": "mlp"}'

Output: one human table to stderr + one JSON summary line to stdout
(total dot FLOPs by operand-dtype pair, plus the top offenders with an
f32 operand).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _dot_flops(eqn, mult: float) -> float:
    """2*B*M*N*K for a dot_general, scaled by the enclosing trip count."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    k = math.prod(lhs.shape[d] for d in lc) or 1
    b = math.prod(lhs.shape[d] for d in lb) or 1
    m = math.prod(lhs.shape[d] for d in range(len(lhs.shape))
                  if d not in set(lc) | set(lb)) or 1
    n = math.prod(rhs.shape[d] for d in range(len(rhs.shape))
                  if d not in set(rc) | set(rb)) or 1
    return 2.0 * b * m * n * k * mult


def _sub_jaxprs(eqn):
    """Yield (jaxpr, extra_multiplier) for every jaxpr nested in eqn."""
    import jax.extend.core as jex_core

    name = eqn.primitive.name
    mult = 1.0
    if name == "scan":
        mult = float(eqn.params.get("length", 1))
    elif name == "pallas_call":
        gm = eqn.params.get("grid_mapping")
        grid = getattr(gm, "grid", None) or ()
        mult = float(math.prod(int(g) for g in grid) or 1)
    elif name == "while":
        # Trip count is dynamic; assume 1 and tag via the name.
        mult = 1.0
    for v in eqn.params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr, mult
        elif isinstance(v, jex_core.Jaxpr):
            yield v, mult
        elif isinstance(v, (list, tuple)):
            for item in v:
                if isinstance(item, jex_core.ClosedJaxpr):
                    yield item.jaxpr, mult
                elif isinstance(item, jex_core.Jaxpr):
                    yield item, mult


def _walk(jaxpr, mult: float, path: str, out: list) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
            out.append({
                "path": path,
                "lhs": (str(lhs.dtype), tuple(lhs.shape)),
                "rhs": (str(rhs.dtype), tuple(rhs.shape)),
                "out_dtype": str(eqn.outvars[0].aval.dtype),
                "preferred": str(eqn.params.get(
                    "preferred_element_type", "")),
                "flops": _dot_flops(eqn, mult),
                "mult": mult,
            })
        elif name in ("conv_general_dilated",):
            o = eqn.outvars[0].aval
            lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
            out.append({
                "path": path, "conv": True,
                "lhs": (str(lhs.dtype), tuple(lhs.shape)),
                "rhs": (str(rhs.dtype), tuple(rhs.shape)),
                "out_dtype": str(o.dtype), "preferred": "",
                "flops": 2.0 * math.prod(o.shape)
                * math.prod(rhs.shape) / max(1, rhs.shape[-1])
                * mult,
                "mult": mult,
            })
        for sub, m2 in _sub_jaxprs(eqn):
            _walk(sub, mult * m2, f"{path}/{name}", out)


def audit(batch: int, seq_len: int, model_kwargs: dict) -> dict:
    import jax
    import numpy as np

    # An offline audit of the traced program: it runs on the CPU
    # whatever the machine holds, and never touches a chip.
    jax.config.update("jax_platforms", "cpu")

    from distributed_training_tpu.config import Config
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               SyntheticLMDataset)
    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.runtime import initialize_runtime
    from distributed_training_tpu.train.trainer import Trainer

    cfg = Config()
    cfg.train.batch_size = batch
    cfg.train.optimizer = "adamw"
    cfg.train.dtype = "bfloat16"
    cfg.train.log_every = 0
    cfg.train.parallel_strategy = "ddp"
    rt = initialize_runtime(cfg)
    model = build_model("gpt2_125m", dtype="bfloat16", **model_kwargs)
    ds = SyntheticLMDataset(size=max(64, batch), seq_len=seq_len,
                            vocab_size=model_kwargs.get("vocab_size",
                                                        50257), seed=0)
    loader = ShardedDataLoader(ds, rt, batch_size=batch, shuffle=False)
    trainer = Trainer(cfg, rt, model, loader)
    b = next(iter(loader.epoch(0)))

    closed = jax.make_jaxpr(
        lambda s, bt, r: trainer._step_fn(s, bt, r))(
            trainer.state, b, jax.random.PRNGKey(0))
    dots: list = []
    _walk(closed.jaxpr, 1.0, "", dots)

    by_pair: dict = defaultdict(float)
    for d in dots:
        by_pair[f"{d['lhs'][0]}x{d['rhs'][0]}"] += d["flops"]
    total = sum(by_pair.values()) or 1.0
    f32_heavy = sorted(
        (d for d in dots
         if ("float32" in (d["lhs"][0], d["rhs"][0])
             and d["flops"] > 1e9)),
        key=lambda d: -d["flops"])
    return {
        "batch": batch, "seq_len": seq_len,
        "model_kwargs": model_kwargs,
        "n_dots": len(dots),
        "total_dot_flops": total,
        "flops_by_dtype_pair": {
            k: {"flops": v, "pct": round(100 * v / total, 2)}
            for k, v in sorted(by_pair.items(), key=lambda kv: -kv[1])},
        "f32_offenders": [
            {"path": d["path"], "lhs": [d["lhs"][0], list(d["lhs"][1])],
             "rhs": [d["rhs"][0], list(d["rhs"][1])],
             "pct_of_total": round(100 * d["flops"] / total, 2),
             "mult": d["mult"]}
            for d in f32_heavy[:20]],
        "top_dots": [
            {"path": d["path"], "lhs": [d["lhs"][0], list(d["lhs"][1])],
             "rhs": [d["rhs"][0], list(d["rhs"][1])],
             "pct_of_total": round(100 * d["flops"] / total, 2)}
            for d in sorted(dots, key=lambda d: -d["flops"])[:12]],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--model-kwargs",
                    default='{"remat": true, "remat_policy": "mlp"}')
    args = ap.parse_args()
    rep = audit(args.batch, args.seq_len,
                json.loads(args.model_kwargs))
    for pair, row in rep["flops_by_dtype_pair"].items():
        print(f"{pair:24s} {row['pct']:6.2f}%  "
              f"{row['flops'] / 1e12:8.2f} TF", file=sys.stderr)
    for d in rep["f32_offenders"]:
        print(f"F32 OFFENDER {d['pct_of_total']:5.2f}% "
              f"{d['lhs']} x {d['rhs']}  at {d['path']}",
              file=sys.stderr)
    print(json.dumps(rep))


if __name__ == "__main__":
    main()
