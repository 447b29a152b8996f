#!/usr/bin/env python3
"""One expert layer's call (``models/experts.py::expert_layer``: router,
held experts' products, combine; no shared expert, which is one dense
product in either form) timed on the chip as it is, the held experts as
one grouped product, and with the dense form in its place (every held
expert over every row), at the published widths of the four
expert configurations (``conf/model/*.yaml``), bfloat16, over rows from
a decode iteration's 32 to a prompt chunk's 1,024, with weights, rows
and so routing drawn from ``--seed`` (PERF.md section 6).

    python3 benchmarks/expert_form_table.py [--seed N]   # on a TPU

Prints one JSON line a shape (``dense_ms``, ``grouped_ms``, the faster
form, the rows each form computes, the worst difference between the two
forms' outputs) and writes them to ``chiprun_out/expert_form_table.json``.
At the chunk shape it also times the grouped form at other row tiles
(``_TILE_ROWS``). Times are of twenty calls after one, a layer alone:
what decides between two forms, not a benchmark result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (configuration file, model name in the registry)
CONFIGS = {"command-a": "command_a_plus_ep16",
           "smallthinker": "smallthinker_21b_ep4",
           "joyai": "joyai_llm_flash_ep4",
           "dots3": "dots3_note_ep8"}
ROWS = (32, 64, 128, 256, 512, 1024)
CHUNK = 1024


def config(name: str):
    """The model configuration of ``conf/model/<file>.yaml``, bfloat16."""
    import yaml

    from distributed_training_tpu.models import build_model

    with open(os.path.join(REPO, "conf", "model",
                           CONFIGS[name] + ".yaml")) as f:
        conf = yaml.safe_load(f)
    return build_model(conf["name"], dtype="bfloat16",
                       **conf["kwargs"]).cfg


def layer(c, seed: int):
    """One expert layer's parameters at ``c``'s widths: the router over
    all experts (and its selection bias where the model has one), the
    held experts' weights."""
    import jax
    import jax.numpy as jnp

    D, F, E = c.d_model, c.moe_d_ff, c.experts_held
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf = jnp.bfloat16
    m = {"router": 0.02 * jax.random.normal(k[0], (D, c.n_routed_experts),
                                            jnp.float32),
         "wg": (0.02 * jax.random.normal(k[1], (E, D, F))).astype(bf),
         "wu": (0.02 * jax.random.normal(k[2], (E, D, F))).astype(bf),
         "wd": (0.02 * jax.random.normal(k[3], (E, F, D))).astype(bf)}
    if getattr(c, "routed_scaling_factor", None) is not None:
        m["router_bias"] = 1e-3 * jax.random.normal(
            k[4], (c.n_routed_experts,), jnp.float32)
    return m


def dense(act, x, g, local, mine, load, *held):
    """``experts._routed`` as the dense form."""
    from distributed_training_tpu.models import experts

    return experts._dense(act, x, g, local, mine, *held)


def timed(c, m, h, reps: int = 20, **patch) -> tuple:
    """``(ms a call, output, counts)`` of the layer's call compiled with
    ``models/experts.py``'s names in ``patch`` set for its trace."""
    import jax

    from distributed_training_tpu.models import experts

    kept = {k: getattr(experts, k) for k in patch}
    try:
        for k, v in patch.items():
            setattr(experts, k, v)
        # A function of its own a variant: jit keeps what it traced
        # for one function at one set of shapes.
        fn = jax.jit(lambda h, m: experts.expert_layer(h, m, c)
                     ).lower(h, m).compile()
    finally:
        for k, v in kept.items():
            setattr(experts, k, v)
    y, counts = jax.block_until_ready(fn(h, m))
    t0 = time.perf_counter()
    for _ in range(reps):
        last = fn(h, m)
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / reps * 1e3, y, counts


def rows_of(name: str, seed: int) -> list:
    import jax
    import jax.numpy as jnp

    c = config(name)
    m = layer(c, seed)
    rows = []
    for T in ROWS:
        h = jax.random.normal(jax.random.PRNGKey(seed + T),
                              (T, c.d_model), jnp.bfloat16)
        d_ms, yd, cd = timed(c, m, h, _routed=dense)
        g_ms, yg, cg = timed(c, m, h)
        row = {"config": name, "rows": T,
               "dense_ms": d_ms, "grouped_ms": g_ms,
               "faster": "grouped" if g_ms < d_ms else "dense",
               "picks_held": int(cd[1]),
               "dense_rows": T * c.experts_held,
               "grouped_rows": int(cg[-1]),
               "max_abs_diff": float(jnp.abs(
                   yd.astype(jnp.float32) - yg.astype(jnp.float32)).max())}
        if T == CHUNK:
            for tm in (64, 256):
                row[f"grouped_tm{tm}_ms"] = timed(
                    c, m, h, _TILE_ROWS=tm)[0]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    import jax

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--configs", default=",".join(CONFIGS))
    args = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("expert_form_table: no TPU, nothing was timed",
              file=sys.stderr)
        return 1
    rows = []
    for name in args.configs.split(","):
        rows += rows_of(name, args.seed)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "expert_form_table.json"), "w") as f:
        json.dump({"rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
