#!/usr/bin/env python3
"""The calibration table of ``ops/paged_attention.py::latent_form``:
both forms of ``latent_attention_chunk`` timed on the chip, one layer's
call, at the published widths of ``joyai-llm-flash-ep4`` (rank 512,
heads of 128 + 64 and 128, bfloat16) over tables of 256 pages of 16
(PERF.md section 6).

    python3 benchmarks/latent_form_table.py    # on a machine with a TPU

Prints one JSON line a shape (``absorbed_ms``, ``expanded_ms``, the form
the rule takes, the faster form, the worst difference between the two)
and writes them to ``chiprun_out/latent_form_table.json``. Times are of
twenty calls after one, a layer alone: what decides between two forms,
not a benchmark result.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RANK, NOPE, ROPE, V, H, PS, P = 512, 128, 64, 128, 32, 16, 256
# (sequences, queries a sequence): a resident iteration, then chunks on
# either side of where the forms cross.
SHAPES = [(32, 1), (4, 64), (4, 128), (4, 192), (4, 256), (4, 384),
          (4, 512), (1, 256), (1, 512), (1, 1024), (2, 1024)]


def case(B: int, S: int, reps: int = 20) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import as_layer

    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(B * 10007 + S), 6)
    N = B * P + 1
    rng = np.random.default_rng(B * 10007 + S)
    tables = (rng.permutation(N - 1).reshape(B, P) + 1).astype(np.int32)
    lengths = rng.integers(max(S, P * PS // 4), P * PS + 1, B)
    q_pos = (lengths[:, None] - S + np.arange(S)[None, :]).astype(np.int32)
    args = (jax.random.normal(ks[0], (B, S, H, NOPE), bf),
            jax.random.normal(ks[1], (B, S, H, ROPE), bf),
            as_layer(jax.random.normal(ks[2], (1, N, PS, RANK), bf)),
            as_layer(jax.random.normal(ks[3], (1, N, PS, ROPE), bf)),
            jnp.asarray(tables), jnp.asarray(q_pos),
            jax.random.normal(ks[4], (RANK, H, NOPE), bf) * RANK ** -0.5,
            jax.random.normal(ks[5], (RANK, H, V), bf) * RANK ** -0.5)
    rule = pa.latent_form((B, S, H), (RANK, NOPE, V))
    row = {"B": B, "S": S, "rule": rule}
    out, taken = {}, pa.latent_form
    try:
        for form in ("absorbed", "expanded"):
            pa.latent_form = lambda *a, _f=form: _f
            # A function of its own a form: jit keeps what it traced for
            # one function at one set of shapes.
            fn = jax.jit(lambda *a: pa.latent_attention_chunk(*a)
                         ).lower(*args).compile()
            out[form] = jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(reps):
                last = fn(*args)
            jax.block_until_ready(last)
            row[form + "_ms"] = (time.perf_counter() - t0) / reps * 1e3
    finally:
        pa.latent_form = taken
    row["max_abs_diff"] = float(jnp.abs(
        out["absorbed"].astype(jnp.float32)
        - out["expanded"].astype(jnp.float32)).max())
    row["faster"] = ("absorbed" if row["absorbed_ms"] < row["expanded_ms"]
                     else "expanded")
    return row


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("latent_form_table: no TPU, nothing was timed",
              file=sys.stderr)
        return 1
    rows = []
    for B, S in SHAPES:
        rows.append(case(B, S))
        print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "latent_form_table.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
