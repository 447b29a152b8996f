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

Then the table of ``sparse_form``: a full layer of ``dots3-note-ep8``
under its selection of 2,048 (``chip_smoke.py::sparse_latent_case``), a
prompt chunk of 1 x 1024 in its masked and its gather form at 4k, 8k
and 16k of context over the cell's table of 16,384 rows, and over
tables of 32k to 128k rows read to their end, where the two cross
(``_ROW_GATHER``; ten calls after one).
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


# (pages of 16 a table, contexts): the cell's table, then longer ones
# read to their end.
SPARSE = [(1024, (4096, 8192, 16384)), (2048, (32768,)),
          (4096, (65536,)), (8192, (131072,))]


def sparse_rows(pages: int, contexts, B: int = 1, S: int = 1024,
                **dims) -> list:
    """One row a context: ``flash_ms`` and ``absorbed_ms`` (the masked
    and the gather form) of a full layer's call over a table of
    ``pages`` pages, the form the rule takes and the faster one."""
    import chip_smoke
    from distributed_training_tpu.ops import paged_attention as pa

    dims = {**dims, "P": pages}
    d = {**chip_smoke.SPARSE_LATENT["full"], **dims}
    case = chip_smoke.sparse_latent_case(
        "full", B, S, context=contexts[0], contexts=contexts[1:],
        dense=False, **dims)
    rule = pa.sparse_form((B, S, d["H"]), pages * 16, d["index"][2],
                          (d["rank"], d["nope"], d["rope"], d["v"]))
    return [{"B": B, "S": S, "table": pages * 16, "context": c,
             "rule": rule, **row,
             "faster": ("flash" if row["flash_ms"] < row["absorbed_ms"]
                        else "absorbed")}
            for c, row in case.get("forms_ms", {}).items()]


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
    for pages, contexts in SPARSE:
        for row in sparse_rows(pages, contexts):
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "latent_form_table.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
