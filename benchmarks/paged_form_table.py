#!/usr/bin/env python3
"""The calibration table of ``ops/paged_attention.py::chunk_form``:
both forms of ``paged_attention_chunk`` timed on the chip, one layer's
call, at the shapes the serving engines trace (PERF.md section 6).

    python3 benchmarks/paged_form_table.py     # on a machine with a TPU

Prints one JSON line a shape (``gather_ms``, ``pool_ms``, the form the
rule takes, the faster form) and writes them to
``chiprun_out/paged_form_table.json``. Then the many-query rows: the
flash form (the kernel ``dtt_paged_prefill``) against the XLA form,
queries a block at a time where one pass would not fit, at the shapes
of a prompt chunk (``xla_ms``, ``flash_ms``): what decides whether
``_LOGITS_LIMIT`` could come down. Last, the latent rows (PR 34): one
full layer under its learned selection against dense attention over
the same table, and one window layer over its ring, at
``dots3-note-ep8``'s widths (``sparse_ms``, ``dense_ms``, ``ring_ms``).
Times are of twenty calls after one (ten for the latent rows), a layer
alone: what decides between forms, not a benchmark result.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# name: B, S, H, Hkv, P, N. The first five are the calibration table's;
# the rest bracket where the two forms cross.
XL = dict(H=25, Hkv=25, P=64, N=385)
SMALL = dict(H=12, Hkv=12, P=64, N=3073)
SHAPES = {
    "xl.resident_16x1": dict(B=16, S=1, **XL),
    "xl.prefill_batch_4x128": dict(B=4, S=128, **XL),
    "xl.prefill_cont_1x128": dict(B=1, S=128, **XL),
    "xl.spec_16x4": dict(B=16, S=4, **XL),
    "small.resident_64x1": dict(B=64, S=1, **SMALL),
    "xl.16x8": dict(B=16, S=8, **XL),
    "xl.16x16": dict(B=16, S=16, **XL),
    "xl.16x32": dict(B=16, S=32, **XL),
    "xl.4x32": dict(B=4, S=32, **XL),
    "small.spec_64x4": dict(B=64, S=4, **SMALL),
    "small.prefill_batch_8x128": dict(B=8, S=128, **SMALL),
    "small.16x1": dict(B=16, S=1, **SMALL),
    "xl.gqa_16x1": dict(B=16, S=1, H=25, Hkv=5, P=64, N=385),
}

# The flash form's rows: smallthinker-21b-ep4's chunk of 1024 (28 query
# heads over 4 kv heads of 128) over a window layer's ring of 320 pages
# after its first turn and over a global layer's table of 1024 pages at
# a median and at the longest prompt, and gpt2-xl's 4 x 128 prefill
# over 1024 slots, which stays under the rule.
THINKER = dict(B=1, S=1024, H=28, Hkv=4, hd=128)
MANY = {
    "thinker.ring_1x1024": dict(**THINKER, P=320, N=10241, window=4096,
                                ring=True, start=8192),
    "thinker.table_1x1024_at_4096": dict(**THINKER, P=1024, N=32769,
                                         start=4096),
    "thinker.table_1x1024_at_11264": dict(**THINKER, P=1024, N=32769,
                                          start=11264),
    "xl.prefill_batch_4x128": dict(B=4, S=128, H=25, Hkv=25, hd=64,
                                   P=64, N=385, start=512),
}


# dots3-note-ep8's two kinds of latent layer (chip_smoke.py::
# sparse_latent_case): a full layer under its selection of 2,048
# against dense attention over the same table, and a window layer over
# its ring of 97 pages; a decode iteration and a prompt chunk, at 8k of
# context.
SPARSE = {
    "dots3.full_32x1": dict(kind="full", B=32, S=1),
    "dots3.full_1x1024": dict(kind="full", B=1, S=1024),
    "dots3.window_32x1": dict(kind="window", B=32, S=1),
    "dots3.window_1x1024": dict(kind="window", B=1, S=1024),
}


def main() -> int:
    import jax

    import chip_smoke

    if jax.devices()[0].platform != "tpu":
        print("paged_form_table: no TPU, nothing was timed",
              file=sys.stderr)
        return 1
    rows = []
    for name, shape in SHAPES.items():
        row = {"name": name, **chip_smoke.paged_forms_case(**shape)}
        row["faster"] = ("pool" if row["pool_ms"] < row["gather_ms"]
                         else "gather")
        print(json.dumps(row), flush=True)
        rows.append(row)
    for name, shape in MANY.items():
        row = {"name": name, **chip_smoke.paged_prefill_case(**shape)}
        row["faster"] = ("flash" if row["flash_ms"] < row["xla_ms"]
                         else "xla")
        print(json.dumps(row), flush=True)
        rows.append(row)
    for name, shape in SPARSE.items():
        row = {"name": name, **chip_smoke.sparse_latent_case(**shape)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "paged_form_table.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
