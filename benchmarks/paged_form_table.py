#!/usr/bin/env python3
"""The calibration table of ``ops/paged_attention.py::chunk_form``:
both forms of ``paged_attention_chunk`` timed on the chip, one layer's
call, at the shapes the serving engines trace (PERF.md section 6).

    python3 benchmarks/paged_form_table.py [few] [many] [latent]
                                          # on a machine with a TPU

Prints one JSON line a shape (``gather_ms``, ``pool_ms``, ``ragged_ms``
where the call's query rows are few enough for the rule to offer the
ragged form, the form the rule takes, the faster form) and writes them
to ``chiprun_out/paged_form_table.json``. Then the decode rows (PR 37):
the ragged form (the kernel ``dtt_paged_decode``) against the gather
form, and at ``gpt2-xl``'s shape the pool form, at the resident decode
shapes of ``smallthinker-21b-ep4`` (table and ring) and ``gpt2-xl``
with a quarter, a half and all of a table live, and the fit of the
rule's two ragged constants to every ``ragged_ms`` above
(``_RAGGED_READ``, ``_RAGGED_PAGE``). Then the many-query rows: the
flash form (the kernel ``dtt_paged_prefill``) against the XLA form,
queries a block at a time where one pass would not fit, at the shapes
of a prompt chunk (``xla_ms``, ``flash_ms``): what decides whether
``_LOGITS_LIMIT`` could come down. Last, the latent rows (PR 34): one
full layer under its learned selection against dense attention over
the same table, and one window layer over its ring, at
``dots3-note-ep8``'s widths (``sparse_ms``, ``dense_ms``, ``ring_ms``).
Times are of twenty calls after one (ten for the latent rows), a layer
alone; the few-query rows since PR 37 sixteen calls a program, four
programs after one, so the device's time and not a dispatch's: what
decides between forms, not a benchmark result.
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

# The ragged form's rows: the resident decode call of
# smallthinker-21b-ep4 (32 x 1, 28 query heads over 4 kv heads of 128)
# over a global layer's table of 1,024 pages and a window layer's ring
# of 320, and gpt2-xl's (16 x 1, 24 pages a sequence of its 385, the
# pool four layers deep so that the pool form pays for its layer's slice
# as it does in the engine), each sequence holding ``context`` tokens: a
# quarter, a half and all of the table; a quarter and half a window and
# a ring that has turned.
DECODE = dict(B=32, S=1, H=28, Hkv=4, hd=128)
XL_DECODE = dict(B=16, S=1, H=25, Hkv=25, hd=64, P=64, N=385, pool=True,
                 layers=4)
FEW = {
    "thinker.table_32x1_at_4096": dict(**DECODE, P=1024, N=32769,
                                       context=4096),
    "thinker.table_32x1_at_8192": dict(**DECODE, P=1024, N=32769,
                                       context=8192),
    "thinker.table_32x1_at_16384": dict(**DECODE, P=1024, N=32769),
    "thinker.ring_32x1_at_1024": dict(**DECODE, P=320, N=10241,
                                      window=4096, ring=True,
                                      context=1024),
    "thinker.ring_32x1_at_2048": dict(**DECODE, P=320, N=10241,
                                      window=4096, ring=True,
                                      context=2048),
    "thinker.ring_32x1_at_10243": dict(**DECODE, P=320, N=10241,
                                       window=4096, ring=True,
                                       context=10243),
    "xl.decode_16x1_at_96": dict(**XL_DECODE, context=96),
    "xl.decode_16x1_at_192": dict(**XL_DECODE, context=192),
    "xl.decode_16x1_at_384": dict(**XL_DECODE),
    "xl.spec_16x4_at_384": dict(**{**XL_DECODE, "S": 4}),
}

# Calls a program of the few-query rows (``chip_smoke._in_one_program``):
# a small call's wall time is its dispatch, 0.2 ms, whatever the form.
INNER = 16

# A v5e's HBM bandwidth in bytes a millisecond: the rule's costs are
# bytes, a call's time is milliseconds.
HBM_BYTES_MS = 819e6

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


def fit_ragged(rows: list) -> dict:
    """``_RAGGED_READ`` and ``_RAGGED_PAGE`` from every row that timed
    the ragged form: least squares of ``ragged_ms * HBM_BYTES_MS`` on
    the bytes and the pages the kernel walked, with a floor a call, in
    RELATIVE error (the rows' times lie forty times apart, and the rule
    compares ratios)."""
    import numpy as np

    timed = [r for r in rows if "ragged_ms" in r]
    a = np.asarray([[r["bytes"], r["pages"], 1.0] for r in timed])
    b = np.asarray([r["ragged_ms"] * HBM_BYTES_MS for r in timed])
    fit, *_ = np.linalg.lstsq(a / b[:, None], np.ones_like(b),
                              rcond=None)
    ratio = a @ fit / b
    return {"name": "fit.ragged", "rows": len(timed),
            "_RAGGED_READ": float(fit[0]), "_RAGGED_PAGE": float(fit[1]),
            "floor_ms": float(fit[2] / HBM_BYTES_MS),
            "worst_ratio": float(np.max(np.maximum(ratio, 1 / ratio)))}


def faster(row: dict) -> str:
    """The form of a row's ``<form>_ms`` that took least."""
    return min((f for f in ("gather", "pool", "ragged")
                if f"{f}_ms" in row), key=lambda f: row[f"{f}_ms"])


def main(parts) -> int:
    """``parts``: which of ``few`` (the thirteen shapes and the decode
    rows, with the fit), ``many`` and ``latent`` to time; all three
    unless named."""
    import jax

    import chip_smoke

    if jax.devices()[0].platform != "tpu":
        print("paged_form_table: no TPU, nothing was timed",
              file=sys.stderr)
        return 1
    parts = set(parts) or {"few", "many", "latent"}
    rows = []

    def add(row):
        print(json.dumps(row), flush=True)
        rows.append(row)

    if "few" in parts:
        for case, shapes in ((chip_smoke.paged_forms_case, SHAPES),
                             (chip_smoke.paged_decode_case, FEW)):
            for name, shape in shapes.items():
                row = {"name": name,
                       **case(**shape, reps=4, inner=INNER)}
                add({**row, "faster": faster(row)})
        add(fit_ragged(rows))
    if "many" in parts:
        for name, shape in MANY.items():
            row = {"name": name,
                   **chip_smoke.paged_prefill_case(**shape)}
            add({**row, "faster": "flash" if row["flash_ms"]
                 < row["xla_ms"] else "xla"})
    if "latent" in parts:
        for name, shape in SPARSE.items():
            add({"name": name, **chip_smoke.sparse_latent_case(**shape)})
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "paged_form_table.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
