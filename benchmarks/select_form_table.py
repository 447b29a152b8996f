#!/usr/bin/env python3
"""One full layer's learned selection (``ops/paged_attention.py::
select_topk``: the exact top 2,048 of a query's index scores over a
table of 16,384 positions) timed on the chip as it is, found by
counting (``dtt_select_topk``), against a sort (``jax.lax.top_k``, the
form it replaced) and the same count written in plain XLA, at ``dots3_ep8.serve_sparse``'s
shapes: a prompt chunk's block of 1 x 128 queries (what the masked form
takes: the mask) and a resident iteration's 32 x 1 (what the gather
form takes: the positions), at 4k, 8k and 16k positions seen, the
scores those of ``index_scores`` at the published widths (64 index
heads of 128, bfloat16 keys) drawn from ``--seed`` (PERF.md section 6).

    python3 benchmarks/select_form_table.py [--seed N]   # on a TPU

Prints one JSON line a shape and context (``<form>_ms``, the faster
form, whether every form chose the same set) and writes them to
``chiprun_out/select_form_table.json``. At the chunk shape it also times
the kernel at other widths of a counting step (``_SELECT_LANES``); at
decode, the kernel's mask with its positions compacted by a scatter
(``threshold_scatter``) beside ``_compact``. Times are of eight
different inputs in one program, ten calls after one: what decides
between two forms, not a benchmark result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SLOTS, TOPK, HEADS, DIM = 16384, 2048, 64, 128
# (sequences, queries a sequence): a prompt chunk's query block, a
# resident iteration.
SHAPES = [(1, 128), (32, 1)]
CONTEXTS = (4096, 8192, 16384)
INPUTS = 8


def xla_mask(scores, seen, k: int):
    """The kernel's count in plain XLA: the ``k``-th largest key a row
    by bisection on its 32 bits, then the cutoff among the keys equal
    to it by bisection on the position, each count a pass over the
    row."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.ops import paged_attention as pa

    keys = pa._order_keys(scores, seen)
    pos = jnp.arange(keys.shape[-1], dtype=jnp.int32)
    lead = keys.shape[:-1] + (1,)

    def key_bit(b, t):
        bit = t | jnp.left_shift(jnp.int32(1), 31 - b)
        n = jnp.sum(keys >= bit ^ pa._UNSEEN, axis=-1, keepdims=True)
        return jnp.where(n >= k, bit, t)

    t = jax.lax.fori_loop(0, 32, key_bit, jnp.zeros(lead, jnp.int32)
                          ) ^ pa._UNSEEN
    room = k - jnp.sum(keys > t, axis=-1, keepdims=True)
    bits = keys.shape[-1].bit_length()

    def pos_bit(b, p):
        bit = p | jnp.left_shift(jnp.int32(1), bits - 1 - b)
        n = jnp.sum((keys == t) & (pos < bit), axis=-1, keepdims=True)
        return jnp.where(n <= room, bit, p)

    p = jax.lax.fori_loop(0, bits, pos_bit, jnp.zeros(lead, jnp.int32))
    return ((keys > t) | ((keys == t) & (pos < p))) & (keys != pa._UNSEEN)


def sort_topk(scores, seen, k: int):
    """The selection as a sort (``jax.lax.top_k``, the tie rule kept by
    a mask of the scores equal to the ``k``-th), as it was found before
    ``dtt_select_topk``: ``(positions, kept, chosen)``."""
    import jax
    import jax.numpy as jnp

    scores = jnp.where(seen, jnp.where(scores == 0, 0.0, scores),
                       -jnp.inf)
    top, positions = jax.lax.top_k(scores, k)
    last = top[..., -1:]
    above, equal = scores > last, scores == last
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (equal & (jnp.cumsum(equal, axis=-1) <= room))
    return positions, top > -jnp.inf, chosen & seen


def inputs(B: int, S: int, context: int, seed: int):
    """``INPUTS`` sets of index scores ``(B, S, SLOTS)`` float32 and the
    queries' positions: the last ``S`` of a context of ``context``."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.ops import paged_attention as pa

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (INPUTS, B, S, HEADS, DIM), bf)
    w = jax.random.normal(ks[1], (INPUTS, B, S, HEADS), jnp.float32)
    keys = jax.random.normal(ks[2], (INPUTS, B, SLOTS, DIM), bf)
    scores = jax.jit(jax.vmap(lambda q, w, k: pa.index_scores(
        pa.Selection(q, w, None, TOPK), k)))(q, w, keys)
    q_pos = jnp.broadcast_to(
        jnp.arange(context - S, context, dtype=jnp.int32), (B, S))
    return scores, q_pos


def timed(fn, scores, q_pos, reps: int = 10) -> tuple:
    """``(ms a call, outputs)`` of ``fn(scores, seen)`` over the
    ``INPUTS`` sets in one program."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.ops import paged_attention as pa

    def over(scores, q_pos):
        seen = pa._visible(q_pos, jnp.arange(SLOTS, dtype=jnp.int32)[None],
                           None, None)
        return jax.lax.map(lambda s: fn(s, seen), scores)

    call = jax.jit(over).lower(scores, q_pos).compile()
    out = jax.block_until_ready(call(scores, q_pos))
    t0 = time.perf_counter()
    for _ in range(reps):
        last = call(scores, q_pos)
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / reps / INPUTS * 1e3, out


def forms_of(B: int, S: int):
    """``{form: fn(scores, seen)}``: what the call's caller takes of the
    selection, the mask at a chunk and the positions with ``kept`` at
    decode."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.ops import paged_attention as pa

    def taken(select):
        def fn(scores, seen):
            positions, kept, chosen = select(scores, seen, TOPK)
            if S == 1:
                return positions, kept
            return chosen.astype(jnp.int8)
        return fn

    def lanes(n):
        def select(scores, seen, k):
            kept = pa._SELECT_LANES
            try:
                pa._SELECT_LANES = n
                return pa.select_topk(scores, seen, k)
            finally:
                pa._SELECT_LANES = kept
        return taken(select)

    def scattered(scores, seen):
        """The kernel's mask, its positions compacted by a scatter of
        every position to its place among the chosen."""
        Sk = scores.shape[-1]
        chosen = pa._threshold_mask(
            pa._order_keys(scores, seen).reshape(-1, Sk),
            jnp.max(jnp.where(seen, jnp.arange(1, Sk + 1), 0),
                    axis=-1).reshape(-1), TOPK).reshape(scores.shape) != 0
        place = jnp.where(chosen, jnp.cumsum(chosen, axis=-1) - 1, TOPK)
        slot = jnp.arange(Sk, dtype=jnp.int32)
        positions = jax.vmap(
            lambda i: jnp.zeros(TOPK, jnp.int32).at[i].set(
                slot, mode="drop"))(place.reshape(-1, Sk))
        kept = jnp.arange(TOPK) < jnp.sum(chosen, axis=-1, keepdims=True)
        return positions.reshape(kept.shape), kept

    forms = {"sort": taken(sort_topk), "threshold": taken(pa.select_topk)}
    if S == 1:
        forms["threshold_scatter"] = scattered
    else:
        forms["xla"] = lambda s, seen: xla_mask(s, seen, TOPK).astype(
            jnp.int8)
        for n in (256, 1024, 2048):
            forms[f"threshold_lanes{n}"] = lanes(n)
    return forms


def as_set(out):
    """A form's outputs as the set it chose: the mask, or the kept
    positions in order."""
    import numpy as np

    if isinstance(out, tuple):
        positions, kept = map(np.asarray, out)
        return np.sort(np.where(kept, positions, SLOTS), axis=-1)
    return np.asarray(out)


def table(seed: int, contexts=CONTEXTS) -> list:
    """One row a shape and context of ``SHAPES`` x ``contexts``."""
    import numpy as np

    rows = []
    for B, S in SHAPES:
        for context in contexts:
            scores, q_pos = inputs(B, S, context, seed + context + S)
            row = {"B": B, "S": S, "slots": SLOTS, "topk": TOPK,
                   "context": context}
            outs = {}
            for form, fn in forms_of(B, S).items():
                row[f"{form}_ms"], outs[form] = timed(fn, scores, q_pos)
            row["faster"] = min(("sort", "threshold", "xla"),
                                key=lambda f: row.get(f"{f}_ms", 1e9))
            sets = {form: as_set(out) for form, out in outs.items()}
            row["same_set"] = all(np.array_equal(x, sets["sort"])
                                  for x in sets.values())
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    import jax

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("select_form_table: no TPU, nothing was timed",
              file=sys.stderr)
        return 1
    rows = table(args.seed)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "select_form_table.json"), "w") as f:
        json.dump({"rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
