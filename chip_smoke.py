#!/usr/bin/env python3
"""Does the system still start on the chip? One command, one process.

    python3 chip_smoke.py          # on a machine with a TPU

Drives the main path once through the entry points a user calls, at the
full width of gpt2_125m (12 layers, d_model 768, 12 heads of 64, vocab
50304; random weights from a seed, synthetic data):

1. kernels — every Pallas path a supported config can reach, compiled
   (not interpreted) and compared with the naive reference at bf16
   tolerance: flash forward + fused backward at S 1024 / D 64, the
   two-kernel split backward where the fused one does not fit VMEM, a
   sliding window, GQA; both forms of paged attention at two of
   gpt2-xl's engine shapes against each other, and its flash form (the
   kernel of a prompt chunk) against the XLA form over a ring;
2. trainer — ``distributed_training_tpu.train.cli.main`` takes a few
   steps at batch 32 / seq 1024 / bf16 / AdamW with the telemetry, the
   collectives audit and the checkpoint code a user gets;
3. engine — an ``Engine`` at the same widths behind a ``ServingServer``
   answers ``POST /generate`` requests of a few hundred prompt tokens,
   streamed and plain.

It asserts ``platform == "tpu"`` before any work and exits non-zero
otherwise; every phase's failure is the script's failure. On success
the LAST line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``;
on failure no result line is printed. All work stays in this one
process (a chip belongs to one process) and every thread it starts is
stopped. Logs and event streams land in ``chiprun_out/chip_smoke/``
(the checkpoint is deleted once checked).

Every time printed here is SMOKE OUTPUT — cold compiles included, a
handful of steps — and never a benchmark result.
"""

from __future__ import annotations

import gc
import http.client
import json
import logging
import math
import os
import shutil
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
SEED = 0
BF16_TOL = 3e-2     # the repo's bf16 kernel tolerance (tests/)
TIE_TOL = 0.12      # logits: how far apart two bf16 "equal" scores may sit
BATCH, SEQ_LEN, STEPS = 32, 1024, 6


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def load_jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def train_argv(out_dir: str, name: str, *overrides: str) -> list:
    """Overrides for ``python -m distributed_training_tpu.train``: the
    headline configuration on synthetic data from a seed, a handful of
    steps, every step logged and HBM-sampled. Shared with
    benchmarks/chip_multichip.py so one-chip and four-chip runs see the
    same data."""
    return ["model=gpt2_125m", "train=gpt2",
            f"train.global_batch_size={BATCH}",
            f"train.max_steps_per_epoch={STEPS}",
            "train.total_epochs=1", "train.log_every=1",
            "train.hbm_sample_every=1", f"train.seed={SEED}",
            "train.shuffle=false", f"run.output_dir={out_dir}",
            f"run.experiment_name={name}", *overrides]


# One engine geometry for every smoke engine at gpt2_125m widths: the
# fast cadence (speculative chunks inside the device-resident loop).
ENGINE_GEOMETRY = dict(page_size=16, max_seq_len=1024, prefill_chunk=128,
                       spec_k=4, resident_k=8)
PAGES_PER_SEQ = 1024 // 16
NEW_TOKENS = 16


def serving_fixture(prompt_lengths):
    """(model, f32 params, bf16 params, prompts): gpt2_125m at full
    width with random weights from SEED, and seeded random prompts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.models import build_model

    model = build_model("gpt2_125m", dtype="bfloat16")
    params = model.init(jax.random.PRNGKey(SEED))
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    rng = np.random.default_rng(SEED)
    prompts = [[int(t) for t in rng.integers(0, 50257, n)]
               for n in prompt_lengths]
    return model, params, bf16, prompts


def make_reference(model, params):
    """``rows(seq, first)``: logits rows ``first..len(seq)-1`` of the
    training-path forward over ``seq`` (padded to one fixed length, so
    one compile); row ``i`` scores the token at position ``i + 1``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    apply = jax.jit(lambda p, ids: model.apply(p, ids)[0])

    def rows(seq: list, first: int):
        ids = np.zeros((1, 512), np.int32)
        ids[0, :len(seq)] = seq
        logits = apply(params, jnp.asarray(ids))[0]
        return np.asarray(logits[first:len(seq)], np.float32)

    return rows


# -- phase 1: kernels ---------------------------------------------------------


def _close(name: str, got, want) -> float:
    """Max error of ``got`` against ``want`` in units of the repo's
    bf16 tolerance band (atol + rtol * |want|); <= 1.0 passes."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite values")
    # Gradients of a sum over S keys grow with S; scale the absolute
    # term by the reference's own magnitude so one band fits all cases.
    scale = max(1.0, float(np.abs(want).max()))
    band = BF16_TOL * scale + BF16_TOL * np.abs(want)
    return float((np.abs(got - want) / band).max())


def flash_case(B, H, Hkv, S, D, window=0, expect_fused=True):
    """(run, describe) for one flash fwd+bwd comparison, BHSD layout
    (the model's fast path), bf16, causal."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.ops import flash_attention as fa
    from distributed_training_tpu.ops.attention import (
        dot_product_attention)

    bq, bk = fa.default_blocks(S, S, D)
    fused = fa._fused_bwd_fits(S, D, bq, bk, jnp.bfloat16)
    if fused != expect_fused:
        raise AssertionError(
            f"case meant for the {'fused' if expect_fused else 'split'} "
            f"backward but _fused_bwd_fits(S={S}, D={D}) is {fused}")

    def inputs():
        ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
        q = jax.random.normal(ks[0], (B, H, S, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.bfloat16)
        g = jax.random.normal(ks[3], (B, H, S, D), jnp.bfloat16)
        return q, k, v, g

    def make(impl):
        def f(q, k, v, g):
            def loss(q, k, v):
                o = dot_product_attention(q, k, v, causal=True,
                                          impl=impl, window=window,
                                          layout="bhsd")
                return jnp.sum(o.astype(jnp.float32)
                               * g.astype(jnp.float32)), o
            (_, o), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (o, *grads)
        return jax.jit(f)

    label = (f"B{B} H{H}/{Hkv} S{S} D{D} tiles {bq}x{bk}"
             + (f" window {window}" if window else "")
             + (" fused-bwd" if fused else " split-bwd (dq + dkv)"))
    return make("flash"), make("naive"), inputs, label


def _smoke_time(fn, args, reps: int):
    """``fn`` compiled for ``args``: the compiled program, what one
    call gives and the wall milliseconds a call of ``reps`` calls after
    it."""
    import jax

    run = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        last = run(*args)
    jax.block_until_ready(last)
    return run, out, (time.perf_counter() - t0) / reps * 1e3


def _in_one_program(fn, inner: int):
    """``fn(q, kp, vp, tables, q_pos)`` called ``inner`` times in ONE
    program, each call's layer number and page table fed a zero that
    the call before computed (so the compiler can neither hoist a
    gather or a layer's slice out of the loop nor drop a call): the
    device's time a call, without the 0.2 ms of wall that a dispatch
    of its own costs, which is most of a small call's. As the engine
    runs it: a layer's call inside a loop, the layer's number carried."""
    import jax
    import jax.numpy as jnp

    if inner == 1:
        return fn

    def run(q, kp, vp, tables, q_pos):
        def body(_, carry):
            zero, _out = carry
            k, v = (p.layout.layer(p.pool, p.number + zero)
                    for p in (kp, vp))
            out = fn(q, k, v, tables + zero, q_pos)
            return (out[0, 0, 0, 0] > 1e30).astype(jnp.int32), out
        return jax.lax.fori_loop(
            0, inner, body, (jnp.int32(0), jnp.zeros_like(q)))[1]
    return run


def _forms_row(label, ms, walked, kp, diff, rule) -> dict:
    """A paged case's result: each form's ``<form>_ms``, the live
    ``pages`` the ragged form walks and their nominal ``bytes`` in the
    two pools (what a layer of ``kp`` holds, as ``chunk_form`` counts
    it), the worst difference and the rule's form."""
    page = kp.page_size * 2 * kp.layout.heads * kp.layout.width \
        * kp.dtype.itemsize
    return {"ok": True, "shape": label,
            **{f"{f}_ms": t for f, t in ms.items()}, "pages": walked,
            "bytes": walked * page, "max_abs_diff": diff, "rule": rule}


def paged_forms_case(B, S, H, Hkv, P, N, hd=64, ps=16, reps=20,
                     inner=1) -> dict:
    """The forms of ``paged_attention_chunk`` (gather, pool, and where
    the call's query rows are few enough to be offered it, ragged) on
    one layer's bf16 pool at an engine's shapes, against the gather
    form: the worst absolute difference, each form's smoke time a
    call, the live pages the ragged form walks, and the form the rule
    takes. The sequences share the pool as the engine
    deals it: distinct pages out of order, ragged lengths, the unused
    tail of every row on scratch page 0, one inactive row. ``inner``
    calls a program (``_in_one_program``): above 1 the times are the
    device's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import as_layer

    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    # One layer of a pool as the cache stores it.
    kp, vp = (as_layer(jax.random.normal(k, (Hkv, N, ps, hd),
                                         jnp.bfloat16))
              for k in ks[1:])
    rng = np.random.default_rng(SEED)
    own = min(P, (N - 1) // B)
    pages = rng.permutation(N - 1)[:B * own].reshape(B, own) + 1
    lengths = rng.integers(S, own * ps + 1, B)
    lengths[B // 2] = 0
    tables = np.zeros((B, P), np.int32)
    for b in range(B):
        used = -(-int(lengths[b]) // ps)
        tables[b, :used] = pages[b, :used]
    q_pos = lengths[:, None] - S + np.arange(S)[None, :]
    q_pos = np.where(lengths[:, None] > 0, q_pos, -1).astype(np.int32)
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(q_pos))
    forms = {"gather": pa._gather_attention,
             "pool": pa._pool_attention}
    # The ragged form where the rule may offer it (few query rows).
    tiles, rows = kp.layout.spread(q).shape[2:4]
    if tiles * S * rows <= pa._RAGGED_ROWS:
        forms["ragged"] = pa._ragged_attention
    out, ms = {}, {}
    for form, fn in forms.items():
        _run, out[form], ms[form] = _smoke_time(
            _in_one_program(fn, inner), args, reps)
        ms[form] /= inner
    diff, band = 0.0, 0.0
    for form in set(forms) - {"gather"}:
        diff = max(diff, float(jnp.abs(
            out[form].astype(jnp.float32)
            - out["gather"].astype(jnp.float32)).max()))
        band = max(band, _close("paged_forms", out[form],
                                out["gather"]))
    rule = pa.chunk_form(q.shape, (Hkv, N, ps, hd), tables.shape,
                         kp.dtype.itemsize)
    walked = int(sum(-(-int(n) // ps) for n in lengths))
    label = f"{B} x {S}, H{H}/{Hkv} D{hd}, pool {N} x {ps}, P {P}"
    say(f"  paged forms [{label}]: "
        + ", ".join(f"{f} {t:.3f} ms" for f, t in ms.items())
        + f" a call (smoke wall), {walked} pages live, worst |diff| "
        f"{diff:.4f} ({band:.3f} of the bf16 band), rule -> {rule}")
    if band > 1.0:
        raise AssertionError(
            f"forms differ beyond the bf16 band: {diff} ({band})")
    return _forms_row(label, ms, walked, kp, diff, rule)


def paged_decode_case(B, S, H, Hkv, P, N, hd=128, ps=16, window=None,
                      ring=False, context=None, pool=False,
                      layers=1, reps=20, inner=1) -> dict:
    """The ragged form of ``paged_attention_chunk`` (the Pallas kernel
    ``dtt_paged_decode``, which walks a sequence's live pages where
    they lie in the carried pool) against the gather form (and the
    pool form, ``pool=True``) on one layer's bf16 pool at a decode
    program's shapes (the pool ``layers`` deep, the layer read in the
    middle): ``B`` sequences of ``context`` tokens each (all
    of its pages unless given; of a ring, which has turned where the
    context is longer), the ``S`` queries the last positions, pages
    out of order, table tails on scratch page 0, the last sequence
    dead. The worst absolute difference, each form's smoke time a
    call (the device's where ``inner`` calls share a program:
    ``_in_one_program``), the pages and bytes the kernel walks, and
    the form the rule takes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import as_layer

    ks = jax.random.split(jax.random.PRNGKey(SEED + 4), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    kp, vp = (as_layer(jax.random.normal(k, (Hkv, N, ps, hd),
                                         jnp.bfloat16))
              for k in ks[1:])
    if layers > 1:      # the layer named in the middle of a deeper pool
        kp, vp = (p.layout.layer(jnp.tile(p.pool, (layers, 1, 1, 1)),
                                 p.number + layers // 2)
                  for p in (kp, vp))
    rng = np.random.default_rng(SEED + 4)
    own = min(P, (N - 1) // B)
    context = context or own * ps
    held = own if ring else min(own, -(-context // ps))
    tables = np.zeros((B, P), np.int32)
    tables[:, :held] = (rng.permutation(N - 1)[:B * held] + 1
                        ).reshape(B, held)
    q_pos = np.tile(context - S + np.arange(S, dtype=np.int32), (B, 1))
    if B > 1:
        q_pos[-1] = -1
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(q_pos))
    forms = {"gather": pa._gather_attention,
             "ragged": pa._ragged_attention}
    if pool:
        forms["pool"] = pa._pool_attention
    out, ms = {}, {}
    for form, fn in forms.items():
        run, out[form], ms[form] = _smoke_time(_in_one_program(
            lambda *a, fn=fn: fn(*a, window, ring), inner), args, reps)
        ms[form] /= inner
        if form == "ragged" and "dtt_paged_decode" not in run.as_text():
            raise AssertionError("no dtt_paged_decode custom call in "
                                 "the compiled ragged form")
    diff = float(jnp.abs(out["ragged"].astype(jnp.float32)
                         - out["gather"].astype(jnp.float32)).max())
    band = _close("paged_decode", out["ragged"], out["gather"])
    with pa.observe_forms() as seen:
        jax.eval_shape(lambda *a: pa.paged_attention_chunk(
            *a, window=window, ring=ring), *args)
    # What the kernel walks: the pages that hold the seen slots.
    lo = max(context - S - window + 1, 0) if window else 0
    walked = (B - (B > 1)) * ((context - 1) // ps - lo // ps + 1)
    label = (f"{B} x {S}, H{H}/{Hkv} D{hd}, "
             f"{'ring' if ring else 'table'} of {P} pages in {N}"
             + (f", window {window}" if window else "")
             + f", context {context}")
    say(f"  paged decode [{label}]: "
        + ", ".join(f"{f} {ms[f]:.3f} ms" for f in ms)
        + f" a call (smoke wall), {walked} pages walked, worst |diff| "
        f"{diff:.4f} ({band:.3f} of the bf16 band), rule -> {seen[0]}")
    if band > 1.0:
        raise AssertionError(
            f"forms differ beyond the bf16 band: {diff} ({band})")
    return _forms_row(label, ms, walked, kp, diff, seen[0])


def paged_prefill_case(B, S, H, Hkv, P, N, hd=128, ps=16, window=None,
                       ring=False, start=0, reps=20, blocks=None) -> dict:
    """The flash form of ``paged_attention_chunk`` (the Pallas kernel
    ``dtt_paged_prefill``) against the XLA form it took the place of,
    the queries a block at a time wherever the float32 logits of one
    pass would not fit, on one layer's bf16 pool: a prompt chunk of
    ``S`` rows from position ``start`` on, a sequence, over a table of
    ``P`` pages or a ring of them that has turned ``start // (P * ps)``
    times, the chunk's own rows written and the last sequence dead.
    The worst absolute difference, each form's smoke time a call, and
    the form the rule takes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import as_layer

    ks = jax.random.split(jax.random.PRNGKey(SEED + 3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    kp, vp = (as_layer(jax.random.normal(k, (Hkv, N, ps, hd),
                                         jnp.bfloat16))
              for k in ks[1:])
    rng = np.random.default_rng(SEED + 3)
    tables = (rng.permutation(N - 1)[:B * P].reshape(B, P) + 1
              ).astype(np.int32)
    q_pos = np.tile(start + np.arange(S, dtype=np.int32), (B, 1))
    if B > 1:
        q_pos[-1] = -1
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(q_pos))
    slots = P * ps

    def by_blocks(q, kp, vp, tables, q_pos):
        """PR 32's ``_tile_attention``: one pass, or a ``lax.map`` over
        the smallest power-of-two number of query blocks whose logits
        fit."""
        n = S
        while not pa._one_pass_fits((B, n, H, hd), slots) \
                and n % 2 == 0:
            n //= 2
        kd, vd = kp.pages(tables), vp.pages(tables)
        slot = jnp.arange(slots, dtype=jnp.int32)[None]

        def one(lo):
            rows, at = (jax.lax.dynamic_slice_in_dim(x, lo, n, axis=1)
                        for x in (q, q_pos))
            return pa._tile_attention(
                kp.layout, rows, kd, vd,
                pa._visible(at, slot, window, slots if ring else None))
        out = jax.lax.map(one, jnp.arange(S // n, dtype=jnp.int32) * n)
        return out.transpose(1, 0, 2, 3, 4).reshape(q.shape)

    def flash(*args):
        return pa._flash_attention(*args, window, ring, blocks)

    out, ms = {}, {}
    for form, fn in (("xla", by_blocks), ("flash", flash)):
        run, out[form], ms[form] = _smoke_time(fn, args, reps)
        if form == "flash" and "dtt_paged_prefill" not in run.as_text():
            raise AssertionError("no dtt_paged_prefill custom call in "
                                 "the compiled flash form")
    diff = float(jnp.abs(out["flash"].astype(jnp.float32)
                         - out["xla"].astype(jnp.float32)).max())
    band = _close("paged_prefill", out["flash"], out["xla"])
    with pa.observe_forms() as seen:
        jax.eval_shape(lambda *a: pa.paged_attention_chunk(
            *a, window=window, ring=ring), *args)
    label = (f"{B} x {S}, H{H}/{Hkv} D{hd}, "
             f"{'ring' if ring else 'table'} of {slots} slots"
             + (f", window {window}" if window else "")
             + f", from {start}")
    say(f"  paged prefill [{label}]: xla {ms['xla']:.3f} ms, flash "
        f"{ms['flash']:.3f} ms a call (smoke wall), worst |diff| "
        f"{diff:.4f} ({band:.3f} of the bf16 band), rule -> {seen[0]}")
    if band > 1.0:
        raise AssertionError(
            f"forms differ beyond the bf16 band: {diff} ({band})")
    return {"ok": True, "shape": label, "xla_ms": ms["xla"],
            "flash_ms": ms["flash"], "max_abs_diff": diff,
            "rule": seen[0]}


# dots3-note-ep8's two kinds of latent layer at their published widths
# (perfbench/configs/dots3-note-ep8.json): H, rank, nope, rope, v, the
# pages of a sequence's row (a table of 16,384 rows; a ring of 97
# pages), and the full layer's indexer (heads, width, top-k).
SPARSE_LATENT = {
    "full": dict(H=128, rank=512, nope=128, rope=64, v=128, P=1024,
                 index=(64, 128, 2048)),
    "window": dict(H=64, rank=1024, nope=192, rope=64, v=128, P=97,
                   window=513),
}


def sparse_latent_case(kind: str, B: int, S: int, context: int = 8192,
                       ps: int = 16, reps: int = 10, contexts=(),
                       dense: bool = True, **dims) -> dict:
    """One layer's ``latent_attention_chunk`` of ``kind`` (``"full"``:
    under the learned selection; ``"window"``: over a ring) at
    dots3-note-ep8's widths (``dims``: others, for a rehearsal or a
    longer table), ``B`` sequences of ``S`` queries that end at
    ``context``: its smoke time a call and, for a full layer, that of
    dense attention over the same table (``dense``; every earlier
    position seen, queries a block at a time where the logits ask for
    it). Where a full layer's queries can share rows (``S * topk``
    over the table's rows: a prompt chunk), its masked and its gather
    form are timed side by side and held to each other, at ``context``
    and at each of ``contexts``: the numbers ``sparse_form`` rests on.
    Checked besides: the selection with room for every position gives
    what dense attention gives; a ring no longer than its slots gives
    what the same pages give as a table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import PoolLayout

    d = {**SPARSE_LATENT[kind], **dims}
    bf = jnp.bfloat16
    H, rank, P = d["H"], d["rank"], d["P"]
    ks = jax.random.split(jax.random.PRNGKey(SEED + B * 31 + S), 9)
    N = B * P + 1
    zero = jnp.zeros((), jnp.int32)

    def layer(key, width):
        lay = PoolLayout(1, width)
        return lay.layer(jax.random.normal(
            key, (1, N, ps, lay.lanes), bf), zero)

    rng = np.random.default_rng(SEED + B)
    tables = (rng.permutation(N - 1).reshape(B, P) + 1).astype(np.int32)
    back = rng.integers(0, 64, B)

    def positions(ends):
        return jnp.asarray(ends[:, None] - S + np.arange(S)[None, :],
                           jnp.int32)

    heads = (jax.random.normal(ks[0], (B, S, H, d["nope"]), bf),
             jax.random.normal(ks[1], (B, S, H, d["rope"]), bf))
    pools = (layer(ks[2], rank), layer(ks[3], d["rope"]),
             jnp.asarray(tables))
    up = (jax.random.normal(ks[4], (rank, H, d["nope"]), bf)
          * rank ** -0.5,
          jax.random.normal(ks[5], (rank, H, d["v"]), bf) * rank ** -0.5)
    ends = np.full(B, context) - back

    def at(context):
        return (*heads, *pools,
                positions(np.full(B, context) - back), *up)
    args = at(context)
    label = f"{kind} {B} x {S} at {context}, H{H} rank {rank}, P {P}"
    ms, out = {}, {}
    if kind == "full":
        J, di, topk = d["index"]
        iq = jax.random.normal(ks[6], (B, S, J, di), bf)
        iw = jax.random.normal(ks[7], (B, S, J), jnp.float32)
        ip = layer(ks[8], di)
        Sk = P * ps

        def sparse(k):
            return lambda qn, qr, c, r, t, qp, uk, uv, iq, iw, ip: \
                pa.latent_attention_chunk(
                    qn, qr, c, r, t, qp, uk, uv,
                    select=pa.Selection(iq, iw, ip, k))

        def dense_call(qn, qr, c, r, t, qp, uk, uv):
            return pa.latent_attention_chunk(qn, qr, c, r, t, qp, uk,
                                             uv, window=Sk)
        with pa.observe_forms() as seen:
            _r, out["sparse"], ms["sparse"] = _smoke_time(
                sparse(topk), args + (iq, iw, ip), reps)
        extra = {"sparse_ms": ms["sparse"]}
        band = 0.0
        if dense:
            _r, out["dense"], ms["dense"] = _smoke_time(dense_call, args,
                                                        reps)
            _r, out["all_kept"], _ms = _smoke_time(
                sparse(Sk), args + (iq, iw, ip), 1)
            band = _close("sparse_latent", out["all_kept"], out["dense"])
            extra["dense_ms"] = ms["dense"]
        say(f"  sparse latent [{label}]: selection of {topk} "
            f"{ms['sparse']:.3f} ms a call (smoke wall; "
            f"{'+'.join(seen)})"
            + (f", dense {ms['dense']:.3f} ms, all kept against dense "
               f"{band:.3f} of the bf16 band" if dense else ""))
        if S * topk > Sk:
            # Queries that can share rows: the masked form and the
            # gather form of the same call, whichever the rule takes.
            rule, forms = pa.sparse_form, {}
            for c in dict.fromkeys((context, *contexts)):
                got, row = {}, {}
                for form in ("flash", "absorbed"):
                    if c == context and seen[0] == form + ".sparse":
                        got[form], row[form + "_ms"] = (out["sparse"],
                                                        ms["sparse"])
                        continue
                    pa.sparse_form = lambda *a, _f=form: _f
                    try:
                        _r, got[form], row[form + "_ms"] = _smoke_time(
                            sparse(topk), at(c) + (iq, iw, ip), reps)
                    finally:
                        pa.sparse_form = rule
                row["err_over_bf16_band"] = _close(
                    "sparse_latent", got["flash"], got["absorbed"])
                band = max(band, row["err_over_bf16_band"])
                forms[c] = row
                say(f"  sparse latent [{label}] at {c}: masked "
                    f"{row['flash_ms']:.3f} ms, gather "
                    f"{row['absorbed_ms']:.3f} ms a call (smoke wall), "
                    f"{row['err_over_bf16_band']:.3f} of the bf16 band "
                    "apart")
            extra["forms_ms"] = forms
    else:
        def ring(is_ring):
            return lambda qn, qr, c, r, t, qp, uk, uv: \
                pa.latent_attention_chunk(qn, qr, c, r, t, qp, uk, uv,
                                          window=d["window"],
                                          ring=is_ring)
        with pa.observe_forms() as seen:
            _r, _o, ms["ring"] = _smoke_time(ring(True), args, reps)
        # Before the ring's first turn a position is its ring slot.
        short = (*heads, *pools,
                 positions(np.minimum(ends, P * ps - 1)), *up)
        _r, out["ring"], _ms = _smoke_time(ring(True), short, 1)
        _r, out["table"], _ms = _smoke_time(ring(False), short, 1)
        band = _close("sparse_latent", out["ring"], out["table"])
        extra = {"ring_ms": ms["ring"]}
        say(f"  sparse latent [{label}]: ring {ms['ring']:.3f} ms a "
            f"call (smoke wall; {seen[0]}), ring against table "
            f"{band:.3f} of the bf16 band")
    if band > 1.0:
        raise AssertionError(f"{label}: beyond the bf16 band ({band})")
    return {"ok": True, "shape": label, "form": seen[0],
            "select": [f for f in seen if f.startswith("select.")],
            "err_over_bf16_band": band, **extra}


def compare_case(name: str, run, ref, inputs, label: str) -> dict:
    """Compile ``run`` for the chip, require a Mosaic kernel in it, and
    compare what it computes with ``ref``."""
    import jax

    args = inputs()
    compiled = run.lower(*args).compile()
    n_pallas = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    if n_pallas < 1:
        raise AssertionError("no tpu_custom_call in the compiled "
                             "program")
    got = jax.block_until_ready(compiled(*args))
    want = jax.block_until_ready(ref(*args))
    errs = {n: round(_close(f"{name}.{n}", g, w), 3)
            for n, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
    say(f"  {name}: [{label}] pallas_calls={n_pallas} "
        f"err/band={errs}")
    if max(errs.values()) > 1.0:
        raise AssertionError(f"mismatch beyond the bf16 band: {errs}")
    return {"ok": True, "shape": label, "pallas_calls": n_pallas,
            "err_over_bf16_band": errs}


def phase_kernels() -> dict:
    def compared(name, build, *args, **kw):
        return name, lambda: compare_case(name, *build(*args, **kw))

    cases = dict([
        compared("flash_fused_headline", flash_case, 4, 12, 12, 1024, 64),
        compared("flash_split_bwd", flash_case, 1, 2, 2, 8192, 128,
                 expect_fused=False),
        compared("flash_sliding_window", flash_case, 1, 4, 4, 4096, 64,
                 window=1024),
        compared("flash_gqa", flash_case, 2, 8, 2, 1024, 128),
        # gpt2-xl's engine (perfbench/configs/gpt2-xl.json): resident
        # decode and spec_k 4.
        ("paged_forms_xl_16x1",
         lambda: paged_forms_case(16, 1, 25, 25, P=64, N=385)),
        ("paged_forms_xl_16x4",
         lambda: paged_forms_case(16, 4, 25, 25, P=64, N=385)),
        # smallthinker-21b-ep4's prompt chunk over a window layer's
        # ring past its first turn: the flash form's kernel.
        ("paged_prefill_ring_1x1024",
         lambda: paged_prefill_case(1, 1024, 28, 4, P=320, N=10241,
                                    window=4096, ring=True, start=8192)),
        # ... and its resident decode call over a ring that has turned:
        # the ragged form's kernel against the gather form.
        ("paged_decode_ring_32x1",
         lambda: paged_decode_case(32, 1, 28, 4, P=320, N=10241,
                                   window=4096, ring=True,
                                   context=10243)),
        # dots3-note-ep8's two kinds of latent layer, a decode
        # iteration and a prompt chunk at 8k of context; the full
        # layer's chunk in its masked and its gather form at 4k, 8k
        # and 16k.
        ("sparse_latent_full_32x1",
         lambda: sparse_latent_case("full", 32, 1)),
        ("sparse_latent_full_1x1024",
         lambda: sparse_latent_case("full", 1, 1024,
                                    contexts=(4096, 16384))),
        ("sparse_latent_window_32x1",
         lambda: sparse_latent_case("window", 32, 1)),
        ("sparse_latent_window_1x1024",
         lambda: sparse_latent_case("window", 1, 1024)),
    ])
    results = {}
    for name, case in cases.items():
        t0 = time.perf_counter()
        try:
            results[name] = case()
            say(f"  {name}: ok (smoke wall "
                f"{time.perf_counter() - t0:.1f}s)")
        except Exception as e:  # noqa: BLE001 — report every kernel
            traceback.print_exc()
            results[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"[:400]}
            say(f"  {name}: FAILED {results[name]['error']}")
    failed = [n for n, r in results.items() if not r["ok"]]
    if failed:
        raise AssertionError(f"kernel cases failed: {failed}")
    return results


# -- phase 2: trainer ---------------------------------------------------------


class _CacheLog(logging.Handler):
    """Collect JAX's persistent-compile-cache hit/miss lines (module
    names included) while the trainer runs."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits: list[str] = []
        self.misses: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if "Persistent compilation cache hit" in msg:
            self.hits.append(msg.split("'")[1])
        elif "PERSISTENT COMPILATION CACHE MISS" in msg:
            self.misses.append(msg.split("'")[1])


def phase_trainer() -> dict:
    from distributed_training_tpu import native, telemetry
    from distributed_training_tpu.train import cli

    run_dir = os.path.join(OUT, "train")
    argv = train_argv(OUT, "train", "train.save_every=1")
    say(f"  python -m distributed_training_tpu.train {' '.join(argv)}")
    compiler_log = logging.getLogger("jax._src.compiler")
    cache_log = _CacheLog()
    old_level, old_prop = compiler_log.level, compiler_log.propagate
    compiler_log.addHandler(cache_log)
    compiler_log.setLevel(logging.DEBUG)
    compiler_log.propagate = False
    t0 = time.perf_counter()
    try:
        rc = cli.main(argv)
    finally:
        compiler_log.removeHandler(cache_log)
        compiler_log.setLevel(old_level)
        compiler_log.propagate = old_prop
        telemetry.uninstall()
    wall = time.perf_counter() - t0
    if rc != 0:
        raise AssertionError(f"train.cli.main returned {rc}")

    rows = [r for r in load_jsonl(os.path.join(run_dir, "metrics.jsonl"))
            if "loss" in r]
    losses = [r["loss"] for r in rows]
    if len(losses) != STEPS or not all(
            isinstance(x, float) and math.isfinite(x) for x in losses):
        raise AssertionError(f"want {STEPS} finite losses, got {losses}")
    # Random weights: the first loss sits at ln(vocab), a wrong one
    # (bad labels, bad logits) does not.
    if abs(losses[0] - math.log(50257)) > 0.5:
        raise AssertionError(
            f"first loss {losses[0]:.3f} is not near ln(50257) = "
            f"{math.log(50257):.3f}")

    events = load_jsonl(os.path.join(run_dir, "events.jsonl"))
    audits = [e for e in events if e.get("kind") == "collectives"]
    if not audits:
        raise AssertionError(
            "no `collectives` event: the post-step-one audit of the "
            "compiled step failed or never ran (see training.log)")
    pallas_calls = audits[0]["pallas_calls"]
    if pallas_calls < 2:
        raise AssertionError(
            f"{pallas_calls} tpu_custom_call(s) in the compiled train "
            f"step, want >= 2 (flash forward + backward): the step "
            f"took the naive attention path")
    hbm = [e for e in events if e.get("kind") == "hbm"]
    peak = max((d["stats"] or {}).get("peak_bytes_in_use", 0)
               for e in hbm for d in e["devices"]) if hbm else 0

    ckpt_root = os.path.join(run_dir, "checkpoints")
    steps_saved = sorted(int(d) for d in os.listdir(ckpt_root)
                         if d.isdigit())
    if steps_saved != [STEPS]:
        raise AssertionError(
            f"want one checkpoint at step {STEPS} under {ckpt_root}, "
            f"found {steps_saved}")
    ckpt_bytes = sum(
        os.path.getsize(os.path.join(dp, f))
        for dp, _d, fs in os.walk(os.path.join(ckpt_root, str(STEPS)))
        for f in fs)
    # 124M params + two AdamW moments in f32 is 1.49e9 bytes before
    # the store's compression.
    if ckpt_bytes < 1.0e9:
        raise AssertionError(
            f"checkpoint holds {ckpt_bytes} bytes, want >= 1.0e9")
    # Checked; the payload is too large to carry back from the chip
    # machine with the logs.
    shutil.rmtree(ckpt_root)

    # The audit lowers the SAME step again (from abstract inputs): one
    # backend compile of it means the audit was served from a cache —
    # JAX's in-memory one when the lowering is identical, else the
    # persistent one (a hit logged under the step's name).
    step_compiles = cache_log.misses.count("jit_train_step")
    audit_reused = (step_compiles == 1 if cache_log.misses else None)
    say(f"  losses {[round(x, 4) for x in losses]}")
    say(f"  collectives audit: pallas_calls={pallas_calls} "
        f"total_collectives={audits[0]['total_collectives']}")
    say(f"  persistent compile cache: hits {cache_log.hits} misses "
        f"{cache_log.misses}")
    say(f"  backend compiles of the train step: {step_compiles} "
        f"(the audit's second lowering reused the first: "
        f"{audit_reused})")
    say(f"  checkpoint step {STEPS}: {ckpt_bytes / 1e9:.2f} GB written "
        f"(then deleted); memory_stats peak_bytes_in_use "
        f"{peak / 2**30:.2f} GiB; data loader: "
        f"{'native (dtt_native.cpp)' if native.available() else 'NumPy fallback'}")
    say(f"  smoke wall (compile + {STEPS} steps + save) {wall:.1f}s")
    return {"losses": losses, "pallas_calls": pallas_calls,
            "train_step_backend_compiles": step_compiles,
            "cache_hits": cache_log.hits,
            "cache_misses": cache_log.misses,
            "checkpoint_bytes": ckpt_bytes,
            "peak_hbm_bytes": peak,
            "loader": "native" if native.available() else "numpy"}


# -- phase 3: engine ----------------------------------------------------------


def _post_generate(port: int, body: dict) -> list:
    """POST /generate; returns the tokens (streamed: one JSON line per
    token, then a final record that must repeat them)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/generate", json.dumps(body).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise AssertionError(
                f"/generate -> {resp.status}: {resp.read()[:300]!r}")
        if not body.get("stream"):
            return json.loads(resp.read())["tokens"]
        lines = []
        while line := resp.readline():
            lines.append(json.loads(line))
        toks = [ln["token"] for ln in lines if "token" in ln]
        if not lines[-1].get("done") or lines[-1]["tokens"] != toks:
            raise AssertionError(f"bad stream tail: {lines[-1]}")
        return toks
    finally:
        conn.close()


def phase_engine() -> dict:
    from distributed_training_tpu import telemetry
    from distributed_training_tpu.serving.engine import (
        Engine, EngineConfig)
    from distributed_training_tpu.serving.server import ServingServer
    from distributed_training_tpu.telemetry.op_scopes import UNSCOPED

    model, params, bf16, prompts = serving_fixture((211, 333, 450))
    ecfg = EngineConfig(max_batch=8, num_pages=8 * PAGES_PER_SEQ + 1,
                        **ENGINE_GEOMETRY)
    tel = telemetry.install(telemetry.Telemetry(
        events_jsonl=os.path.join(OUT, "serve", "events.jsonl")))
    bursts: list = []   # the decode step records: the engine's ledger
    tel.add_observer(lambda r: bursts.append(r)
                     if r.get("kind") == "serving"
                     and r.get("op") == "decode" else None)
    maps: list = []     # HLO instruction -> dtt.* scope, a program
    tel.add_observer(lambda r: maps.append(r)
                     if r.get("kind") == "program_scopes" else None)
    t0 = time.perf_counter()
    eng = Engine(model, bf16, ecfg, mesh=None)
    counts = eng.warmup()
    forms = eng.paged_forms()
    say(f"  engine warm (smoke wall {time.perf_counter() - t0:.1f}s): "
        f"compile_counts {counts}, paged forms {forms}, weights "
        f"{eng.weight_bytes / 1e6:.0f} MB bf16")
    scope_counts = {
        m["program"]: {"instructions": m["instructions"],
                       UNSCOPED: len(m["scopes"].get(UNSCOPED, ())),
                       "mixed": len(m["mixed"])} for m in maps}
    say(f"  program_scopes (instructions, of them under no dtt.* scope, "
        f"fusions that span scopes): {scope_counts}")
    if set(scope_counts) != set(forms):
        raise AssertionError(
            f"programs without a scope map: "
            f"{sorted(set(forms) - set(scope_counts))}")
    srv = ServingServer(eng, port=0)
    if srv.start() is None:
        raise AssertionError("ServingServer did not start")
    try:
        t0 = time.perf_counter()
        streamed = [_post_generate(srv.port, {
            "prompt_ids": p, "max_new_tokens": NEW_TOKENS,
            "stream": True}) for p in prompts]
        # The plain requests arrive together, so they share launches
        # (continuous batching) and meet the prefixes the streamed
        # ones left in the cache.
        plain: list = [None] * len(prompts)

        def ask(i):
            plain[i] = _post_generate(srv.port, {
                "prompt_ids": prompts[i],
                "max_new_tokens": NEW_TOKENS})

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        wall = time.perf_counter() - t0
    finally:
        srv.stop()
        telemetry.uninstall()
        tel.close()
    if srv.leaked_threads:
        raise AssertionError(f"{srv.leaked_threads} server thread(s) "
                             f"outlived stop()")
    for i, (s, p) in enumerate(zip(streamed, plain)):
        if len(s) != NEW_TOKENS or s != p:
            raise AssertionError(
                f"request {i}: streamed {s} != plain {p}")
    after = eng.compile_counts()
    if after != counts:
        raise AssertionError(
            f"recompiled after warm-up: {counts} -> {after}")

    # Reference: the training-path forward over prompt + generated
    # tokens (teacher-forced, so one bf16 near-tie cannot cascade).
    # Each emitted token must sit within bf16 tolerance of the
    # reference argmax's logit.
    worst, exact = 0.0, 0
    reference = make_reference(model, params)
    for prompt, toks in zip(prompts, streamed):
        rows = reference(prompt + toks, len(prompt) - 1)
        for row, tok in zip(rows, toks):
            worst = max(worst, float(row.max() - row[tok]))
            exact += int(row.argmax() == tok)
    n_tok = NEW_TOKENS * len(prompts)
    say(f"  {len(prompts)} streamed == {len(prompts)} plain requests, "
        f"prompts {[len(p) for p in prompts]} tokens, {NEW_TOKENS} new "
        f"each; first answer {streamed[0]}")
    say(f"  vs full-context forward: {exact}/{n_tok} tokens are its "
        f"argmax, worst logit gap {worst:.4f} (tolerance {TIE_TOL})")
    say(f"  no recompile after warm-up: {after}; host syncs "
        f"{eng.host_syncs}; resident bursts {len(bursts)}, "
        f"{sum(r['tokens'] for r in bursts)} tokens in "
        f"{sum(r['slot_iters'] for r in bursts)} slot iterations; "
        f"prefix {eng.prefix_stats}")
    say(f"  smoke wall for the {2 * len(prompts)} requests {wall:.1f}s")
    if worst > TIE_TOL:
        raise AssertionError(
            f"an emitted token's reference logit is {worst:.4f} below "
            f"the reference argmax (tolerance {TIE_TOL})")
    return {"tokens": streamed, "argmax_agreement": [exact, n_tok],
            "worst_logit_gap": worst, "compile_counts": after,
            "paged_forms": forms, "program_scopes": scope_counts}


# -- driver ------------------------------------------------------------------


def main() -> int:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"jax {jax.__version__} device {device}")
    if device["platform"] != "tpu":
        print("[chip_smoke] FAILED: JAX found no TPU (platform "
              f"{device['platform']!r}); nothing was run",
              file=sys.stderr)
        return 1

    sys.path.insert(0, REPO)
    from distributed_training_tpu.runtime import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)

    summary: dict = {"device": device, "jax": jax.__version__}
    failed = []
    for name, phase in (("kernels", phase_kernels),
                        ("trainer", phase_trainer),
                        ("engine", phase_engine)):
        say(f"phase {name}")
        t0 = time.perf_counter()
        try:
            summary[name] = phase()
            say(f"phase {name} ok (smoke wall "
                f"{time.perf_counter() - t0:.1f}s)")
        except Exception as e:  # noqa: BLE001 — run every phase, fail at the end
            traceback.print_exc()
            summary[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"[:600]}
            say(f"phase {name} FAILED: {type(e).__name__}: "
                f"{str(e)[:300]}")
            failed.append(name)
        gc.collect()
        stats = devices[0].memory_stats() or {}
        say(f"  device memory in use after phase: "
            f"{stats.get('bytes_in_use', 0) / 2**20:.0f} MiB")
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if failed:
        print(f"[chip_smoke] FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
