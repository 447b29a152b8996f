"""Paged attention: decode/prefill attention over a paged KV pool.

The serving KV cache (serving/kv_cache.py) stores keys/values in
fixed-size PAGES drawn from a preallocated pool — virtual memory for
KV, so concurrent sequences of wildly different lengths share one HBM
reservation with no per-sequence max_len buffers and no copying on
join/evict. This module is the attention math over that layout:

- pool layout (per layer): ``k_pages``/``v_pages`` of shape
  ``(n_kv_heads, num_pages, page_size, head_dim)`` — kv-head-major,
  the canonical layout of the TPU Pallas paged-attention kernel
  (``jax.experimental.pallas.ops.tpu.paged_attention``), so the
  kernel path needs zero relayout;
- per-sequence ``page_indices`` row: logical page ``j`` of the
  sequence lives in physical page ``page_indices[j]``; logical
  position ``p`` is slot ``p % page_size`` of logical page
  ``p // page_size``.

A third entrypoint, ``latent_attention_chunk``, attends over a LATENT
cache (one shared row a token instead of keys and values a head), in an
absorbed or an expanded form that ``latent_form`` takes from the shapes.

Two entrypoints over keys and values:

- ``paged_attention`` — single-token decode: one query per sequence
  against its pages. Dispatches to the TPU Pallas kernel when
  ``kernel_supported`` (one async DMA per non-contiguous page,
  double-buffered — see the Pallas guide's paged-attention walk-
  through; it needs ``head_dim % 128 == 0``, so not GPT-2's 64);
  everywhere else it is ``paged_attention_chunk`` with ``S = 1``.
  Exact same numerics contract as ops/attention.py: fp32
  logits/softmax, output in q.dtype, GQA via hkv-major grouping.
- ``paged_attention_chunk`` — multi-query form: ``S`` queries per
  sequence, each masked to logical positions ``<= its own position``.
  Every engine program but the first prefill chunk calls it once a
  layer (the first chunk has no prefix and runs the ordinary causal
  path, flash-eligible, via ops.attention). It has two forms with the
  same mathematics, and ``chunk_form`` takes the cheaper from the
  static shapes when the program is traced:

  - *gather form* reads ``B * P * ps`` slots: every sequence's whole
    table row copied dense in logical order, whatever is live, and
    transposed out of the pool's head-major order. Right when queries
    are many and sequences few (prefill chunks: 4 x 128, 1 x 128);
  - *pool form* reads the layer's ``N * ps`` slots once for all
    sequences, in place, and scores every query against all of them
    under a mask made from the page table turned inside out. No
    gather, no transpose, no copy. Right when queries are few (the
    resident decode loop 16 x 1, speculative verify 16 x 4, the
    per-token decode program), where it also keeps the contraction on
    the MXU in the pool's dtype: with one query a sequence the gather
    form's per-sequence dot has one row, and the TPU compiler lowers
    it to a float32 copy of the gathered block and a multiply-reduce.

  The ragged kernel that reads only the pages a sequence owns is the
  end state (ROADMAP S1); it wants the pool re-laid-out first (S2).

There is no switch between the forms: ``paged_impl`` means kernel or
reference and nothing else. The form each compiled program took
(``"pool"``, ``"gather"``, ``"kernel"``) is seen at trace time by
``observe_forms`` and reported per program by ``Engine.paged_forms()``
and the ``serving_warmup`` telemetry record (docs/observability.md).
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from distributed_training_tpu.runtime import default_platform


_observers: list[list[str]] = []


@contextlib.contextmanager
def observe_forms():
    """Collect, while open, the form every ``paged_attention`` and
    ``paged_attention_chunk`` call takes (``"kernel"``, ``"pool"``,
    ``"gather"``). The form follows from static shapes, so a call is
    seen when the program around it is TRACED: the engine opens this
    around each program's body (``serving/engine.py::_named``)."""
    seen: list[str] = []
    _observers.append(seen)
    try:
        yield seen
    finally:
        _observers.remove(seen)


def _took(form: str) -> None:
    for seen in _observers:
        seen.append(form)


def kernel_supported(q: jax.Array, k_pages: jax.Array,
                     page_size: int | None = None) -> bool:
    """Should single-token decode dispatch to the TPU Pallas kernel?

    Conservative, mirroring ops/flash_attention.supported(): TPU
    platform only (elsewhere the interpreter is orders of magnitude
    slower than XLA's gather), MXU-friendly head_dim, and a page size
    the kernel's DMA descriptor tiles evenly. A backend that fails to
    start, or a CPU nobody asked for, raises — it is not read as "use
    the reference" (runtime.default_platform)."""
    if default_platform() != "tpu":
        return False
    head_dim = q.shape[-1]
    ps = page_size if page_size is not None else k_pages.shape[2]
    if head_dim % 128:
        return False
    if ps % 16:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return True


def _gather_pages(pages: jax.Array, page_indices: jax.Array
                  ) -> jax.Array:
    """(Hkv, N, ps, hd) pool + (B, P) tables → (B, P*ps, Hkv, hd)
    dense per-sequence KV, logical order. Slot ``s`` of the result is
    logical position ``s`` of the sequence."""
    Hkv, _N, ps, hd = pages.shape
    B, P = page_indices.shape
    g = pages[:, page_indices]              # (Hkv, B, P, ps, hd)
    return g.transpose(1, 2, 3, 0, 4).reshape(B, P * ps, Hkv, hd)


def _masked_softmax(logits: jax.Array, visible: jax.Array
                    ) -> jax.Array:
    """Float32 softmax over the last axis of ``logits`` where
    ``visible`` (broadcast against them) holds; a row with nothing
    visible gives zeros, not the uniform weights a softmax of equal
    ``finfo.min`` would."""
    logits = jnp.where(visible, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.where(jnp.any(visible, axis=-1, keepdims=True), probs,
                     0.0)


def _masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      visible: jax.Array) -> jax.Array:
    """GQA attention with an explicit visibility mask.

    q (B, S, H, hd); k/v (B, Sk, Hkv, hd); visible (B, S, Sk) bool.
    fp32 logits/softmax (ops/attention.py numerics contract), output
    in q.dtype. Rows with zero visible keys (inactive batch slots)
    produce zeros, not NaN — the engine masks their outputs anyway,
    but NaN would poison debugging."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads "
                         f"{Hkv}")
    group = H // Hkv
    qg = q.reshape(B, S, Hkv, group, hd)
    logits = jnp.einsum("bshgd,bkhd->bhgsk", qg, k,
                        preferred_element_type=jnp.float32)
    probs = _masked_softmax(logits * (hd ** -0.5),
                            visible[:, None, None])
    out = jnp.einsum("bhgsk,bkhd->bshgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, hd).astype(q.dtype)


# HBM bytes a v5e moves for each nominal byte, fitted to one layer's
# call timed on the chip in both forms at thirteen engine shapes
# (benchmarks/paged_form_table.py; the table is in PERF.md section 6).
# Nominal sizes mislead by these factors, which is why they are here.
_GATHER_COPY = 9.0       # gathered KV: the gather, the transpose to
#                          (B, Sk, Hkv, hd), head_dim re-tiled to 128
_GATHER_COPY_ROW = 32.0  # ... with ONE query row a kv head (S * group
#                          == 1) the contraction is no dot: a float32
#                          copy of the block and a multiply-reduce
_POOL_READ = 5.3         # the pool read in place by an underfed MXU
_LOGITS = 5.75           # float32 logits: written, masked, softmaxed,
#                          cast to the values' dtype, read (both forms)


def chunk_form(q_shape, pool_shape, table_shape, itemsize: int) -> str:
    """``"pool"`` or ``"gather"``: the form of ``paged_attention_chunk``
    that moves fewer bytes for q ``(B, S, H, hd)``, a pool ``(Hkv, N,
    ps, hd)`` of ``itemsize``-byte elements and a table ``(B, P)``.
    Shapes are static, so this runs when a program is traced: one
    algorithm whose cost crosses over with the shape. The gather form
    copies ``B * P * ps`` slots whatever is live and scores them; the
    pool form reads ``N * ps`` slots once for all sequences and scores
    every query against all of them, so it wins while queries are few
    (decode, speculative verify) and loses by its logits when they are
    many (prefill chunks)."""
    B, S, H, hd = q_shape
    Hkv, N, ps, _ = pool_shape
    P = table_shape[1]
    kv_slot = 2 * Hkv * hd * itemsize           # keys and values
    copy = (_GATHER_COPY_ROW if S * (H // Hkv) == 1 else _GATHER_COPY)
    gather = B * P * ps * (copy * kv_slot + _LOGITS * S * H * 4)
    pool = N * ps * (_POOL_READ * kv_slot + _LOGITS * B * S * H * 4)
    return "pool" if pool < gather else "gather"


def _gather_attention(q: jax.Array, k_pages: jax.Array,
                      v_pages: jax.Array, page_indices: jax.Array,
                      q_positions: jax.Array) -> jax.Array:
    """Gather form: each sequence's pages copied dense in logical
    order (``B * P * ps`` slots, however few are live), then masked
    attention over the copy."""
    kd = _gather_pages(k_pages, page_indices)
    vd = _gather_pages(v_pages, page_indices)
    Sk = kd.shape[1]
    slot = jnp.arange(Sk, dtype=jnp.int32)
    visible = (slot[None, None, :] <= q_positions[:, :, None]) \
        & (q_positions[:, :, None] >= 0)
    return _masked_attention(q, kd, vd, visible)


def _pool_attention(q: jax.Array, k_pages: jax.Array,
                    v_pages: jax.Array, page_indices: jax.Array,
                    q_positions: jax.Array) -> jax.Array:
    """Pool form: every query against the layer's WHOLE pool where it
    lies (``N * ps`` slots a head, read once for all sequences), the
    page table turned inside out into a visibility mask. No gather, no
    transpose, no copy of the pool; the same keys at the same
    precisions as the gather form, summed in physical order.

    Physical page ``n`` holds logical page ``j`` of sequence ``b`` iff
    ``page_indices[b, j] == n``; the LOWEST such ``j`` counts, so the
    unused tail of a row (all scratch page 0) puts page 0 past the
    sequence's last used page, where no query position reaches, and a
    page that copy-on-write sharing put into two rows is visible to
    both."""
    B, S, H, hd = q.shape
    Hkv, N, ps, _ = k_pages.shape
    P = page_indices.shape[1]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads "
                         f"{Hkv}")
    group = H // Hkv
    logical = jnp.arange(P, dtype=jnp.int32)[None, :, None]
    owns = page_indices[:, :, None] \
        == jnp.arange(N, dtype=page_indices.dtype)[None, None, :]
    # (B, N): logical page of each physical page; P where not owned,
    # which is past every position a table of P pages can hold.
    owner = jnp.min(jnp.where(owns, logical, P), axis=1)
    slot_pos = (owner[:, :, None] * ps
                + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
                ).reshape(B, N * ps)
    visible = (slot_pos[:, None, :] <= q_positions[:, :, None]) \
        & (q_positions[:, :, None] >= 0)             # (B, S, N*ps)
    qg = q.reshape(B, S, Hkv, group, hd)
    # Batched over the kv head with B*S*group rows a head: an MXU dot
    # in the pool's dtype also at S = group = 1, where the gather
    # form's per-sequence contraction has one row and is lowered to a
    # float32 multiply-reduce.
    logits = jnp.einsum("bshgd,hkd->hbsgk", qg,
                        k_pages.reshape(Hkv, N * ps, hd),
                        preferred_element_type=jnp.float32)
    probs = _masked_softmax(logits * (hd ** -0.5),
                            visible[None, :, :, None, :])
    out = jnp.einsum("hbsgk,hkd->hbsgd", probs.astype(v_pages.dtype),
                     v_pages.reshape(Hkv, N * ps, hd),
                     preferred_element_type=jnp.float32)
    # Transposed apart: with the head moved inside the einsum's own
    # output, XLA's CPU runtime has no bfloat16 dot to run it with.
    out = out.transpose(1, 2, 0, 3, 4)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def paged_attention_chunk(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array,
                          page_indices: jax.Array,
                          q_positions: jax.Array) -> jax.Array:
    """Multi-query paged attention (prefill chunks, reference path).

    q (B, S, H, hd); pools (Hkv, N, ps, hd); page_indices (B, P);
    q_positions (B, S) int32 — each query's ABSOLUTE position. Query
    (b, s) attends logical positions ``<= q_positions[b, s]`` of
    sequence b (the chunk's own KV must already be written to the
    pool). Negative q_positions mark padding queries (zero output).
    The form (``chunk_form``) follows from the static shapes.
    """
    form = chunk_form(q.shape, k_pages.shape, page_indices.shape,
                      k_pages.dtype.itemsize)
    _took(form)
    attend = _pool_attention if form == "pool" else _gather_attention
    return attend(q, k_pages, v_pages, page_indices, q_positions)


# How many of its own multiply-adds ((nope + v) * rank a gathered row a
# head) the expansion of the latent rows is worth on a v5e before the
# queries of one call repay it: fitted to one layer's call at the
# published widths (benchmarks/latent_form_table.py; PERF.md section 6).
_EXPAND_COST = 2.5


def latent_form(q_shape, dims) -> str:
    """``"expanded"`` or ``"absorbed"`` for ``latent_attention_chunk``
    at q ``(B, S, H)`` and widths ``dims = (rank, nope, v)``. Both
    forms gather the same rows. Expanding turns each into ``H`` keys
    and values (``(nope + v) * rank`` multiply-adds a row a head), after
    which a query-key pair costs ``nope + v``; absorbed it costs ``2 *
    rank``. So the expansion pays where queries are many (prompt
    chunks) and never at decode. On the chip the crossing goes with the
    queries of the whole call, ``B * S``, not of one sequence (the table:
    1 x 1024 is a draw, 4 x 256 expands a third faster)."""
    B, S, _H = q_shape
    rank, nope, v = dims
    return ("expanded"
            if B * S * (2 * rank - nope - v)
            > _EXPAND_COST * (nope + v) * rank else "absorbed")


def latent_attention_chunk(q_nope: jax.Array, q_rope: jax.Array,
                           c_pages: jax.Array, r_pages: jax.Array,
                           page_indices: jax.Array,
                           q_positions: jax.Array, w_uk: jax.Array,
                           w_uv: jax.Array) -> jax.Array:
    """Multi-query attention over a LATENT paged cache.

    q_nope (B, S, H, nope), q_rope (B, S, H, rope), RoPE applied;
    c_pages (1, N, ps, rank): a token's latent row after its norm;
    r_pages (1, N, ps, rope): its rotary key after RoPE, one for all
    heads; page_indices (B, P); q_positions (B, S) as in
    ``paged_attention_chunk``; w_uk (rank, H, nope) and w_uv
    (rank, H, v) expand a latent row into a head's key and value.
    Scores are ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
    rope)``, float32 like the softmax. Returns (B, S, H, v).

    Each sequence's rows are gathered dense in logical order, as
    ``_gather_attention`` does. *Absorbed*: ``q_nope . (W_uk c) =
    (W_uk^T q_nope) . c``, so the query is folded into the latent
    width, every head scores the one shared row, the weighted sum is of
    latent rows and ``W_uv`` is applied to it: no key or value a head is
    ever made. *Expanded*: the gathered rows are expanded into keys and
    values and attended as ordinary heads. Same mathematics, rounded in
    another order; ``latent_form`` takes one from the shapes."""
    B, S, H, nope = q_nope.shape
    rope, v = q_rope.shape[-1], w_uv.shape[-1]
    form = latent_form((B, S, H), (c_pages.shape[-1], nope, v))
    _took(form)
    f32 = jnp.float32
    cd = _gather_pages(c_pages, page_indices)[:, :, 0]    # (B, Sk, rank)
    rd = _gather_pages(r_pages, page_indices)[:, :, 0]    # (B, Sk, rope)
    slot = jnp.arange(cd.shape[1], dtype=jnp.int32)
    visible = ((slot[None, None, :] <= q_positions[:, :, None])
               & (q_positions[:, :, None] >= 0))[:, None]
    scores = jnp.einsum("bshe,bke->bhsk", q_rope, rd,
                        preferred_element_type=f32)
    if form == "expanded":
        scores = scores + jnp.einsum(
            "bshn,bkhn->bhsk", q_nope,
            jnp.einsum("bkr,rhn->bkhn", cd, w_uk),
            preferred_element_type=f32)
    else:
        scores = scores + jnp.einsum(
            "bshr,bkr->bhsk",
            jnp.einsum("bshn,rhn->bshr", q_nope, w_uk), cd,
            preferred_element_type=f32)
    probs = _masked_softmax(scores * (nope + rope) ** -0.5,
                            visible).astype(cd.dtype)
    if form == "expanded":
        return jnp.einsum("bhsk,bkhv->bshv", probs,
                          jnp.einsum("bkr,rhv->bkhv", cd, w_uv),
                          preferred_element_type=f32
                          ).astype(q_nope.dtype)
    # The heads stay where the softmax left them: with them moved inside
    # this einsum's own output, XLA's CPU runtime has no bfloat16 dot to
    # run it with.
    ctx = jnp.einsum("bhsk,bkr->bhsr", probs, cd,
                     preferred_element_type=f32).astype(q_nope.dtype)
    return jnp.einsum("bhsr,rhv->bshv", ctx, w_uv)


def paged_attention(q: jax.Array, k_pages: jax.Array,
                    v_pages: jax.Array, lengths: jax.Array,
                    page_indices: jax.Array,
                    impl: str = "auto") -> jax.Array:
    """Single-token decode attention against the paged pool.

    q (B, H, hd) — the current token's query per sequence; pools
    (Hkv, N, ps, hd); lengths (B,) int32 — VALID kv entries per
    sequence, current token's k/v included (attends logical positions
    ``[0, lengths)``; 0 = inactive slot, zero output); page_indices
    (B, P). ``impl``: "auto" (TPU kernel when supported, else
    reference), "kernel", "ref".
    """
    if impl not in ("auto", "kernel", "ref"):
        raise ValueError(f"unknown paged-attention impl '{impl}'")
    use_kernel = (impl == "kernel"
                  or (impl == "auto"
                      and kernel_supported(q, k_pages)))
    if use_kernel:  # pragma: no cover - needs a TPU (chip_smoke.py)
        _took("kernel")
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as tpu_paged_attention,
        )
        # Kernel layout: q (B, H, hd), pools (Hkv, N, ps, hd),
        # lengths (B,), page_indices (B, P) — ours verbatim. Two
        # things the stock kernel leaves to its caller (both found by
        # its first run on a chip, chip_smoke.py): it computes q·k
        # UNSCALED, so q carries the hd**-0.5 (in f32 — the kernel
        # upcasts q anyway, and a bf16-rounded scale would move
        # near-tied argmaxes off the reference path's); and it never
        # writes the output rows of zero-length sequences, whose
        # uninitialized values would reach the scratch page through
        # the next layer's KV write and, as NaN, every sequence that
        # reads a masked slot of it — so inactive rows are zeroed
        # here, the module's contract. The compute block must divide
        # the pages per sequence: up to 4 pages (64 tokens at
        # page_size 16), fewer for a ragged table.
        out = tpu_paged_attention(
            q.astype(jnp.float32) * (q.shape[-1] ** -0.5),
            k_pages, v_pages, lengths, page_indices,
            pages_per_compute_block=math.gcd(
                4, page_indices.shape[1]))
        return jnp.where((lengths > 0)[:, None, None], out,
                         0).astype(q.dtype)
    out = paged_attention_chunk(
        q[:, None], k_pages, v_pages, page_indices,
        (lengths - 1)[:, None].astype(jnp.int32))
    return out[:, 0]
