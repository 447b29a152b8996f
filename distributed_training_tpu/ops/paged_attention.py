"""Paged attention: decode/prefill attention over a paged KV pool.

The serving KV cache (serving/kv_cache.py) stores keys/values in
fixed-size PAGES drawn from a preallocated pool — virtual memory for
KV, so concurrent sequences of wildly different lengths share one HBM
reservation with no per-sequence max_len buffers and no copying on
join/evict. This module is the attention math over that layout:

- the pool: ``k_pages``/``v_pages`` reach this module as one layer of
  the cache's carried pool, unread (``serving/kv_cache.py::
  PoolLayer``: the pool and the layer's number). The cache stores a
  token's row of a layer token-major in whole 128-lane tiles and owns
  the order of the axes; this module reads through the view's two
  accessors, ``slots()`` (every slot of the layer in physical order,
  ``(N * ps, tiles, tile)``) and ``pages(page_indices)`` (a table's
  pages dense in logical order, ``(B, P * ps, tiles, tile)``), and
  contracts on the tiles as they lie (``_tile_attention``);
- per-sequence ``page_indices`` row: logical page ``j`` of the
  sequence lives in physical page ``page_indices[j]``; logical
  position ``p`` is slot ``p % page_size`` of logical page
  ``p // page_size``.

A second entrypoint, ``latent_attention_chunk``, attends over a LATENT
cache (one shared row a token instead of keys and values a head), in an
absorbed or an expanded form that ``latent_form`` takes from the shapes;
over a window layer's ring (``window=``, ``ring=``) and under a learned
selection of positions (``select=``: the indexer's scores over the index
pool's layer, ``index_scores``, and an exact top-k, ``select_topk``,
found by counting in one Pallas kernel, ``dtt_select_topk``) as
well. Under a selection a query reads its chosen rows alone (the gather
form, decode) or, where a sequence's queries choose more rows than its
table has (a prompt chunk), the table is read once and one Pallas
kernel (``dtt_sparse_prefill``, ``_sparse_flash_attention``) attends
under the selection as a mask; ``sparse_form`` takes one from the
shapes.

One entrypoint over keys and values:

- ``paged_attention_chunk`` — ``S`` queries per sequence, each masked
  to logical positions ``<= its own position``; single-token decode is
  ``S = 1``. Every engine program calls it once a layer. Exact same
  numerics contract as ops/attention.py: fp32 logits/softmax, output
  in q.dtype, GQA via hkv-major grouping. It has four forms with the
  same mathematics. ``chunk_form`` takes the cheapest of the first
  three from the static shapes when the program is traced:

  - *gather form* reads ``B * P * ps`` slots: every sequence's whole
    table row copied dense in logical order, whatever is live,
    indexed ``(layer, page)`` straight out of the carried pool. Right
    when queries are many and sequences few (prefill chunks: 4 x 128,
    1 x 128);
  - *pool form* reads the layer's ``N * ps`` slots once for all
    sequences and scores every query against all of them under a mask
    made from the page table turned inside out. No gather; what XLA
    still copies is the layer, once, to put the slots on the lanes
    for the contraction (ROADMAP S1). Right when queries are few and
    the pool is small beside the tables (speculative verify and the
    per-token decode program of a pool that the tables cover several
    times over), where it also keeps the contraction on the MXU in
    the pool's dtype: with one query a sequence the gather form's
    per-sequence dot has one row, and the TPU compiler lowers it to a
    float32 copy of the gathered block and a multiply-reduce;
  - *ragged form*: ONE Pallas TPU kernel (``dtt_paged_decode``,
    ``_ragged_attention``) that is handed the CARRIED pools as they
    are stored, left in HBM, and walks, a sequence, only the pages
    some query of the call sees, where they lie: a page one contiguous
    DMA into VMEM, several a step, the next step's in flight while
    this one's are contracted, the softmax online. Nothing is
    gathered or re-laid, no layer is sliced out of a pool, no logits
    touch HBM, and what it reads goes with what is live, not with the
    table's width. It spreads every query over a whole stored row,
    so it is offered where a call's query rows are few (``_RAGGED_ROWS``:
    the resident decode loop, speculative verify) and the pool's heads
    are on one device; its cost is the bytes of the pages the tables
    can name and a constant a page (the DMAs), both fitted on the chip.

  The first two hold the float32 logits of all their queries at once.
  Where those would pass ``_LOGITS_LIMIT`` (a prompt chunk of 1024
  against a ring of 5,120 slots or a table of 16,384) the call takes
  the fourth:

  - *flash form*: the gather form's copy, attended by one Pallas TPU
    kernel (``dtt_paged_prefill``, ``_flash_attention``) that takes
    the queries and the slots a block at a time and keeps the softmax
    online as ``ops/flash_attention.py`` does for training, so no
    logits are ever held in HBM, the mask is made in the kernel and the
    key blocks no query of a block sees are skipped. The many-query
    half of the ragged kernel: reading a prompt chunk's pages in place
    with the ragged kernel's walk is what is left (ROADMAP S1).

  Both forms take a ``window`` (a query sees the last ``window``
  positions, itself included) and, for a window layer's pool, a table
  that is a RING (``ring=True``, ``serving/kv_cache.py``): entry ``e``
  holds the sequence's logical pages ``e, e + P, e + 2P, ...`` in turn,
  so ring slot ``s`` (``s = e * ps + offset``) holds, of the positions
  ``s, s + P * ps, ...``, the last one written. For a query at ``q``
  that is the position ``q - ((q - s) mod (P * ps))``, unless a later
  row of the same launch has already overwritten it: the ring is as
  long as the window plus the most rows a launch writes before it
  reads, so such a slot lies outside the window either way and the
  mask ``(q - s) mod (P * ps) < window`` needs nothing but the query's
  position and the slot's place.

There is no switch between the forms: static shapes decide, so a
compiled program takes a form always or never. The form each took
(``"pool"``, ``"gather"``, ``"ragged"``, ``"flash"``, of latent attention
``"absorbed"``, ``"expanded"``; under a selection ``"absorbed.sparse"``
or ``"flash.sparse"``, and ``"select.threshold"`` where its top-k was
found by counting; ``".window"`` behind it over a ring) is seen at
trace time by
``observe_forms`` and reported per
program by ``Engine.paged_forms()`` and the ``serving_warmup`` telemetry
record (docs/observability.md).
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_training_tpu.ops.flash_attention import (
    NEG_INF, _platform_is_tpu)


_observers: list[list[str]] = []


@contextlib.contextmanager
def observe_forms():
    """Collect, while open, the form every ``paged_attention_chunk``
    (``"pool"``, ``"gather"``, ``"ragged"``, ``"flash"``) and
    ``latent_attention_chunk``
    (``"absorbed"``, ``"expanded"``, ``".window"`` behind either;
    ``"absorbed.sparse"``, ``"flash.sparse"``) call takes, and every
    ``select_topk`` call (``"select.threshold"``). The form follows from
    static shapes, so a call is seen when the program around it is
    TRACED: the engine opens this around each program's body
    (``serving/engine.py::_named``)."""
    seen: list[str] = []
    _observers.append(seen)
    try:
        yield seen
    finally:
        _observers.remove(seen)


def _took(form: str) -> None:
    for seen in _observers:
        seen.append(form)


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


# Float32 logits one call may hold in one pass. A prompt chunk of 1024
# against a table of 16,384 slots is 1.9 GB of them a layer, beside the
# pools: over the limit the call takes the flash form, which holds
# none. No shape of the engines the benchmark had before PR 32 reaches
# it.
_LOGITS_LIMIT = 256 << 20


def _one_pass_fits(q_shape, slots: int) -> bool:
    """Whether the float32 logits of q ``(B, S, H, hd)`` against
    ``slots`` keys a sequence stay under ``_LOGITS_LIMIT``: the one
    static rule that says where the kernel runs."""
    B, S, H, _ = q_shape
    return B * S * H * slots * 4 <= _LOGITS_LIMIT


def _tile_attention(layout, q: jax.Array, k: jax.Array,
                    v: jax.Array, visible: jax.Array) -> jax.Array:
    """GQA attention with an explicit visibility mask in one pass, on
    the pool's tiles as they lie (``serving/kv_cache.py::PoolLayout``).

    q (B, S, H, hd); k/v ``(B, Sk, tiles, tile)``, or ``(Sk, tiles,
    tile)`` shared by every sequence (the pool form); visible (B, S,
    Sk) bool. The queries are spread onto the lanes of their kv heads
    (zeros on their tile-mates'), so both contractions run over whole
    128-lane tiles with no copy of the keys or values into a layout a
    head at a time; the zeros cost MXU rows and change no sum. fp32
    logits/softmax (ops/attention.py numerics contract), output in
    q.dtype. Rows with zero visible keys (inactive batch slots)
    produce zeros, not NaN — the engine masks their outputs anyway,
    but NaN would poison debugging."""
    qt = layout.spread(q)                     # (B, S, tiles, J, tile)
    kv = "bktl" if k.ndim == 4 else "ktl"
    # Batched over the tile with B*S*J rows a tile: an MXU dot in the
    # pool's dtype also at S = J = 1, where a per-sequence contraction
    # has one row and is lowered to a float32 multiply-reduce.
    logits = jnp.einsum(f"bstjl,{kv}->tbsjk", qt, k,
                        preferred_element_type=jnp.float32)
    probs = _masked_softmax(logits * (q.shape[-1] ** -0.5),
                            visible[None, :, :, None, :])
    out = jnp.einsum(f"tbsjk,{kv}->tbsjl", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    # Transposed apart: with the tile moved inside the einsum's own
    # output, XLA's CPU runtime has no bfloat16 dot to run it with.
    return layout.collect(out.transpose(1, 2, 0, 3, 4)).astype(q.dtype)


# HBM bytes a v5e moves for each nominal byte, fitted to one layer's
# call timed on the chip in both forms at thirteen engine shapes
# (benchmarks/paged_form_table.py; the table is in PERF.md section 6),
# again since the pool is stored in whole 128-lane tiles (PR 29: the
# copies fell from 9.0 and 5.3, no row being re-tiled from 64 lanes).
# Nominal sizes mislead by these factors, which is why they are here.
_GATHER_COPY = 3.8       # gathered KV: the gather out of the carried
#                          pool and the copy that puts the tiles first
_POOL_READ = 1.9         # the layer sliced out of the carried pool and
#                          copied tiles first, then read by the dots
_LOGITS = 2.8            # float32 logits: written, masked, softmaxed,
#                          cast to the values' dtype, read (both forms)
# The ragged form, fitted the same way at the resident decode shapes of
# smallthinker-21b-ep4 and gpt2-xl with a quarter, a half and all of a
# table live (PERF.md section 6, PR 37): what a byte of a walked page
# costs, and a page's two DMAs (keys, values) on top of its bytes,
# which is what makes many short sequences dear. Pages of 20 to 102 kB
# were timed, and over them the fit trades one constant for the other
# (1.21 / 10.8e3 and 1.29 / 4.4e3 on two runs of the table, within 8% of
# each other at every page timed): a page of a few kB, which no engine
# on a chip has, is priced by the first pair, under which its call's
# floor (0.03 ms, not in the rule) keeps it off the kernel.
_RAGGED_READ = 1.2
_RAGGED_PAGE = 11e3

# Query rows one ragged call may spread over a stored row's lanes (tiles
# x queries x rows a tile): decode and speculative verify of every
# engine here; a prompt chunk's thousands keep the gather or flash form.
# Which shapes sit where: gpt2-xl's decode is 13 tiles x 1 query x 2
# heads a tile = 26 rows (104 at four queries a slot),
# smallthinker-21b-ep4's 4 tiles x 7 query heads a kv head = 28, and
# command-a-plus-ep16's 8 tiles x 16 = 128, AT the limit: one query a
# slot takes the kernel (contracting 128 rows over all 1,024 lanes of a
# stored row, zeros on seven tiles of eight), two a slot (speculative
# verify at these head counts) would not (ROADMAP S1).
_RAGGED_ROWS = 128


def chunk_form(q_shape, pool_shape, table_shape, itemsize: int,
               ragged: bool = True) -> str:
    """``"pool"``, ``"gather"`` or ``"ragged"``: the form of
    ``paged_attention_chunk`` that moves fewer bytes for q ``(B, S, H,
    hd)``, a layer of the pool of ``pool_shape = (Hkv, N, ps, hd)`` (kv
    heads, pages, slots a page, a head's width: what it holds, not how
    it is stored) in ``itemsize``-byte elements and a table ``(B, P)``.
    Shapes are static, so this runs when a program is traced: one
    algorithm whose cost crosses over with the shape. The gather form
    copies ``B * P * ps`` slots whatever is live and scores them; the
    pool form reads ``N * ps`` slots once for all sequences and scores
    every query against all of them, so it wins over the gather form
    while queries are few (decode, speculative verify) and loses by its
    logits when they are many (prefill chunks); the ragged form reads
    at most the pages the tables name, ``min(B * P, N)`` of them (a
    sequence's live ones: the rule cannot see how few), once, a DMA
    pair a page, and holds no logits, but spreads every query over a
    whole stored row, so it is offered (``ragged``: the caller's, false
    for a pool whose heads are sharded, which one chip's kernel cannot
    read) only where the call's rows stay under ``_RAGGED_ROWS``: never
    to a prompt chunk."""
    B, S, H, hd = q_shape
    Hkv, N, ps, _ = pool_shape
    P = table_shape[1]
    kv_slot = 2 * Hkv * hd * itemsize           # keys and values
    per = max(1, 128 // hd)                     # kv heads a tile
    rows = S * (H // Hkv) * per                 # query rows a tile
    cost = {
        "gather": B * P * ps * (_GATHER_COPY * kv_slot
                                + _LOGITS * S * H * 4),
        "pool": N * ps * (_POOL_READ * kv_slot
                          + _LOGITS * B * S * H * 4)}
    if ragged and -(-Hkv // per) * rows <= _RAGGED_ROWS:
        cost["ragged"] = min(B * P, N) * (
            _RAGGED_READ * ps * kv_slot + _RAGGED_PAGE)
    return min(cost, key=cost.get)


def _visible(q_positions: jax.Array, slot_pos: jax.Array,
             window, ring_slots) -> jax.Array:
    """The mask ``_tile_attention`` wants, ``(B, S, Sk)``: queries at
    ``q_positions (B, S)`` (negative: a dead query, sees nothing)
    against slots that hold positions ``slot_pos (B or 1, Sk)``, or,
    of a ring of ``ring_slots`` slots, are at places ``slot_pos`` in it
    (``ring_slots`` and past: no slot of this sequence's). A query sees
    the positions up to its own, the last ``window`` of them where one
    is given."""
    qp = q_positions[:, :, None]
    at = slot_pos[:, None, :]
    if ring_slots is None:
        seen = at <= qp
        if window:
            seen &= at > qp - window
    else:
        back = jnp.mod(qp - at, ring_slots)   # rows behind the query
        seen = (back < window) & (back <= qp) & (at < ring_slots)
    return seen & (qp >= 0)


def _gather_attention(q: jax.Array, k_pages, v_pages,
                      page_indices: jax.Array,
                      q_positions: jax.Array, window=None,
                      ring: bool = False) -> jax.Array:
    """Gather form: each sequence's pages copied dense in table order
    (``B * P * ps`` slots, however few are live), then masked
    attention over the copy."""
    kd = k_pages.pages(page_indices)
    vd = v_pages.pages(page_indices)
    slot = jnp.arange(kd.shape[1], dtype=jnp.int32)[None]
    return _tile_attention(
        k_pages.layout, q, kd, vd,
        _visible(q_positions, slot, window,
                 kd.shape[1] if ring else None))


def _pool_attention(q: jax.Array, k_pages, v_pages,
                    page_indices: jax.Array,
                    q_positions: jax.Array, window=None,
                    ring: bool = False) -> jax.Array:
    """Pool form: every query against the layer's WHOLE pool
    (``N * ps`` slots a head, read once for all sequences), the page
    table turned inside out into a visibility mask. No gather; the
    same keys at the same precisions as the gather form, summed in
    physical order.

    Physical page ``n`` holds logical page ``j`` of sequence ``b`` iff
    ``page_indices[b, j] == n``; the LOWEST such ``j`` counts, so the
    unused tail of a row (all scratch page 0) puts page 0 past the
    sequence's last used page, where no query position reaches, and a
    page that copy-on-write sharing put into two rows is visible to
    both."""
    B = q.shape[0]
    N, ps = k_pages.num_pages, k_pages.page_size
    P = page_indices.shape[1]
    with jax.named_scope("dtt.kv.read"):   # the table, inside out
        logical = jnp.arange(P, dtype=jnp.int32)[None, :, None]
        owns = page_indices[:, :, None] \
            == jnp.arange(N, dtype=page_indices.dtype)[None, None, :]
        # (B, N): logical page of each physical page; P where not
        # owned, which is past every position a table of P pages can
        # hold.
        owner = jnp.min(jnp.where(owns, logical, P), axis=1)
        slot_pos = (owner[:, :, None] * ps
                    + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
                    ).reshape(B, N * ps)
    # In a ring the same arithmetic gives a slot's place in the ring,
    # and ``P * ps`` and past where the sequence does not own the page.
    return _tile_attention(
        k_pages.layout, q, k_pages.slots(), v_pages.slots(),
        _visible(q_positions, slot_pos, window,
                 P * ps if ring else None))


# Rows of a query block times slots of a key block whose float32 logits
# (4 MiB, with their mask and their exponentials beside them) and the
# double-buffered blocks fit a v5e's VMEM: ``flash_attention.
# default_blocks``' reading, the key block the larger.
_FLASH_ROWS = 1024
_FLASH_SLOTS = 1024


def _flash_blocks(S: int, J: int, Sk: int) -> tuple:
    """``(block_q, block_k)`` of ``_flash_attention`` for ``S`` queries
    of ``J`` rows a tile against ``Sk`` slots: a power of two of
    queries whose rows stay under ``_FLASH_ROWS`` (16 at least: a
    bfloat16 block's sublanes), and the largest key block that divides
    the slots once they are padded to whole 128s."""
    block_q = 16
    while block_q * 2 * J <= _FLASH_ROWS and block_q < S:
        block_q *= 2
    padded = -(-Sk // 128) * 128
    block_k = next(b for b in (_FLASH_SLOTS, 512, 256, 128)
                   if padded % b == 0)
    return block_q, block_k


def _online_update(s, v, acc_ref, m_ref, l_ref) -> None:
    """One block of an online softmax, in a kernel: the masked float32
    logits ``s (rows, slots)`` (``NEG_INF`` where unseen) and the
    block's values ``v (slots, lanes)`` folded into the running
    maximum, normaliser and unnormalised float32 accumulator (VMEM
    scratch); the weights go to the second product in the values'
    dtype."""
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # A row that has seen nothing yet: exp(NEG_INF - 0) = 0, where
    # exp(NEG_INF - NEG_INF) would count every masked slot.
    m_use = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_use)
    alpha = jnp.exp(m_prev - m_use)
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new


def _flash_kernel(first_ref, count_ref, pos_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, scale, block_k,
                  slots, window, ring):
    """One (sequence, tile, query block, key block) of the flash form:
    ``ops/flash_attention.py::_fwd_kernel`` with ``_visible``'s
    predicate for its mask. ``pos_ref`` holds each row's query
    position and, for a ring, its place in the ring; ``first_ref`` /
    ``count_ref`` the run of slots, from place ``first`` on and round
    the ring's end, that some live query of the query block sees."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k0 = ki * block_k
    first, count = first_ref[b, qi], count_ref[b, qi]
    needed = (k0 < first + count) & (k0 + block_k > first)
    if ring:
        needed |= k0 < first + count - slots
    needed &= count > 0

    @pl.when(needed)
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qp = pos_ref[0, :, 0:1]                     # (rows, 1)
        at = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        # ``_visible``; a dead query (qp < 0) is behind every slot.
        if ring:
            back = pos_ref[0, :, 1:2] - at          # mod slots, below
            back = jnp.where(back < 0, back + slots, back)
            seen = (back < window) & (back <= qp) & (at < slots)
        else:
            seen = at <= qp
            if window:
                seen &= at > qp - window
        _online_update(jnp.where(seen, s, NEG_INF), v, acc_ref, m_ref,
                       l_ref)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        lsum = l_ref[:]
        o_ref[0, 0] = (acc_ref[:] / jnp.where(lsum == 0.0, 1.0, lsum)
                       ).astype(o_ref.dtype)


def _flash_attention(q: jax.Array, k_pages, v_pages,
                     page_indices: jax.Array,
                     q_positions: jax.Array, window=None,
                     ring: bool = False, blocks=None) -> jax.Array:
    """Flash form: the gather form's copy, attended by ONE Pallas
    kernel (``dtt_paged_prefill``) that keeps the softmax online, so
    no logits are held in HBM however many the queries.

    Grid ``(B, tiles, query blocks, key blocks)``, the key blocks
    innermost and sequential. A block is ``block_q`` queries spread
    onto a tile's lanes (``block_q * J`` rows, zeros on tile-mates'
    lanes: the kernel is the same for heads of 128 and of 64 and never
    unpacks a head) against ``block_k`` slots of that tile on the MXU;
    running maximum, normaliser and float32 accumulator in VMEM
    scratch, the output written on the last key block. The mask is
    ``_visible``'s predicate made in the kernel from the rows'
    positions and an iota over the block's slots; key blocks that no
    live query of a query block sees are skipped, decided from two
    scalars a query block computed here and prefetched. Same
    precisions as one pass: operands in their own dtype to both
    products, float32 logits, maximum, exponential and sums, weights
    cast to the pool's dtype before the second product (unnormalised:
    the division comes last), output in ``q.dtype``; dead queries and
    rows that see nothing give zeros. ``blocks``: ``(block_q,
    block_k)`` in place of ``_flash_blocks``', for the tests and the
    form table."""
    layout = k_pages.layout
    B, S = q.shape[:2]
    # Tiles first: the order XLA gives the gathered copy for the one-
    # pass contraction too, so a key block is ``block_k`` whole rows.
    with jax.named_scope("dtt.kv.read"):
        kd = k_pages.pages(page_indices).transpose(0, 2, 1, 3)
        vd = v_pages.pages(page_indices).transpose(0, 2, 1, 3)
    qt = layout.spread(q).transpose(0, 2, 1, 3, 4)  # (B, T, S, J, tile)
    T, J, tile = qt.shape[1], qt.shape[3], qt.shape[4]
    Sk = kd.shape[2]
    block_q, block_k = blocks or _flash_blocks(S, J, Sk)
    pad_q, pad_k = -S % block_q, -Sk % block_k
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0), (0, 0)))
    # Padding slots lie past every position a table holds, and past a
    # ring's places.
    with jax.named_scope("dtt.kv.read"):
        kd, vd = (jnp.pad(x, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
                  for x in (kd, vd))
    qp = jnp.pad(q_positions, ((0, 0), (0, pad_q)), constant_values=-1)
    nq, nk, rows = (S + pad_q) // block_q, (Sk + pad_k) // block_k, \
        block_q * J
    # The slots some live query of a block sees: positions ``first``
    # to its highest live position, which lie at those places of a
    # table and round a ring from place ``first % Sk`` on.
    live = (qp >= 0).reshape(B, nq, block_q)
    blocked = qp.reshape(B, nq, block_q)
    top = jnp.max(blocked, axis=-1)                # -1: all dead
    low = jnp.min(jnp.where(live, blocked, top[..., None]), axis=-1)
    first = jnp.maximum(low - window + 1, 0) if window \
        else jnp.zeros_like(low)
    count = top - first + 1                        # 0 where all dead
    if ring:
        first, count = first % Sk, jnp.minimum(count, Sk)
    pos = jnp.repeat(qp, J, axis=1)[:, :, None]
    if ring:
        pos = jnp.concatenate([pos, pos % Sk], axis=-1)

    # Index maps: the grid's place, then the two prefetched arrays.
    def of_queries(b, t, qi, ki, *_):
        return b, t, qi, 0

    def of_slots(b, t, qi, ki, *_):
        return b, t, ki, 0

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=q.shape[-1] ** -0.5, block_k=block_k,
            slots=Sk, window=window or 0, ring=ring),
        name="dtt_paged_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, T, nq, nk),
            in_specs=[
                pl.BlockSpec((1, rows, pos.shape[-1]),
                             lambda b, t, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, 1, rows, tile), of_queries),
                pl.BlockSpec((1, 1, block_k, tile), of_slots),
                pl.BlockSpec((1, 1, block_k, tile), of_slots),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, tile), of_queries),
            scratch_shapes=[pltpu.VMEM((rows, tile), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, T, nq * rows, tile), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=not _platform_is_tpu(),
    )(first, count, pos, qt.reshape(B, T, nq * rows, tile), kd, vd)
    out = out.reshape(B, T, S + pad_q, J, tile)[:, :, :S]
    return layout.collect(out.transpose(0, 2, 1, 3, 4))


# Bytes of keys (and as many of values) a step of the walk copies into
# one of its two VMEM buffers: 64 pages of (16, 512) bfloat16 lanes,
# 1,024 slots a contraction; 16 of gpt2-xl's (16, 1664). A step's chain
# of product, maximum, exponential, sum and product is latency, not
# work, at a few hundred slots: 16 pages a step ran at 55% of the 64's
# rate (PERF.md section 6, PR 37).
_RAGGED_STEP_BYTES = 1 << 20


# Page copies an iteration of the loop that starts (or waits for) a
# step's DMAs, with no branch between them. What is unrolled is traced:
# the kernel's body is Python run once a kind of layer at every warm-up.
_RAGGED_UNROLL = 4


def _ragged_pages(table_pages: int, page_bytes: int) -> int:
    """Pages a step of the ragged walk: a power of two whose bytes stay
    under ``_RAGGED_STEP_BYTES``, no more than the table has, 8 at
    least."""
    pages = 8
    while pages * 2 * page_bytes <= _RAGGED_STEP_BYTES \
            and pages < table_pages:
        pages *= 2
    return pages


def _ragged_walks(first, count, ps: int, pages: int):
    """A sequence's walk from its run of seen slots (``count`` of them
    from slot ``first`` on, each ``(B,)``), five numbers a sequence
    flat ``(5 * B,)`` int32: the first page, the slots of it before
    the run, the run's end as an offset from that page's first slot
    (the run: offsets ``[lead, end)``), the pages it touches (none for
    an empty run) and the steps of ``pages`` pages that takes."""
    page0 = first // ps
    lead = first - page0 * ps
    end = lead + count
    n_pages = jnp.where(count > 0, -(-end // ps), 0)
    return jnp.stack([page0, lead, end, n_pages, -(-n_pages // pages)]
                     ).astype(jnp.int32).reshape(-1)


def _ragged_kernel(layers_ref, tables_ref, walks_ref, pos_ref, q_ref,
                   k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, flying,
                   acc_ref, m_ref, l_ref, *, scale, pages, table_pages,
                   window, ring):
    """One sequence of the ragged form: its LIVE pages walked where they
    lie in the carried pools (``k_hbm`` / ``v_hbm``, left in HBM),
    ``pages`` of them a step copied into VMEM one DMA a page, the next
    step's in flight while this one's are contracted
    (``_flash_kernel``'s online softmax, ``_visible``'s predicate), and
    the NEXT sequence's first step in flight during this one's last.
    ``walks_ref``: a sequence's walk (``_ragged_walks``: the run of
    slots some live query of it sees, as pages and steps);
    ``pos_ref`` each row's query position and, for a ring, its place in
    it; ``layers_ref`` the layer's number in either pool; ``flying``
    (SMEM, kept from one sequence to the next): the buffer the next
    step to be contracted is copied into, and whether this sequence's
    first step is in flight already."""
    b, seqs = pl.program_id(0), pl.num_programs(0)
    ps = k_buf.shape[1] // pages
    slots = table_pages * ps
    layers = layers_ref[0], layers_ref[1]

    def walk(b):
        """``_ragged_walks``' five numbers of sequence ``b``."""
        return tuple(walks_ref[n * seqs + b] for n in range(5))

    def copies(b, step, slot, start):
        """Start, or wait for, the DMAs of the live pages of sequence
        ``b``'s ``step`` into buffer ``slot``: ``_RAGGED_UNROLL`` pages
        an iteration with no branch a page, then the few that are left
        one by one."""
        page0, _lead, _end, n_pages, _n = walk(b)
        live = jnp.clip(n_pages - step * pages, 0, pages)
        unrolled = min(_RAGGED_UNROLL, pages)
        entry0, row = page0 + step * pages, b * table_pages

        def page(j):
            rows = pl.ds(pl.multiple_of(j * ps, ps), ps)
            if start:
                entry = entry0 + j
                if ring:               # page0 < P, and P pages at most
                    entry = jax.lax.select(entry >= table_pages,
                                           entry - table_pages, entry)
                at = tables_ref[row + entry]
            for n, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                            (v_hbm, v_buf))):
                if start:
                    pltpu.make_async_copy(
                        hbm.at[layers[n], at], buf.at[slot, rows],
                        sems.at[n, slot]).start()
                else:                  # a wait is for a page's bytes
                    pltpu.make_async_copy(
                        hbm.at[0, 0], buf.at[slot, rows],
                        sems.at[n, slot]).wait()

        def group(g, carry):
            for j in range(unrolled):
                page(g * unrolled + j)
            return carry

        whole = jax.lax.div(live, unrolled)
        jax.lax.fori_loop(0, whole, group, None)
        jax.lax.fori_loop(whole * unrolled, live,
                          lambda j, carry: page(j), None)

    @pl.when(b == 0)
    def _finite():
        # A step's slots past the run's pages keep what an earlier step
        # left there: weight zero times that, which must be finite.
        v_buf[...] = jnp.zeros_like(v_buf)
        flying[0] = 0
        flying[1] = 0

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    page0, lead, end, _n_pages, n_steps = walk(b)
    slot0 = flying[0]

    @pl.when((n_steps > 0) & (flying[1] == 0))
    def _first():
        copies(b, 0, slot0, True)

    # The sequence after this one, whose first step goes out during
    # this one's last (none after the last, none for a dead one).
    after = jnp.minimum(b + 1, seqs - 1)
    follows = (b < seqs - 1) & (walk(after)[4] > 0)

    def step(i, carry):
        slot = jax.lax.rem(slot0 + i, 2)
        more = i + 1 < n_steps

        @pl.when(more | follows)
        def _next():    # this sequence's next step, or the next's first
            copies(jax.lax.select(more, b, after),
                   jax.lax.select(more, i + 1, 0), 1 - slot, True)

        copies(b, i, slot, False)
        q, k, v = q_ref[0], k_buf[slot], v_buf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qp = pos_ref[0, :, 0:1]                     # (rows, 1)
        nth = i * (pages * ps) + jax.lax.broadcasted_iota(
            jnp.int32, (1, pages * ps), 1)          # of the walk
        at = page0 * ps + nth                       # place, position
        # ``_visible``, of the run's slots alone: a ring whose run
        # starts inside a page comes by that page twice.
        if ring:
            at = jnp.where(at >= slots, at - slots, at)
            back = pos_ref[0, :, 1:2] - at          # mod slots, below
            back = jnp.where(back < 0, back + slots, back)
            seen = (back < window) & (back <= qp)
        else:
            seen = at <= qp
            if window:
                seen &= at > qp - window
        seen &= (nth >= lead) & (nth < end)
        _online_update(jnp.where(seen, s, NEG_INF), v, acc_ref, m_ref,
                       l_ref)
        return carry

    jax.lax.fori_loop(0, n_steps, step, None)

    @pl.when(n_steps > 0)
    def _hand_over():
        flying[0] = jax.lax.rem(slot0 + n_steps, 2)
        flying[1] = follows.astype(jnp.int32)

    lsum = l_ref[:]
    o_ref[0] = (acc_ref[:] / jnp.where(lsum == 0.0, 1.0, lsum)
                ).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _ragged_call(B, rows, lanes, cols, P, ps, pages, k_dtype, v_dtype,
                 q_dtype, scale, window, ring, interpret):
    """The ``pl.pallas_call`` of the ragged form for one set of static
    shapes, made once: a program calls it a run of like layers (six
    times in ``smallthinker-21b-ep4``'s resident decode), and the
    kernel's body, a thousand operations to trace, is then traced once
    a kind of layer and not once a run."""
    def of_rows(b, *_):
        return b, 0, 0

    return pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale, pages=pages,
                          table_pages=P, window=window, ring=ring),
        name="dtt_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, rows, cols), of_rows),
                pl.BlockSpec((1, rows, lanes), of_rows),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, lanes), of_rows),
            scratch_shapes=[
                pltpu.VMEM((2, pages * ps, lanes), k_dtype),
                pltpu.VMEM((2, pages * ps, lanes), v_dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((rows, lanes), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, rows, lanes), q_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)


def _ragged_attention(q: jax.Array, k_pages, v_pages,
                      page_indices: jax.Array,
                      q_positions: jax.Array, window=None,
                      ring: bool = False, pages=None) -> jax.Array:
    """Ragged form: ONE Pallas kernel (``dtt_paged_decode``) that takes
    the carried pools as they are stored and reads, a sequence, the
    pages some query of the call sees, where they lie. Nothing is
    gathered or re-laid, no layer is sliced out of a pool, no logits
    touch HBM.

    Grid ``(B,)``, a sequence a step. The page table, the layer's
    number and the walk of the sequence's run of seen slots (``first``,
    ``count``: positions of a table, places round a ring,
    ``_flash_attention``'s arithmetic with the call one query block;
    ``_ragged_walks``) are prefetched scalars;
    the kernel walks the run's pages ``pages`` a step (``_ragged_pages``'
    unless given), a page one contiguous DMA ``(page_size, lanes)`` out
    of ``pool[number, page_indices[b, j]]`` into one of two VMEM
    buffers, a sequence's first step during the last of the sequence
    before. The queries are spread over the whole stored row (a row a
    tile, query and tile-row: its head on its kv head's lanes of its
    tile, zeros on every other lane), so one product of ``tiles * S *
    J`` rows against a step's slots contracts every tile on the lanes
    as they lie, and of the weighted sum a row keeps its own tile's
    lanes (``layout.collect``). Same precisions as ``_flash_attention``:
    operands in the pool's dtype to both products, float32 logits,
    maximum, exponentials and sums, weights cast to the pool's dtype
    before the second product, the division last, output in
    ``q.dtype``; a dead query walks nothing and gives zeros."""
    layout = k_pages.layout
    B, S = q.shape[:2]
    P, ps = page_indices.shape[1], k_pages.page_size
    pages = pages or _ragged_pages(
        P, ps * layout.lanes * k_pages.dtype.itemsize)
    qt = layout.spread(q).transpose(0, 2, 1, 3, 4)  # (B, T, S, J, tile)
    T, J, tile = qt.shape[1], qt.shape[3], qt.shape[4]
    rows, lanes = T * S * J, T * tile
    pad = -rows % 16                   # a bfloat16 block's sublanes
    full = jnp.einsum("btsjl,tu->btsjul", qt, jnp.eye(T, dtype=q.dtype))
    full = jnp.pad(full.reshape(B, rows, lanes), ((0, 0), (0, pad), (0, 0)))
    with jax.named_scope("dtt.kv.read"):
        # The slots some live query sees: positions ``first`` to the
        # highest live position, round a ring from place ``first % Sk``.
        live = q_positions >= 0
        top = jnp.max(q_positions, axis=-1)          # -1: all dead
        low = jnp.min(jnp.where(live, q_positions, top[:, None]), axis=-1)
        first = jnp.maximum(low - window + 1, 0) if window \
            else jnp.zeros_like(low)
        count = top - first + 1                      # 0 where all dead
        if ring:
            first, count = first % (P * ps), jnp.minimum(count, P * ps)
        walks = _ragged_walks(first, count, ps, pages)
        pos = jnp.broadcast_to(q_positions[:, None, :, None],
                               (B, T, S, J)).reshape(B, rows, 1)
        pos = jnp.pad(pos, ((0, 0), (0, pad), (0, 0)), constant_values=-1)
        if ring:
            pos = jnp.concatenate([pos, pos % (P * ps)], axis=-1)
        layers = jnp.stack([k_pages.number, v_pages.number]
                           ).astype(jnp.int32)
        tables = page_indices.reshape(-1).astype(jnp.int32)

    with jax.named_scope("dtt.attn.core"):
        out = _ragged_call(
            B, rows + pad, lanes, pos.shape[-1], P, ps, pages,
            k_pages.dtype, v_pages.dtype, q.dtype, q.shape[-1] ** -0.5,
            window or 0, ring, not _platform_is_tpu(),
        )(layers, tables, walks, pos, full, k_pages.pool, v_pages.pool)
    # Of every row its own tile's lanes.
    out = jnp.einsum("btsjtl->bstjl", out[:, :rows].reshape(
        B, T, S, J, T, tile))
    return layout.collect(out)


def _held(pages) -> tuple:
    """``(Hkv, N, ps, hd)`` of a layer's view: what ``chunk_form``
    reasons from."""
    return (pages.layout.heads, pages.num_pages, pages.page_size,
            pages.layout.width)


def paged_attention_chunk(q: jax.Array, k_pages, v_pages,
                          page_indices: jax.Array,
                          q_positions: jax.Array, window=None,
                          ring: bool = False) -> jax.Array:
    """Multi-query paged attention (every engine program's, decode
    at ``S = 1``).

    q (B, S, H, hd); k_pages/v_pages a layer of the two pools
    (``PoolLayer``); page_indices (B, P);
    q_positions (B, S) int32 — each query's ABSOLUTE position. Query
    (b, s) attends logical positions ``<= q_positions[b, s]`` of
    sequence b (the chunk's own KV must already be written to the
    pool). Negative q_positions mark padding queries (zero output).
    ``window`` (None or 0: all of them) narrows that to the last
    ``window`` positions, the query's own included; ``ring`` says the
    table is a window layer's ring (see the module's text), which
    needs a window no longer than the ring less the rows a launch
    writes. The form follows from the static shapes: ``chunk_form``'s
    (the ragged form not offered to a pool whose heads are sharded), or
    ``"flash"`` where the pool or gather form's logits would not fit
    one pass (``_one_pass_fits``); a call over a ring is reported as
    ``"<form>.window"``.
    """
    if ring and not window:
        raise ValueError("a ring table needs the window it was sized "
                         "for")
    form = chunk_form(q.shape, _held(k_pages), page_indices.shape,
                      k_pages.dtype.itemsize,
                      ragged=k_pages.layout.shards == 1)
    slots = k_pages.page_size * (k_pages.num_pages if form == "pool"
                                 else page_indices.shape[1])
    if form != "ragged" and not _one_pass_fits(q.shape, slots):
        form = "flash"
    _took(form + ".window" if ring else form)
    attend = {"pool": _pool_attention, "gather": _gather_attention,
              "flash": _flash_attention,
              "ragged": _ragged_attention}[form]
    return attend(q, k_pages, v_pages, page_indices, q_positions,
                  window, ring)


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


# Float32 logits one pass of ``latent_attention_chunk`` may hold. The
# widest call of the engines the benchmark had before PR 34 (a prompt
# chunk of 1024 of 32 heads against a table of 4,096 rows) holds 512 MiB
# and stays one pass; 128 heads against 16,384 rows are 8 GiB and go a
# block of queries at a time.
_LATENT_LOGITS_LIMIT = 640 << 20


class Selection(NamedTuple):
    """A learned selection's side of one call (DeepSeek's sparse
    attention, arXiv 2512.02556): the indexer's queries ``q (B, S, J,
    d)`` (J heads, RoPE applied) and their weights ``w (B, S, J)``
    float32, the layer of the index pool that holds every position's
    key (``PoolLayer``, one ``d``-wide head a token), and how many
    positions a query keeps."""

    q: jax.Array
    w: jax.Array
    pages: object
    topk: int


def index_scores(sel: Selection, keys: jax.Array) -> jax.Array:
    """``I(t, s) = sum_j w[t, j] * relu(q[t, j] . keys[s])`` for the
    queries of ``sel`` against ``keys (B, Sk, d)``: ``(B, S, Sk)``
    float32, the products in the keys' dtype with float32
    accumulation."""
    dots = jnp.einsum("bsjd,bkd->bsjk", sel.q, keys,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bsjk,bsj->bsk", jax.nn.relu(dots),
                      sel.w.astype(jnp.float32))


_UNSEEN = -(1 << 31)

# A block of query rows of ``dtt_select_topk`` (an 8-bit mask tile's 32
# sublanes: 2 MiB of keys at 16,384 positions) and the lanes one step
# of its counts reads.
_SELECT_ROWS = 32
_SELECT_LANES = 512


def _order_keys(scores: jax.Array, seen: jax.Array) -> jax.Array:
    """int32 keys in the order of float32 ``scores``: ``-0.0`` made
    ``+0.0`` (equal under ``==``, so one key), then the low 31 bits of
    a negative flipped. Unseen positions take ``_UNSEEN``, below every
    score's key, ``-inf``'s included."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32),
        jnp.int32)
    return jnp.where(seen, bits ^ ((bits >> 31) & 0x7FFFFFFF), _UNSEEN)


def _select_kernel(live_ref, key_ref, out_ref, *, k, lanes, pos_bits):
    """One block of rows of ``_threshold_mask``: each row's ``k``-th
    largest key ``t`` by bisection on its 32 bits (the largest ``t``
    with ``count(key >= t) >= k``), then, where keys equal to ``t`` are
    more than the room left above it, the cutoff ``p`` by bisection on
    the position (the largest ``p`` with ``count(key == t, pos < p) <=
    room``), and the mask ``key > t | key == t & pos < p`` of the seen.
    A count reads ``lanes`` keys a step and only the steps up to the
    block's highest seen position (``live_ref``); the keys past it are
    unseen."""
    rows = key_ref.shape[0]
    steps = live_ref[pl.program_id(0)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)

    def count(pred):
        def step(j, acc):
            at = pl.multiple_of(j * lanes, lanes)
            return acc + jnp.where(pred(key_ref[:, pl.ds(at, lanes)], at),
                                   1, 0)
        return jnp.sum(jax.lax.fori_loop(
            0, steps, step, jnp.zeros((rows, lanes), jnp.int32)),
            axis=1, keepdims=True)

    # ``t`` is held with its sign bit flipped, so that bisection from
    # the top bit down runs over the keys in order.
    def key_bit(b, carry):
        t, at_t = carry
        bit = t | jnp.left_shift(jnp.int32(1), 31 - b)
        n = count(lambda key, _: key >= bit ^ _UNSEEN)
        return jnp.where(n >= k, bit, t), jnp.where(n >= k, n, at_t)

    zero = jnp.zeros((rows, 1), jnp.int32)
    t, at_t = jax.lax.fori_loop(0, 32, key_bit, (zero, zero + steps * lanes))
    t = t ^ _UNSEEN
    room = k - count(lambda key, _: key > t)

    def pos_bit(b, p):
        bit = p | jnp.left_shift(jnp.int32(1), pos_bits - 1 - b)
        n = count(lambda key, at: (key == t) & (lane < bit - at))
        return jnp.where(n <= room, bit, p)

    # Rows that see fewer than k have ``t`` unseen and take every seen
    # position: no cut.
    split = (at_t - (k - room) > room) & (t != _UNSEEN)
    everyone = zero + ((1 << pos_bits) - 1)
    p = jax.lax.cond(
        jnp.max(jnp.where(split, 1, 0)) > 0,
        lambda: jax.lax.fori_loop(0, pos_bits, pos_bit, zero),
        lambda: everyone)

    def emit(j, carry):
        at = pl.multiple_of(j * lanes, lanes)
        key = key_ref[:, pl.ds(at, lanes)]
        chosen = (key > t) | ((key == t) & (lane < p - at))
        out_ref[:, pl.ds(at, lanes)] = jnp.where(
            chosen & (key != _UNSEEN), 1, 0).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, key_ref.shape[1] // lanes, emit, 0)


def _threshold_mask(keys: jax.Array, extent: jax.Array, k: int
                    ) -> jax.Array:
    """The top-``k`` mask (8-bit) of ``keys (R, Sk)`` (``_order_keys``)
    by ONE Pallas kernel, ``dtt_select_topk``, over blocks of
    ``_SELECT_ROWS`` rows held in VMEM: the keys read once, counted
    there (``_select_kernel``), and the mask written. ``extent (R,)``:
    past it a row sees nothing, so a block counts up to its rows'
    largest."""
    R, Sk = keys.shape
    lanes = min(_SELECT_LANES, -(-Sk // 128) * 128)
    keys = jnp.pad(keys, ((0, -R % _SELECT_ROWS), (0, -Sk % lanes)),
                   constant_values=_UNSEEN)
    width = keys.shape[1]
    live = -(-jnp.max(jnp.pad(extent, (0, -R % _SELECT_ROWS)).reshape(
        -1, _SELECT_ROWS), axis=1) // lanes)
    rows = pl.BlockSpec((_SELECT_ROWS, width), lambda i, live: (i, 0))
    mask = pl.pallas_call(
        functools.partial(_select_kernel, k=k, lanes=lanes,
                          pos_bits=width.bit_length()),
        name="dtt_select_topk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(keys.shape[0] // _SELECT_ROWS,),
            in_specs=[rows], out_specs=rows),
        out_shape=jax.ShapeDtypeStruct(keys.shape, jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=not _platform_is_tpu(),
    )(live.astype(jnp.int32), keys)
    return mask[:R, :Sk]


def select_topk(scores: jax.Array, seen: jax.Array, k: int) -> tuple:
    """The EXACT ``k`` largest of ``scores (..., Sk)`` (float32, not
    NaN) among the positions ``seen`` marks, all of them where they are
    no more than ``k``; equal scores go to the lower position first
    (``jax.lax.top_k``'s order). Returns ``(positions (..., k), kept
    (..., k) bool, chosen (..., Sk) bool)``: the same set as indices, in
    position order (``kept`` false where fewer than ``k`` were seen),
    and as a mask.

    Found by counting (``_threshold_mask``, reported
    ``"select.threshold"``), not by a sort, which on a TPU sorts every
    row whole at ``k`` in the thousands; the positions are compacted out
    of the mask (``_compact``)."""
    _took("select.threshold")
    Sk = scores.shape[-1]
    seen = jnp.broadcast_to(seen, scores.shape)
    extent = jnp.max(jnp.where(seen, jnp.arange(1, Sk + 1), 0), axis=-1)
    chosen = _threshold_mask(_order_keys(scores, seen).reshape(-1, Sk),
                             extent.reshape(-1), k
                             ).reshape(scores.shape) != 0
    positions, kept = _compact(chosen, k)
    return positions, kept, chosen


def _compact(chosen: jax.Array, k: int) -> tuple:
    """``(positions (..., k), kept (..., k))``: the positions ``chosen
    (..., Sk)`` marks, in position order, at most ``k`` of them (0 and
    not kept past the last). No scatter and no sort, whose cost goes
    with the ``Sk`` positions one at a time: the positions in lanes of
    128, each lane's rank among the chosen of its group of 128 a product
    with a triangle of ones, and the ``j``-th chosen found by counting,
    first its group (the groups whose running total is at most ``j``),
    then its lane in the group's row of ranks, picked by a product with
    a one-hot. Every count and rank is a whole number under 2 ** 14,
    exact in the products' float32 sums."""
    *lead, Sk = chosen.shape
    lanes = 128
    n = -(-Sk // lanes)
    f32 = jnp.float32
    marks = jnp.pad(chosen, [(0, 0)] * len(lead) + [(0, n * lanes - Sk)]
                    ).reshape(*lead, n, lanes).astype(jnp.bfloat16)
    lane = jnp.arange(lanes)
    # ranks[..., g, l]: the chosen among lanes 0..l of group g.
    ranks = jnp.einsum("...gl,lm->...gm", marks,
                       (lane[:, None] <= lane[None]).astype(jnp.bfloat16),
                       preferred_element_type=f32)
    counts = ranks[..., -1]
    total = jnp.cumsum(counts, axis=-1)
    j = jnp.arange(k, dtype=f32)[:, None]
    before = total[..., None, :] <= j                  # (..., k, n)
    group = jnp.sum(before, axis=-1)
    rank = j[:, 0] - jnp.sum(jnp.where(before, counts[..., None, :], 0.0),
                             axis=-1)
    row = jnp.einsum(
        "...jg,...gl->...jl",
        (group[..., None] == jnp.arange(n)).astype(jnp.bfloat16),
        ranks.astype(jnp.bfloat16), preferred_element_type=f32)
    at = group * lanes + jnp.sum(row <= rank[..., None], axis=-1)
    kept = jnp.arange(k) < total[..., -1:]
    return jnp.where(kept, at, 0).astype(jnp.int32), kept


def _latent_pass(form: str, q_nope, q_rope, cd, rd, visible, w_uk,
                 w_uv, keys=None, values=None):
    """One pass of latent attention: queries ``(B, S, H, .)`` against
    the rows ``cd (B, Sk, rank)`` / ``rd (B, Sk, rope)`` under
    ``visible`` (broadcast against ``(B, H, S, Sk)``), in ``form``.
    ``keys`` / ``values``: the rows already expanded ``(B, Sk, H, .)``,
    where the caller makes them once for many passes."""
    f32 = jnp.float32
    nope, rope = q_nope.shape[-1], q_rope.shape[-1]
    scores = jnp.einsum("bshe,bke->bhsk", q_rope, rd,
                        preferred_element_type=f32)
    if form == "expanded":
        if keys is None:
            keys = jnp.einsum("bkr,rhn->bkhn", cd, w_uk)
        scores = scores + jnp.einsum(
            "bshn,bkhn->bhsk", q_nope, keys,
            preferred_element_type=f32)
    else:
        scores = scores + jnp.einsum(
            "bshr,bkr->bhsk",
            jnp.einsum("bshn,rhn->bshr", q_nope, w_uk), cd,
            preferred_element_type=f32)
    probs = _masked_softmax(scores * (nope + rope) ** -0.5,
                            visible).astype(cd.dtype)
    if form == "expanded":
        if values is None:
            values = jnp.einsum("bkr,rhv->bkhv", cd, w_uv)
        return jnp.einsum("bhsk,bkhv->bshv", probs, values,
                          preferred_element_type=f32
                          ).astype(q_nope.dtype)
    # The heads stay where the softmax left them: with them moved inside
    # this einsum's own output, XLA's CPU runtime has no bfloat16 dot to
    # run it with.
    ctx = jnp.einsum("bhsk,bkr->bhsr", probs, cd,
                     preferred_element_type=f32).astype(q_nope.dtype)
    return jnp.einsum("bhsr,rhv->bshv", ctx, w_uv)


def _query_block(B: int, S: int, width: int) -> int:
    """Queries a pass of ``latent_attention_chunk``, each holding
    ``width`` float32 values (its heads' logits over the rows it
    attends, or its indexer's scores over the table): all ``S`` where
    they fit ``_LATENT_LOGITS_LIMIT``, else the largest power of two
    that does (8 at least)."""
    if B * S * width * 4 <= _LATENT_LOGITS_LIMIT:
        return S
    block = 8
    while B * 2 * block * width * 4 <= _LATENT_LOGITS_LIMIT:
        block *= 2
    return block


def _in_query_blocks(attend, block: int, q_positions, *by_query):
    """``attend(q_positions, *by_query)`` with the arrays' query axis
    ``(B, S, ...)`` cut into blocks of ``block`` queries, one
    ``jax.lax.map`` step a block, the last block padded with dead
    queries (position -1); all at once where ``block`` covers ``S``."""
    B, S = q_positions.shape
    if block >= S:
        return attend(q_positions, *by_query)
    pad = -S % block

    def blocks(x, fill=0):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                    constant_values=fill)
        return jnp.moveaxis(x.reshape((B, -1, block) + x.shape[2:]),
                            1, 0)

    out = jax.lax.map(lambda a: attend(*a),
                      (blocks(q_positions, -1), *map(blocks, by_query)))
    out = jnp.moveaxis(out, 0, 1).reshape((B, S + pad) + out.shape[3:])
    return out[:, :S]


# What one row read out of a pool by ``PoolLayer.rows`` costs a v5e, in
# the multiply-adds the masked kernel does in that time: fitted to one
# full layer's call at dots3-note-ep8's widths, 1 x 1024 queries over
# tables of 16k to 128k rows (benchmarks/latent_form_table.py; PERF.md
# section 6).
_ROW_GATHER = 1.8e6

# A query block and a key block of the masked kernel: the chunk is one
# query block wherever it can be, because a key block's rows are
# expanded once a query block.
_SPARSE_ROWS = 1024
_SPARSE_SLOTS = 512


def _sparse_blocks(S: int, Sk: int) -> tuple:
    """``(block_q, block_k)`` of ``_sparse_flash_attention``: a power of
    two of queries from 32 (an 8-bit mask block's sublanes) to
    ``_SPARSE_ROWS``, and the largest key block that divides the slots
    once they are padded to whole 128s."""
    block_q = 32
    while block_q < min(S, _SPARSE_ROWS):
        block_q *= 2
    padded = -(-Sk // 128) * 128
    block_k = next(b for b in (_SPARSE_SLOTS, 256, 128)
                   if padded % b == 0)
    return block_q, block_k


def sparse_form(q_shape, slots: int, topk: int, dims) -> str:
    """``"flash"`` or ``"absorbed"`` for ``latent_attention_chunk``
    under a selection of ``topk`` of a table's ``slots`` rows, at q
    ``(B, S, H)`` and widths ``dims = (rank, nope, rope, v)``. One
    algorithm whose cost has two regimes. Gathering, a sequence reads
    ``S * topk`` rows one at a time, each at a price that no width
    moves (``_ROW_GATHER``); masked, it reads its table once and pays
    dense attention's multiply-adds on the MXU, a key block's expansion
    once a query block among them. So the mask wins where a sequence's
    queries choose more rows than its table has (a prompt chunk; at
    decode no query shares a row with another) and the table is not so
    long that dense arithmetic loses to ``topk`` gathered rows."""
    _B, S, H = q_shape
    rank, nope, rope, v = dims
    block_q, _ = _sparse_blocks(S, slots)
    masked = H * slots * (S * (nope + rope + v)
                          + -(-S // block_q) * (nope + v) * rank)
    return ("flash" if S * topk > slots
            and masked < S * topk * _ROW_GATHER else "absorbed")


def _lane_pad(x: jax.Array) -> jax.Array:
    """``x`` with its last axis zero-padded to whole 128 lanes."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, -x.shape[-1] % 128),))


def _sparse_kernel(top_ref, qn_ref, qr_ref, c_ref, r_ref, uk_ref, uv_ref,
                   chosen_ref, o_ref, acc_ref, m_ref, l_ref, *, scale,
                   block_k):
    """One (sequence, head, query block, key block) of the masked form:
    ``_flash_kernel`` with the selection for its mask and the key
    block's latent rows expanded here, in VMEM, into this head's keys
    and values. ``top_ref`` holds each query block's highest live
    position: key blocks past it are skipped."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(ki * block_k <= top_ref[b, qi])
    def _compute():
        c, r = c_ref[0], r_ref[0]
        keys = jnp.dot(c, uk_ref[:], preferred_element_type=jnp.float32
                       ).astype(c.dtype)
        values = jnp.dot(c, uv_ref[:], preferred_element_type=jnp.float32
                         ).astype(c.dtype)
        across = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], keys, across,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], r, across,
                                   preferred_element_type=jnp.float32)
             ) * scale
        # ``chosen`` holds only positions up to the query's own, and
        # none for a dead query.
        s = jnp.where(chosen_ref[0].astype(jnp.int32) != 0, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # As ``_flash_kernel``: a row that has seen nothing yet.
        m_use = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_use)
        alpha = jnp.exp(m_prev - m_use)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        lsum = l_ref[:]
        o_ref[0] = (acc_ref[:] / jnp.where(lsum == 0.0, 1.0, lsum)
                    ).astype(o_ref.dtype)


def _sparse_flash_attention(q_nope: jax.Array, q_rope: jax.Array,
                            c_pages, r_pages, page_indices: jax.Array,
                            q_positions: jax.Array, chosen: jax.Array,
                            w_uk: jax.Array, w_uv: jax.Array,
                            blocks=None) -> jax.Array:
    """The masked form of latent attention under a selection: each
    sequence's table read once, dense in logical order, and ONE Pallas
    kernel (``dtt_sparse_prefill``) that attends a block of queries
    against a block of its rows where ``chosen (B, S, Sk)`` (8-bit,
    ``select_topk``'s mask) is set, softmax online, no logits in HBM.

    Grid ``(B, heads, query blocks, key blocks)``, key blocks innermost
    and sequential. A step expands its key block's latent rows by the
    head's ``W_uk`` / ``W_uv`` in VMEM (no key or value a head is ever
    in HBM; once a query block, so a chunk is one query block where it
    can be) and scores the head's queries against them and against the
    shared rotary keys as they are stored, a 128-lane tile whose upper
    lanes are zero. Key blocks past a query block's highest live
    position are neither computed nor fetched (a scalar prefetched; the
    index maps stay on the last block needed). Same precisions as
    ``_latent_pass`` expanded: operands in the pool's dtype to every
    product, float32 logits, maximum, exponential and sums, weights cast
    to the pool's dtype before the last product, output in
    ``q_nope.dtype``; dead queries and queries that chose nothing give
    zeros. Widths are padded to whole lanes with zeros, which change no
    sum (none at the published widths). ``blocks``: ``(block_q,
    block_k)`` in place of ``_sparse_blocks``', for the tests."""
    B, S, H, nope = q_nope.shape
    v = w_uv.shape[-1]
    with jax.named_scope("dtt.kv.read"):
        cd, rd = (p.pages(page_indices)[:, :, 0]
                  for p in (c_pages, r_pages))
    Sk = cd.shape[1]
    block_q, block_k = blocks or _sparse_blocks(S, Sk)
    pad_q, pad_k = -S % block_q, -Sk % block_k
    nq, nk = (S + pad_q) // block_q, (Sk + pad_k) // block_k
    # A head's lanes side by side: ``(B, S, H * lanes)``, a block a head.
    qn, qr = _lane_pad(q_nope), jnp.pad(
        q_rope, ((0, 0),) * 3 + ((0, rd.shape[-1] - q_rope.shape[-1]),))
    qn, qr = (jnp.pad(x, ((0, 0), (0, pad_q), (0, 0), (0, 0))
                      ).reshape(B, S + pad_q, -1) for x in (qn, qr))
    with jax.named_scope("dtt.kv.read"):
        cd, rd = (jnp.pad(x, ((0, 0), (0, pad_k), (0, 0)))
                  for x in (cd, rd))
    # The expansions' rows as wide as the stored latent row.
    uk, uv = (jnp.pad(_lane_pad(w), ((0, cd.shape[-1] - w.shape[0]),
                                     (0, 0), (0, 0))
                      ).reshape(cd.shape[-1], -1) for w in (w_uk, w_uv))
    chosen = jnp.pad(chosen, ((0, 0), (0, pad_q), (0, pad_k)))
    lanes_n, lanes_r, lanes_v = (qn.shape[-1] // H, rd.shape[-1],
                                 uv.shape[-1] // H)
    top = jnp.max(jnp.pad(q_positions, ((0, 0), (0, pad_q)),
                          constant_values=-1).reshape(B, nq, block_q),
                  axis=-1)                         # -1: all dead

    # Index maps: the grid's place, then the prefetched array.
    def of_queries(b, h, qi, ki, top):
        return b, qi, h

    def last(b, qi, ki, top):
        return jnp.minimum(ki, jnp.maximum(top[b, qi], 0) // block_k)

    def of_rows(b, h, qi, ki, top):
        return b, last(b, qi, ki, top), 0

    def of_head(b, h, qi, ki, top):
        return 0, h

    out = pl.pallas_call(
        functools.partial(_sparse_kernel,
                          scale=(nope + q_rope.shape[-1]) ** -0.5,
                          block_k=block_k),
        name="dtt_sparse_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, lanes_n), of_queries),
                pl.BlockSpec((1, block_q, lanes_r), of_queries),
                pl.BlockSpec((1, block_k, cd.shape[-1]), of_rows),
                pl.BlockSpec((1, block_k, lanes_r), of_rows),
                pl.BlockSpec((cd.shape[-1], lanes_n), of_head),
                pl.BlockSpec((cd.shape[-1], lanes_v), of_head),
                pl.BlockSpec((1, block_q, block_k),
                             lambda b, h, qi, ki, top:
                             (b, qi, last(b, qi, ki, top))),
            ],
            out_specs=pl.BlockSpec((1, block_q, lanes_v), of_queries),
            scratch_shapes=[pltpu.VMEM((block_q, lanes_v), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, S + pad_q, H * lanes_v),
                                       q_nope.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=not _platform_is_tpu(),
    )(top, qn, qr, cd, rd, uk, uv, chosen)
    return out[:, :S].reshape(B, S, H, lanes_v)[..., :v]


def latent_attention_chunk(q_nope: jax.Array, q_rope: jax.Array,
                           c_pages, r_pages,
                           page_indices: jax.Array,
                           q_positions: jax.Array, w_uk: jax.Array,
                           w_uv: jax.Array, window=None,
                           ring: bool = False,
                           select: Selection | None = None
                           ) -> jax.Array:
    """Multi-query attention over a LATENT paged cache.

    q_nope (B, S, H, nope), q_rope (B, S, H, rope), RoPE applied;
    c_pages, a layer of the pool of one ``rank``-wide head
    (``PoolLayer``): a token's latent row after its norm; r_pages,
    likewise ``rope`` wide: its rotary key after RoPE, one for all
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
    another order; ``latent_form`` takes one from the shapes.

    ``window`` / ``ring`` as in ``paged_attention_chunk``: a query sees
    the last ``window`` positions, and the table is a window layer's
    ring (reported ``"<form>.window"``).

    ``select`` (``Selection``): a query attends, of the positions up
    to its own, only the ``select.topk`` whose index keys its indexer
    scores highest (``index_scores``, ``select_topk``: exact, no
    approximation and no blocks of keys), all of them while they are no
    more than that, where the result equals the dense one. Every index
    key of the table is scored. Then, by ``sparse_form``: each query's
    chosen rows ALONE are read out of the two pools (``PoolLayer.rows``,
    through the page table) and attended absorbed, all heads on the one
    gathered row (``"absorbed.sparse"``: decode, where no query shares
    a row with another, and tables far longer than ``topk``); or the
    sequence's table is read once, as without a selection, and one
    kernel attends under ``select_topk``'s mask
    (``"flash.sparse"``, ``_sparse_flash_attention``: a prompt chunk,
    whose queries' selections overlap). The same softmax over the same
    rows either way.

    Where the float32 arrays of all queries (their logits, or the
    indexer's scores over the table) would pass
    ``_LATENT_LOGITS_LIMIT`` the queries go a block at a time
    (``_query_block``; under the mask the selection alone, attended at
    once); without a selection the expanded keys and values are made
    once for all blocks."""
    B, S, H, nope = q_nope.shape
    v = w_uv.shape[-1]
    dims = (c_pages.layout.width, nope, v)
    if ring and not window:
        raise ValueError("a ring table needs the window it was sized "
                         "for")
    if ring and select is not None:
        raise ValueError("a selection reads a table by position, not a "
                         "ring")
    Sk = page_indices.shape[1] * c_pages.page_size
    slot = jnp.arange(Sk, dtype=jnp.int32)[None]
    if select is None:
        form = latent_form((B, S, H), dims)
        _took(form + ".window" if ring else form)
        with jax.named_scope("dtt.kv.read"):
            cd, rd = (p.layout.unpack(p.pages(page_indices))[:, :, 0]
                      for p in (c_pages, r_pages))
            #           (B, Sk, rank), (B, Sk, rope)
        keys = values = None
        if form == "expanded":
            keys = jnp.einsum("bkr,rhn->bkhn", cd, w_uk)
            values = jnp.einsum("bkr,rhv->bkhv", cd, w_uv)

        def attend(qp, qn, qr):
            seen = _visible(qp, slot, window, Sk if ring else None)
            return _latent_pass(form, qn, qr, cd, rd, seen[:, None],
                                w_uk, w_uv, keys, values)

        return _in_query_blocks(attend, _query_block(B, S, H * Sk),
                                q_positions, q_nope, q_rope)

    topk, ps = min(select.topk, Sk), c_pages.page_size
    form = sparse_form((B, S, H), Sk, topk,
                       dims[:2] + (q_rope.shape[-1], v))
    _took(form + ".sparse")
    with jax.named_scope("dtt.kv.read"):
        index_keys = select.pages.layout.unpack(
            select.pages.pages(page_indices))[:, :, 0]

    def choose(qp, sel_q, sel_w):
        with jax.named_scope("dtt.attn.select"):
            return select_topk(
                index_scores(select._replace(q=sel_q, w=sel_w),
                             index_keys),
                _visible(qp, slot, window, None), topk)

    if form == "flash":
        # The selection a block of queries at a time, attended at once.
        with jax.named_scope("dtt.attn.select"):
            chosen = _in_query_blocks(
                lambda *a: choose(*a)[2].astype(jnp.int8),
                _query_block(B, S, select.q.shape[2] * Sk), q_positions,
                select.q, select.w)
        return _sparse_flash_attention(
            q_nope, q_rope, c_pages, r_pages, page_indices, q_positions,
            chosen, w_uk, w_uv)

    def attend(qp, qn, qr, sel_q, sel_w):
        n = qn.shape[1]
        positions, kept, _ = choose(qp, sel_q, sel_w)
        with jax.named_scope("dtt.kv.read"):
            pages = jnp.take_along_axis(
                page_indices, (positions // ps).reshape(B, -1), axis=1
            ).reshape(positions.shape)
            # Every query its own rows: a sequence of one query each.
            cd, rd = (p.layout.unpack(p.rows(pages, positions % ps))
                      [..., 0, :].reshape((B * n, topk, -1))
                      for p in (c_pages, r_pages))
        out = _latent_pass(
            "absorbed", qn.reshape((B * n, 1) + qn.shape[2:]),
            qr.reshape((B * n, 1) + qr.shape[2:]), cd, rd,
            kept.reshape(B * n, 1, 1, -1), w_uk, w_uv)
        return out.reshape(B, n, H, v)

    # The larger of a query's two float32 rows: its heads' logits over
    # the chosen rows, the indexer's heads' scores over the table.
    widest = max(H * topk, select.q.shape[2] * Sk)
    return _in_query_blocks(attend, _query_block(B, S, widest),
                            q_positions, q_nope, q_rope, select.q,
                            select.w)
