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

Two entrypoints:

- ``paged_attention`` — single-token decode: one query per sequence
  against its pages. Dispatches to the TPU Pallas kernel when
  ``kernel_supported`` (one async DMA per non-contiguous page,
  double-buffered — see the Pallas guide's paged-attention walk-
  through); everywhere else (CPU meshes, odd shapes) the XLA
  reference path gathers pages dense and masks. Exact same numerics
  contract as ops/attention.py: fp32 logits/softmax, output in
  q.dtype, GQA via hkv-major grouping.
- ``paged_attention_chunk`` — multi-query (prefill-chunk) form: ``S``
  queries per sequence, each masked to pages at logical positions
  ``<= its own position``. Used by the engine's chunked prefill for
  chunks after the first (the first chunk has no prefix and runs the
  ordinary causal path, flash-eligible, via ops.attention).

Gather-based reference is O(max_pages * page_size) per query
regardless of true length — correct everywhere, and on CPU test
meshes (tiny pools) the gather is cheap. The kernel path reads only
the pages a sequence actually owns.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from distributed_training_tpu.runtime import default_platform


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
    logits = logits * (hd ** -0.5)
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(visible[:, None, None], logits, neg)
    # Guard the all-masked row: subtract a rowwise-safe max and zero
    # the weights where nothing is visible.
    probs = jax.nn.softmax(logits, axis=-1)
    any_visible = jnp.any(visible, axis=-1)          # (B, S)
    probs = jnp.where(any_visible[:, None, None, :, None], probs, 0.0)
    out = jnp.einsum("bhgsk,bkhd->bshgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
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
    """
    kd = _gather_pages(k_pages, page_indices)
    vd = _gather_pages(v_pages, page_indices)
    Sk = kd.shape[1]
    slot = jnp.arange(Sk, dtype=jnp.int32)
    visible = (slot[None, None, :] <= q_positions[:, :, None]) \
        & (q_positions[:, :, None] >= 0)
    return _masked_attention(q, kd, vd, visible)


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
