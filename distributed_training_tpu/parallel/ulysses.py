"""Ulysses-style sequence parallelism: all-to-all over the ``sp`` axis.

The second of the two standard long-context layouts (DeepSpeed-Ulysses;
the other is ring attention, parallel/ring_attention.py). Where the
ring keeps queries home and rotates KV blocks through sp neighbor
exchanges, Ulysses re-shards twice per attention call:

    (B, S/sp, H, D)  --all_to_all-->  (B, S, H/sp, D)
         sequence-sharded                  head-sharded
    → plain LOCAL attention over the full sequence per head group
      (the Pallas flash kernel — full S means its causal masking and
      tiling apply unchanged) →
    (B, S, H/sp, D)  --all_to_all-->  (B, S/sp, H, D)

Tradeoffs vs the ring, both O(S·H·D/sp) activation memory per device:

- communication: Ulysses moves q/k/v/out once each (4 a2a's of the
  local shard) regardless of sp; the ring moves K/V sp−1 times. For
  sp > ~4 Ulysses sends less total traffic, but as monolithic
  all-to-alls with no compute to hide behind, vs the ring's
  per-step ppermutes that overlap block compute.
- constraints: Ulysses needs ``H % sp == 0`` AND ``Hkv % sp == 0``
  (heads are the new shard dim); the ring has no head constraint —
  which is why the ring stays the default for GQA models with few KV
  heads.
- backward: plain autodiff — ``all_to_all`` transposes to the inverse
  all-to-all, and the local attention is the flash custom-VJP. No
  hand-written reverse schedule needed.

The reference repo has nothing like either (SURVEY.md §5.7); this
exists because the brief makes long-context a first-class axis and
names both layouts.
"""

from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributed_training_tpu.ops.attention import dot_product_attention
from distributed_training_tpu.runtime import AXIS_SP, BATCH_AXES


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = AXIS_SP, causal: bool = True,
                      local_impl: str = "auto", block_q: int = 0,
                      block_k: int = 0, window: int = 0) -> jax.Array:
    """Sequence-parallel attention; call INSIDE shard_map.

    Per-device shards: q (B, S_local, H, D); k/v (B, S_local, Hkv, D),
    the global sequence being the concatenation of shards in
    ``axis_name`` order. Output matches q's shape/dtype.
    ``local_impl`` feeds ops.dot_product_attention for the full-sequence
    local attention ("auto" → Pallas flash on TPU); ``block_q``/
    ``block_k`` are the flash tile overrides (0 → kernel defaults),
    threaded so the bench sweep tunes every attention layout
    (single-device, Ulysses, and the ring) with one knob.
    """
    sp = jax.lax.axis_size(axis_name)
    if sp == 1:
        return dot_product_attention(q, k, v, causal=causal,
                                     impl=local_impl, block_q=block_q,
                                     block_k=block_k, window=window)
    # Shapes here are per-shard: when a head axis (tp) also shards the
    # head dim, these are the per-tp-shard counts — which is exactly
    # what must divide by sp (the a2a swaps seq for heads within the
    # local head group, so tp composition falls out for free).
    H, Hkv = q.shape[2], k.shape[2]
    if H % sp or Hkv % sp:
        raise ValueError(
            f"ulysses needs the per-shard head counts (q: {H}, "
            f"kv: {Hkv}) divisible by sp ({sp}); use ring attention "
            "otherwise")

    def seq_to_heads(x):
        # (B, S/sp, h, D) -> (B, S, h/sp, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def heads_to_seq(x):
        # (B, S, h/sp, D) -> (B, S/sp, h, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    # Window semantics survive the a2a: the local attention sees the
    # FULL sequence (only heads are sharded), so the band is global.
    out = dot_product_attention(
        seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
        causal=causal, impl=local_impl, block_q=block_q,
        block_k=block_k, window=window)
    return heads_to_seq(out)


def make_ulysses_attention(mesh: Mesh, causal: bool = True,
                           batch_axes=BATCH_AXES,
                           local_impl: str = "auto", block_q: int = 0,
                           block_k: int = 0, head_axis=None,
                           window: int = 0):
    """Build the shard_map'd Ulysses fn over global (B, S, H, D)
    arrays: batch over ``batch_axes``, sequence over ``sp``, heads
    over ``head_axis`` (tp) when given — the a2a then trades sequence
    for heads within each tp shard's head group, so tp and sp compose
    (requires H and Hkv divisible by tp·sp). Mirrors
    make_ring_attention's contract (the model picks by
    ``attention_impl``)."""
    spec = P(tuple(batch_axes) or None, AXIS_SP, head_axis, None)
    return shard_map(
        functools.partial(ulysses_attention, axis_name=AXIS_SP,
                          causal=causal, local_impl=local_impl,
                          block_q=block_q, block_k=block_k,
                          window=window),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


def ulysses_attention_global(q: jax.Array, k: jax.Array, v: jax.Array,
                             mesh: Mesh, causal: bool = True,
                             batch_axes=BATCH_AXES,
                             head_axis=None) -> jax.Array:
    """Convenience entry for tests/eager use (mirrors
    ring_attention_global)."""
    from distributed_training_tpu.parallel.ring_attention import (
        usable_batch_axes,
    )
    fn = make_ulysses_attention(
        mesh, causal=causal,
        batch_axes=usable_batch_axes(mesh, q.shape[0], batch_axes),
        head_axis=head_axis)
    return jax.jit(fn)(q, k, v)
