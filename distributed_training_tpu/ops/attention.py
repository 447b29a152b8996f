"""Attention implementations.

- ``naive``: straightforward XLA attention (einsum softmax einsum) — the
  numerics reference every kernel is tested against. XLA already fuses
  this competently on TPU; it is the correctness baseline, not a toy.
- ``flash``: Pallas blockwise-softmax kernel (ops/flash_attention.py) —
  O(S) memory, MXU-tiled; used for long sequences / big models.
- ``ring``: sequence-parallel ring attention (parallel/ring_attention.py)
  — KV blocks rotate around the ``sp`` mesh axis via collective permute.

The reference repo has no attention at all (models are Linear;
SURVEY.md §5.7) — this module exists for the BASELINE.json transformer
targets where MFU ≥ 0.4 requires a real attention path.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from einops import rearrange


def _naive_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     causal: bool = True,
                     segment_mask: jax.Array | None = None,
                     window: int = 0) -> jax.Array:
    """Reference attention. Shapes: q (B, Sq, H, D); k/v (B, Sk, Hkv, D).

    Supports grouped-query attention (Hkv divides H). Softmax in fp32
    regardless of input dtype (bf16-safe), output in q.dtype.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    group = H // Hkv
    qg = rearrange(q, "b s (hkv g) d -> b s hkv g d", g=group)
    scale = D ** -0.5
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("window > 0 requires causal=True")
    if causal:
        Sk = k.shape[1]
        # Offset alignment: query i attends keys <= i + (Sk - Sq)
        # (supports the ring-attention case where Sq < Sk).
        rows = jnp.arange(Sq)[:, None] + (Sk - Sq)
        cols = jnp.arange(Sk)[None, :]
        mask = cols <= rows
        if window:
            # Sliding window: keys in [i - window + 1, i] only.
            mask = jnp.logical_and(mask, cols >= rows - (window - 1))
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    if segment_mask is not None:
        logits = jnp.where(segment_mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return rearrange(out, "b q hkv g d -> b q (hkv g) d").astype(q.dtype)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = True,
                          impl: str = "auto",
                          block_q: int | None = None,
                          block_k: int | None = None,
                          window: int = 0,
                          layout: str = "bshd") -> jax.Array:
    """Dispatching attention entrypoint. ``impl``:

    - "auto": flash on TPU when shapes are tile-friendly, else naive
    - "naive" | "flash" | "ring"

    ``block_q``/``block_k`` override the flash kernel's tile sizes
    (None → kernel defaults); ignored by the naive path.
    ``layout="bhsd"``: inputs/outputs are already in the flash
    kernels' (B, H, S, D) layout — no wrapper transposes (the model's
    fast path); the naive fallback transposes at this boundary.
    """
    seq_axis = 2 if layout == "bhsd" else 1
    if impl in ("auto", "flash"):
        from distributed_training_tpu.ops import flash_attention as fa
        # An EXPLICIT tile override that does not divide the sequence
        # must raise, not silently reroute to naive — otherwise sweep
        # rows measure the wrong kernel under the override's label
        # (mirrors ring_attention's raise-don't-ignore).
        if impl == "auto" and (block_q or block_k):
            sq, sk = q.shape[seq_axis], k.shape[seq_axis]
            if (block_q and sq % min(block_q, sq)) or (
                    block_k and sk % min(block_k, sk)):
                raise ValueError(
                    f"explicit flash tile override (block_q={block_q}, "
                    f"block_k={block_k}) does not divide seq lengths "
                    f"(Sq={sq}, Sk={sk}); fix the override or pass "
                    "impl='naive' explicitly")
        reason = None if impl == "flash" else fa.unsupported_reason(
            q, k, v, block_q=block_q or 0, block_k=block_k or 0,
            layout=layout)
        if reason is None:
            kw = {}
            if block_q:
                kw["block_q"] = block_q
            if block_k:
                kw["block_k"] = block_k
            return fa.flash_attention(q, k, v, causal=causal,
                                      window=window, layout=layout,
                                      **kw)
        fa.log_naive_choice(reason)
        impl = "naive"
    if impl == "naive":
        if layout == "bhsd":
            t = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
            return t(_naive_attention(t(q), t(k), t(v), causal,
                                      window=window))
        return _naive_attention(q, k, v, causal, window=window)
    raise ValueError(f"unknown attention impl '{impl}'")
