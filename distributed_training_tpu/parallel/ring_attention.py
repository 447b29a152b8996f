"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context training shards the *sequence* dimension across devices;
attention then needs every query shard to see every KV shard. Ring
attention does this with O(S/sp) *attention-matrix* memory per device
(never materializing S×S scores; backward residuals are O(S_local) —
see the reverse-ring VJP below) and bandwidth-optimal neighbor
exchanges: KV blocks rotate around the ``sp`` ring via
``jax.lax.ppermute`` (XLA lowers it to ICI collective-permute) while each
device folds the incoming block into its queries' running online-softmax
state — the distributed generalization of the flash-attention recurrence
(Liu et al., Ring Attention with Blockwise Transformers, 2023).

Causality with a sequence sharded contiguously: ring step ``t`` delivers
the KV block of device ``(i - t) mod sp`` to device ``i``; that block is

- entirely in the past  (src < i)  → unmasked block attention,
- the diagonal          (src == i) → causal block attention,
- entirely in the future (src > i) → skipped (zero contribution).

The rotation runs a full cycle regardless (uniform collective schedule
on every device — no data-dependent communication), so causal skipping
saves FLOPs, not bandwidth.

Per-block attention dispatches to the Pallas flash kernels when the
local shard is tile-friendly (``block_impl="auto"``): each ring step is
then MXU-tiled with O(tile) score memory — the blockwise-transformer
composition the ring paper assumes — falling back to the fused-einsum
reference otherwise. The merge works on (normalized out, logsumexp)
pairs, which both block implementations produce.

Backward is a REVERSE-RING custom VJP, not autodiff: autodiff through
the scan would save each step's rotated KV carries (O(S_global) per
device — the memory scaling ring attention exists to avoid). Instead
the backward pass re-rotates the *original* KV blocks around the ring a
second time, recomputing each step's normalized softmax from the saved
per-row logsumexp (``p = exp(s - lse)``, the FlashAttention-2 trick)
while dk/dv partial sums travel WITH their KV block — after the full
cycle each block's gradient arrives back at its home device. Residuals
per device: q, k, v, out, lse — all O(S_local).

The reference repo has nothing like this (no attention at all,
SURVEY.md §5.7); it exists because long-context is first-class here.
"""

from __future__ import annotations

import functools

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributed_training_tpu.runtime import AXIS_SP, BATCH_AXES


NEG_INF = -1e30


def _block_mask(Sq: int, Sk: int, mode: str, offset, window: int):
    """Visibility mask (Sq, Sk) for one ring block pair.

    ``offset`` = absolute query-start − absolute key-start (0 on the
    diagonal, t·S_local for a block t ring steps in the past; may be a
    traced scalar). Query row r sits at absolute position r + offset
    relative to key column c: causal keeps ``c <= r + offset``, a
    sliding window additionally needs ``c >= r + offset − (window−1)``.
    Returns None when nothing is masked (pure-past block, no window).
    """
    rows = jnp.arange(Sq)[:, None] + offset
    cols = jnp.arange(Sk)[None, :]
    mask = None
    if mode == "causal":
        mask = cols <= rows
    if window:
        lower = cols >= rows - (window - 1)
        mask = lower if mask is None else jnp.logical_and(mask, lower)
    return mask


def _block_attn_naive(q, k, v, mode: str, offset=None, window: int = 0):
    """XLA-einsum block attention → (out_norm (B,Sq,H,D) f32,
    lse (B,H,Sq) f32). The numerics reference for the flash block.

    ``offset``/``window``: ring-block geometry (see _block_mask);
    ``offset=None`` keeps the historical single-pair alignment
    ``Sk − Sq`` (queries end where keys end)."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Sq, Hkv, group, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    Sk = k.shape[1]
    if offset is None:
        offset = Sk - Sq
    mask = _block_mask(Sq, Sk, mode, offset, window)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    m = jnp.maximum(jnp.max(s, axis=-1), NEG_INF)    # (B,Hkv,g,Sq)
    p = jnp.exp(s - m[..., None])
    lsum = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32) / lsum[..., None]
    out = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    lse = (m + jnp.log(lsum)).reshape(B, Hkv * group, Sq)
    return out, lse


def _validate_tile_overrides(q, k, block_q: int, block_k: int) -> None:
    """Raise-don't-ignore: an explicit flash tile override that does
    not divide the local shard would otherwise be silently dropped —
    how sweeps misattribute their own measurements."""
    S, Sk = q.shape[1], k.shape[1]
    if (block_q and S % min(block_q, S)) or (
        block_k and Sk % min(block_k, Sk)
    ):
        raise ValueError(
            f"flash tile overrides ({block_q}, {block_k}) do not "
            f"divide the local shard lengths ({S}, {Sk})")


def _flash_block_ok(q, k, block_impl: str, block_q: int = 0,
                    block_k: int = 0) -> bool:
    """Route this block through the Pallas flash kernel? Static
    decision (shapes are static under jit/shard_map). Forcing
    ``"flash"`` with non-tile-friendly shards raises: the kernel grid
    would silently leave output rows unwritten (partial tiles), and
    garbage propagated through the ring merge is far worse than a
    trace-time error. Explicit tile overrides that don't divide the
    shard raise for the same reason — a silently ignored override is
    how sweeps misattribute their own measurements."""
    from distributed_training_tpu.ops import flash_attention as fa
    _validate_tile_overrides(q, k, block_q, block_k)
    S, Sk = q.shape[1], k.shape[1]
    if block_impl == "naive":
        return False
    if block_impl == "flash":
        bq, bk = fa._resolve_blocks(block_q, block_k, S, Sk,
                                    q.shape[3])
        if not bq or not bk or S % bq or Sk % bk:
            raise ValueError(
                f"block_impl='flash' forced but local shard lengths "
                f"({S}, {Sk}) admit no dividing kernel tile "
                f"(resolved ({bq}, {bk}), 0 = none fits VMEM); pad "
                f"the sequence or use 'auto'")
        if q.shape[2] % k.shape[2]:
            # A non-dividing group would make the kernel's h // reps
            # KV index map read out-of-range blocks (Pallas clamps —
            # silently wrong heads, no error).
            raise ValueError(
                f"block_impl='flash': n_heads {q.shape[2]} not "
                f"divisible by n_kv_heads {k.shape[2]}")
        if q.dtype not in (jnp.float32, jnp.bfloat16):
            raise ValueError(
                f"block_impl='flash': unsupported dtype {q.dtype} "
                "(float32/bfloat16 only)")
        return True
    # auto: same tile-friendliness rules as single-device dispatch
    # (incl. Sq == Sk, which ring blocks always satisfy), checked
    # against the EFFECTIVE tiles — an override must not demote the
    # ring to the naive path against the default tiles.
    return fa.supported(q, k, k, block_q=block_q, block_k=block_k)


def _bhsd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _flash_blocks(qt, block_q: int = 0, block_k: int = 0):
    """Tile sizes for a (B,H,S,D)-layout ring block (0 → the measured
    seq-aware kernel defaults, clamped to the local shard length)."""
    from distributed_training_tpu.ops import flash_attention as fa
    return fa._resolve_blocks(block_q, block_k, qt.shape[2],
                              qt.shape[2], qt.shape[3])


def _block_attn_flash(qt, k, v, mode: str, block_q: int = 0,
                      block_k: int = 0, window: int = 0):
    """One ring block via the Pallas flash kernel (MXU-tiled, O(tile)
    scores memory). ``qt`` is the loop-invariant (B,H,S,D) transpose of
    the local queries — hoisted out of the ring scan by the caller
    (k/v rotate, so their transposes legitimately live in the step).
    ``window``: legal only for the DIAGONAL block (offset 0 — the
    aligned geometry the kernel's band support models)."""
    from distributed_training_tpu.ops import flash_attention as fa
    bq, bk = _flash_blocks(qt, block_q, block_k)
    # f32 out: per-block partials must not round to the input dtype
    # before the cross-block merge (the naive path is f32 throughout;
    # single-device flash rounds exactly once, at the very end).
    out, lse = fa._flash_fwd(qt, _bhsd(k), _bhsd(v),
                             causal=(mode == "causal"),
                             block_q=bq, block_k=bk,
                             out_dtype=jnp.float32, window=window)
    return _bhsd(out), lse[..., 0]


def _merge(out_a, lse_a, out_b, lse_b):
    """Merge two normalized partial attentions with their logsumexps:
    softmax over the union = lse-weighted convex combination."""
    lse = jnp.logaddexp(lse_a, lse_b)                  # (B,H,S)
    wa = jnp.exp(lse_a - lse)
    wb = jnp.exp(lse_b - lse)
    # (B,H,S) weights onto (B,S,H,D) outputs
    wa = jnp.transpose(wa, (0, 2, 1))[..., None]
    wb = jnp.transpose(wb, (0, 2, 1))[..., None]
    return out_a * wa + out_b * wb, lse


def _ring_perm(sp: int):
    """Rotate right: device i sends to i+1, so at step t device i holds
    the block originating at (i - t) mod sp."""
    return [(i, (i + 1) % sp) for i in range(sp)]


def _ring_branch(src, idx, t, S: int, window: int):
    """Ring-step branch id: 0 = past block, 1 = diagonal, 2 = skip.

    Blocks ahead of the queries are always skipped (causality). Under a
    sliding window, a past block t steps back is additionally skipped
    when even its NEWEST key (gap to the OLDEST local query:
    (t−1)·S + 1 positions) falls outside the window — the FLOPs term
    that makes windowed ring attention O(S·W/sp) per device instead of
    O(S²/sp²)·sp."""
    past = jnp.where(src < idx, 0, 2)
    if window:
        past = jnp.where((t - 1) * S + 1 <= window - 1, past, 2)
    return jnp.where(src == idx, 1, past)


def _ring_fwd_scan(q, k, v, axis_name: str, causal: bool,
                   block_impl: str, block_q: int = 0,
                   block_k: int = 0, window: int = 0):
    """Full ring cycle of online-softmax accumulation. Returns the
    normalized output (B, S, H, D) in q.dtype and per-row logsumexp
    (B, H, S) fp32."""
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    perm = _ring_perm(sp)

    # The Pallas kernel models the band only in the ALIGNED geometry
    # (offset 0), so under a window it serves the diagonal block — the
    # dominant computed block once out-of-window blocks are skipped —
    # while offset (past/boundary) blocks run the einsum reference.
    # Without a window every block is flash-eligible.
    use_flash = _flash_block_ok(q, k, block_impl, block_q, block_k)
    # Loop-invariant: hoisted here because XLA's while-loop LICM does
    # not lift computations out of lax.switch branch computations.
    qt = _bhsd(q) if use_flash else None

    def block(kv, mode, offset):
        if use_flash and (not window or mode == "causal"):
            return _block_attn_flash(qt, kv[0], kv[1], mode,
                                     block_q, block_k, window=window)
        return _block_attn_naive(q, kv[0], kv[1], mode,
                                 offset=offset, window=window)

    out0 = jnp.zeros((B, S, H, D), jnp.float32)
    lse0 = jnp.full((B, H, S), NEG_INF, jnp.float32)

    def step(carry, t):
        k_cur, v_cur, out_acc, lse_acc = carry
        src = (idx - t) % sp
        # Non-future blocks sit exactly t ring steps in the past, so
        # the absolute query-start − key-start offset is t·S.
        offset = t * S

        def full_block(kv):
            return block(kv, "full", offset)

        def diag_block(kv):
            return block(kv, "causal", 0)

        def skip_block(kv):
            del kv  # out-of-view block: zero contribution, no FLOPs
            return jnp.zeros_like(out0), jnp.full_like(lse0, NEG_INF)

        if causal:
            # lax.switch keeps only one branch's FLOPs per step.
            branch = _ring_branch(src, idx, t, S, window)
            out_t, lse_t = jax.lax.switch(
                branch, (full_block, diag_block, skip_block),
                (k_cur, v_cur))
        else:
            out_t, lse_t = full_block((k_cur, v_cur))

        out_acc, lse_acc = _merge(out_acc, lse_acc, out_t, lse_t)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, out_acc, lse_acc), None

    (k_f, v_f, out_acc, lse_acc), _ = jax.lax.scan(
        step, (k, v, out0, lse0), jnp.arange(sp))
    del k_f, v_f
    return out_acc.astype(q.dtype), lse_acc


def _block_grads_naive(q, k, v, do_g, lse, delta, mode: str,
                       offset=None, window: int = 0):
    """Einsum gradients of one KV block against the local queries, with
    the softmax recomputed from the saved FINAL logsumexp
    (``p = exp(s - lse)`` is the globally-normalized softmax — the
    FlashAttention-2 decomposition, so per-block grads sum to the
    exact total).

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D); do_g: (B, Hkv, g, Sq, D)
    fp32; lse/delta: (B, H, Sq) fp32. Returns (dq (B,Sq,H,D) f32,
    dk (B,Sk,Hkv,D) f32, dv likewise)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    group = H // Hkv
    scale = D ** -0.5
    qg = q.reshape(B, Sq, Hkv, group, D)
    lse_g = lse.reshape(B, Hkv, group, Sq)
    delta_g = delta.reshape(B, Hkv, group, Sq)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if offset is None:
        offset = Sk - Sq
    mask = _block_mask(Sq, Sk, mode, offset, window)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jnp.exp(s - lse_g[..., None])                # (B,Hkv,g,Sq,Sk)
    dv = jnp.einsum("bhgqk,bhgqd->bkhd", p, do_g,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bhgqd,bkhd->bhgqk", do_g, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta_g[..., None]) * scale
    dq = jnp.einsum("bhgqk,bkhd->bqhgd", ds, k.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    return dq.reshape(B, Sq, H, D), dk, dv


def _block_grads_flash(qt, dot, k, v, lse, delta, mode: str,
                       block_q: int = 0, block_k: int = 0,
                       window: int = 0):
    """Per-block gradients via the Pallas flash backward kernels. Feeds
    the FINAL (lse, delta) — the FA2 trick makes per-block kernels
    compose into the ring total without any per-block statistics.
    ``qt``/``dot`` are the loop-invariant (B,H,S,D) transposes of the
    local queries / upstream grads, hoisted out of the ring scan.
    ``window``: diagonal block only (aligned geometry)."""
    from distributed_training_tpu.ops import flash_attention as fa
    bq, bk = _flash_blocks(qt, block_q, block_k)
    dq, dk, dv = fa._flash_bwd(
        qt, _bhsd(k), _bhsd(v), None, lse[..., None], dot,
        causal=(mode == "causal"), block_q=bq, block_k=bk,
        delta=delta[..., None], grads_dtype=jnp.float32,
        window=window)
    return _bhsd(dq), _bhsd(dk), _bhsd(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_core(q, k, v, axis_name, causal, block_impl,
               block_q=0, block_k=0, window=0):
    out, _ = _ring_fwd_scan(q, k, v, axis_name, causal, block_impl,
                            block_q, block_k, window)
    return out


def _ring_core_fwd(q, k, v, axis_name, causal, block_impl,
                   block_q=0, block_k=0, window=0):
    out, lse = _ring_fwd_scan(q, k, v, axis_name, causal, block_impl,
                              block_q, block_k, window)
    # Checkpoint-name the residuals the reverse ring consumes (same
    # discipline as ops/flash_attention._flash_bhsd_fwd): un-named
    # custom-VJP residuals are dropped by save_only_these_names remat
    # policies, and the "recompute" here is the ENTIRE forward ring —
    # sp ppermute rotations riding ICI — not just a local kernel.
    # The model's policy allow-lists carry these names
    # (models/transformer.FLASH_RESIDUAL_NAMES). Primal and residual
    # share the named value — see the note in
    # ops/flash_attention._flash_bhsd_fwd.
    name = jax.ad_checkpoint.checkpoint_name
    out = name(out, "flash_out")
    return out, (q, k, v, out, name(lse, "flash_lse"))


def _ring_core_bwd(axis_name, causal, block_impl, block_q, block_k,
                   window, res, do):
    """Reverse ring: KV blocks make a second full rotation; each step
    recomputes that block's softmax and adds its dk/dv contribution into
    accumulators that TRAVEL WITH the block — after sp rotations the
    block (and its finished gradient) is back on its home device. dq
    accumulates locally. Residuals were O(S_local); so are the carries.
    """
    q, k, v, out, lse = res
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    perm = _ring_perm(sp)

    do_f = do.astype(jnp.float32)
    delta = jnp.sum(do_f * out.astype(jnp.float32), axis=-1)  # (B,S,H)
    delta = jnp.transpose(delta, (0, 2, 1))                   # (B,H,S)

    # Loop-invariant per-path precomputes, hoisted out of the scan
    # (XLA's while-loop LICM does not lift out of switch branches):
    # flash wants (B,H,S,D) q/dO; the einsum path wants grouped dO.
    use_flash = _flash_block_ok(q, k, block_impl, block_q, block_k)
    if use_flash:
        qt, dot = _bhsd(q), _bhsd(do)
    else:
        qt = dot = None
    if not use_flash or window:
        # The einsum path serves every block when flash is off, and
        # the offset (past/boundary) blocks under a window.
        do_g = do_f.reshape(B, S, Hkv, group, D).transpose(
            0, 2, 3, 1, 4
        )
    else:
        do_g = None

    def block_grads(kv, mode, offset):
        if use_flash and (not window or mode == "causal"):
            return _block_grads_flash(qt, dot, kv[0], kv[1], lse,
                                      delta, mode, block_q, block_k,
                                      window=window)
        return _block_grads_naive(q, kv[0], kv[1], do_g, lse, delta,
                                  mode, offset=offset, window=window)

    dq0 = jnp.zeros((B, S, H, D), jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)

    def step(carry, t):
        k_cur, v_cur, dq_acc, dk_acc, dv_acc = carry
        src = (idx - t) % sp
        offset = t * S

        def full_block(kv):
            return block_grads(kv, "full", offset)

        def diag_block(kv):
            return block_grads(kv, "causal", 0)

        def skip_block(kv):
            del kv
            return dq0, dk0, dv0

        if causal:
            branch = _ring_branch(src, idx, t, S, window)
            dq_t, dk_t, dv_t = jax.lax.switch(
                branch, (full_block, diag_block, skip_block),
                (k_cur, v_cur))
        else:
            dq_t, dk_t, dv_t = full_block((k_cur, v_cur))

        dq_acc = dq_acc + dq_t
        dk_acc = dk_acc + dk_t
        dv_acc = dv_acc + dv_t
        # Rotate the KV block together with its gradient accumulators.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_acc, axis_name, perm)
        return (k_nxt, v_nxt, dq_acc, dk_nxt, dv_nxt), None

    (k_f, v_f, dq, dk, dv), _ = jax.lax.scan(
        step, (k, v, dq0, dk0, dv0), jnp.arange(sp))
    del k_f, v_f
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = AXIS_SP,
                   causal: bool = True,
                   block_impl: str = "auto",
                   block_q: int = 0, block_k: int = 0,
                   window: int = 0) -> jax.Array:
    """Sequence-parallel attention; call INSIDE shard_map.

    Shapes are per-device shards: q/k/v (B, S_local, H|Hkv, D) where the
    global sequence is the concatenation of shards in ``axis_name``
    order. Output matches q's shape/dtype. ``block_impl``: per-block
    attention kernel — "auto" uses the Pallas flash kernel when the
    local shard is tile-friendly (fwd AND reverse-ring bwd), else the
    einsum reference; "naive"/"flash" force a path. ``block_q``/
    ``block_k`` override the flash tiles (0 → module defaults; must
    divide the local shard — raises rather than silently ignore).

    ``window > 0``: sliding-window (Mistral-style) attention in GLOBAL
    positions — query i attends keys [i − window + 1, i] across shard
    boundaries. Ring blocks entirely behind the window are skipped
    (work per device is O(S_local · window), not O(S_local · S)); the
    diagonal block runs the flash kernel with its aligned band mask
    when tile-friendly, while offset (past/boundary) blocks run the
    einsum path (the kernels don't model the offset band). Requires
    ``causal=True``.
    """
    if window and not causal:
        raise ValueError("window > 0 requires causal=True")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window:
        # Under a window only the DIAGONAL block can use the flash
        # kernel (aligned band); offset blocks run the einsum path.
        # Forcing 'flash' would therefore be partially ignored — the
        # raise-don't-ignore contract on explicit kernel config makes
        # that loud (a silently demoted sweep misattributes its own
        # measurements).
        if block_impl == "flash":
            raise ValueError(
                "block_impl='flash' is unsupported with window > 0 "
                "(the per-block flash kernels don't model the offset "
                "band mask); use block_impl='auto' or 'naive'")
        _validate_tile_overrides(q, k, block_q, block_k)
    sp = jax.lax.axis_size(axis_name)

    if sp == 1:
        # Degenerate ring: plain block attention under autodiff (the
        # naive block — the Pallas fwd kernel alone has no vjp outside
        # the ring's custom VJP). The raise-don't-ignore contract on
        # tile overrides still applies.
        _validate_tile_overrides(q, k, block_q, block_k)
        out, _ = _block_attn_naive(q, k, v,
                                   "causal" if causal else "full",
                                   window=window)
        return out.astype(q.dtype)

    return _ring_core(q, k, v, axis_name, causal, block_impl,
                      block_q, block_k, window)


def make_ring_attention(mesh: Mesh, causal: bool = True,
                        batch_axes=BATCH_AXES,
                        head_axis: str | None = None,
                        block_impl: str = "auto",
                        block_q: int = 0, block_k: int = 0,
                        window: int = 0):
    """Build the shard_map'd ring-attention fn over global (B, S, H, D)
    arrays: batch over ``batch_axes``, sequence over ``sp``, heads over
    ``head_axis`` (pass ``tp`` to compose SP with tensor parallelism).
    The single construction point for every caller (models, tests)."""
    spec = P(tuple(batch_axes) or None, AXIS_SP, head_axis, None)
    return shard_map(
        functools.partial(ring_attention, axis_name=AXIS_SP,
                          causal=causal, block_impl=block_impl,
                          block_q=block_q, block_k=block_k,
                          window=window),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


def usable_batch_axes(mesh: Mesh, batch: int,
                      batch_axes=BATCH_AXES) -> tuple:
    """Mesh batch axes a global batch of ``batch`` rows can actually be
    sharded over; axes that don't divide are dropped (replicated).
    Shared by the eager/test entry points of every sequence-parallel
    attention (ring, ulysses)."""
    import math
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    usable = tuple(a for a in batch_axes if sizes.get(a, 1) > 1)
    if usable and batch % math.prod(sizes[a] for a in usable):
        return ()
    return usable


def ring_attention_global(q: jax.Array, k: jax.Array, v: jax.Array,
                          mesh: Mesh, causal: bool = True,
                          batch_axes=BATCH_AXES,
                          window: int = 0) -> jax.Array:
    """Convenience entry for tests/eager use."""
    fn = make_ring_attention(
        mesh, causal=causal,
        batch_axes=usable_batch_axes(mesh, q.shape[0], batch_axes),
        window=window)
    return jax.jit(fn)(q, k, v)
