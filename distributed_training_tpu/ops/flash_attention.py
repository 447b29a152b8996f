"""Pallas TPU flash attention: blockwise online-softmax, O(S) memory.

Forward + custom-VJP backward, both as Pallas kernels. Design (per the
TPU kernel playbook, /opt/skills/guides/pallas_guide.md):

- grid ``(B, H, nq, nk)``: the innermost ``nk`` dimension executes
  sequentially per core, so softmax statistics (running max ``m``,
  normalizer ``l``) and the output accumulator live in VMEM scratch and
  carry across k-blocks; the q-block output is finalized on the last
  k-step. Q/K/V blocks stream HBM→VMEM via BlockSpec pipelining (the
  compiler double-buffers automatically).
- all matmuls hit the MXU with fp32 accumulation
  (``preferred_element_type``); inputs may be bf16.
- causal masking is applied per-block; fully-masked k-blocks are skipped
  with ``pl.when`` so the causal program does ~half the FLOPs.
- backward uses the saved logsumexp and ``delta = rowsum(dO * O)``
  (computed in XLA, it fuses). Default: a FUSED single-sweep kernel
  producing dq/dk/dv together — the block's softmax (s, exp, dp) is
  computed once instead of twice and q/k/v/do stream from HBM once;
  dq accumulates in a full (S, D) f32 VMEM scratch so its
  across-k-blocks accumulation needs no dedicated grid order. When
  that scratch would not fit VMEM (very long S), falls back to the
  standard FlashAttention-2 two-kernel decomposition: dq (accumulate
  over k-blocks) and dkv (accumulate over q-blocks).

Layout contract: wrapper takes (B, S, H, D) like ops.attention, kernels
work in (B, H, S, D). GQA keeps K/V at Hkv heads end-to-end: the KV
BlockSpec index maps route q-head ``h`` to kv-head ``h // reps``, so
grouped heads are never materialized (dk/dv are group-reduced after the
kernel). Sequence lengths must divide the block size (the transformer's
seq lens are powers of two ≥ 128; others fall back to naive).
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_training_tpu.runtime import default_platform

logger = logging.getLogger(__name__)

DEFAULT_BLOCK_Q = 256   # legacy floor — the real default is seq-aware,
DEFAULT_BLOCK_K = 256   # see default_blocks()
NEG_INF = -1e30


def default_blocks(seq_q: int, seq_k: int,
                   head_dim: int) -> tuple[int, int]:
    """Largest tiles that divide the sequences and fit VMEM comfortably.

    MEASURED (v5e, r4 tune matrix, GPT-2 125M @ S=1024, batch 32):
    per-block overheads — causal-mask iota, online-softmax rescale,
    scratch init/finalize, and the (block, 64)-thin MXU ops — dominate
    at small tiles. 256x256 -> 512x512 -> 1024x1024 moved the full
    train step 0.274 -> 0.367 -> 0.419 MFU (+53% tok/s), while XLA's
    fused naive attention sat at 0.269; block_k mattered more than
    block_q (512x1024 beat 1024x512, 0.401 vs 0.364). VMEM budget:
    the f32 logits tile (bq x bk = 4 MiB at 1024x1024) plus q/k/v/do
    blocks and f32 scratch, double-buffered, fits the ~16 MiB/core
    VMEM at head_dim <= 128; wider heads cap at 512.
    """
    cap = 1024 if head_dim <= 128 else 512

    def pick(s: int) -> int:
        for b in (cap, 512, 256, 128):
            if b <= s and s % b == 0:
                return b
        if s <= cap:
            return s  # one whole-sequence block (also the s < 128 case)
        # No dividing tile and too long for a single block: refuse (0)
        # rather than hand Mosaic an over-VMEM logits tile — auto
        # dispatch falls back to naive, forced flash raises loudly.
        return 0

    return pick(seq_q), pick(seq_k)


def _resolve_blocks(block_q: int, block_k: int, seq_q: int, seq_k: int,
                    head_dim: int) -> tuple[int, int]:
    """Effective tiles: explicit overrides (seq-clamped) win; zeros take
    the measured seq-aware defaults."""
    dq, dk = default_blocks(seq_q, seq_k, head_dim)
    return (min(block_q, seq_q) if block_q else dq,
            min(block_k, seq_k) if block_k else dk)


# Every kernel here runs a (B, H, outer, inner) grid where only the
# innermost dim carries accumulation order (fwd/dq: k-blocks; dkv:
# q-blocks) — declaring the rest parallel lets Mosaic pipeline them.
_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel",
                         "arbitrary"))


def _block_needed(causal: bool, q_start, k_start, block_q: int,
                  block_k: int = 0, window: int = 0):
    """False for k-blocks with no live (query, key) pair: entirely
    above the causal diagonal, or — with a sliding ``window`` (query i
    attends keys in [i − window + 1, i]) — entirely below every
    query's window start. Skipped blocks cost zero FLOPs, so windowed
    attention is O(S·window), not O(S²)."""
    needed = jnp.logical_or(not causal, k_start <= q_start + block_q - 1)
    if window > 0:
        needed = jnp.logical_and(
            needed,
            k_start + block_k - 1 >= q_start - window + 1)
    return needed


def _apply_causal_mask(s, q_start, k_start, block_q: int, block_k: int,
                       window: int = 0):
    rows = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    live = cols <= rows
    if window > 0:
        live = jnp.logical_and(live, cols >= rows - (window - 1))
    return jnp.where(live, s, NEG_INF)


def _platform_is_tpu() -> bool:
    """True when tracing targets a TPU backend. On a CPU the caller
    asked for (JAX_PLATFORMS=cpu / train.device=cpu) the kernels run
    through the Pallas interpreter; a CPU JAX fell back to, or a
    backend that fails to start, raises (runtime.default_platform) —
    neither is read as "use the reference".

    DTT_ASSUME_TPU=1 overrides the attached-device check (read
    dynamically, not at import: it exists for DEVICE-LESS topology AOT
    compiles — runtime.topology_runtime — where jax.devices() reports
    the host CPU even though the program is being compiled by the real
    TPU compiler; without the override those audits trace the naive
    path and 0 Pallas kernels reach the compiled HLO). Never set it in
    a process that will EXECUTE the program on CPU: the kernels would
    run in compiled (non-interpret) mode on a backend without Mosaic."""
    if os.environ.get("DTT_ASSUME_TPU", "0") not in ("", "0"):
        return True
    return default_platform() == "tpu"


def unsupported_reason(q: jax.Array, k: jax.Array, v: jax.Array,
                       block_q: int = 0, block_k: int = 0,
                       layout: str = "bshd") -> str | None:
    """Why auto-dispatch must NOT route here (→ naive), or None when
    the kernel takes these shapes.

    Conservative by design: off-TPU the interpreter would be orders of
    magnitude slower than XLA's fused naive path, and the kernel's
    causal mask assumes Sq == Sk (no bottom-right offset).
    ``block_q``/``block_k`` are the caller's tile overrides (0 → kernel
    defaults) — divisibility is checked against the EFFECTIVE tiles so
    a non-dividing override falls back instead of crashing the trace.
    ``layout``: where the sequence/head axes live ("bshd" or "bhsd").
    """
    del v
    s_ax, h_ax = (2, 1) if layout == "bhsd" else (1, 2)
    sq, sk, d = q.shape[s_ax], k.shape[s_ax], q.shape[3]
    if not _platform_is_tpu():
        return "platform is not tpu"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {q.dtype} is neither float32 nor bfloat16"
    if sq != sk:
        return f"Sq {sq} != Sk {sk}"
    if sq < 128:
        return f"sequence {sq} < 128"
    bq, bk = _resolve_blocks(block_q, block_k, sq, sk, d)
    if not bq or not bk or sq % bq or sk % bk:
        return (f"no tile divides the sequence (S={sq}, "
                f"block_q={bq}, block_k={bk})")
    if d > 256:
        return f"head_dim {d} > 256"
    if q.shape[h_ax] % k.shape[h_ax]:
        return (f"n_heads {q.shape[h_ax]} not a multiple of "
                f"n_kv_heads {k.shape[h_ax]}")
    return None


def supported(q: jax.Array, k: jax.Array, v: jax.Array,
              block_q: int = 0, block_k: int = 0,
              layout: str = "bshd") -> bool:
    """Should auto-dispatch route here? (Else: naive fallback.)"""
    return unsupported_reason(q, k, v, block_q, block_k, layout) is None


@functools.lru_cache(maxsize=None)
def _warn_naive_once(reason: str) -> None:
    logger.warning("attention_impl=auto runs the NAIVE path on this "
                   "TPU, not the Pallas flash kernel: %s", reason)


def log_naive_choice(reason: str) -> None:
    """One log line per distinct reason ``auto`` attention runs the
    naive path ON A TPU, so a run's log says which kernel it measured.
    (Off-TPU naive is the expected path and is not logged.)"""
    if _platform_is_tpu():
        _warn_naive_once(reason)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, block_q, block_k,
                causal, window=0):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # Causal: skip blocks entirely above the diagonal (and, with a
    # sliding window, entirely below it).
    needed = _block_needed(causal, q_start, k_start, block_q,
                           block_k, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]  # (block_q, d)
        k = k_ref[0, 0]  # (block_k, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            s = _apply_causal_mask(s, q_start, k_start, block_q,
                                   block_k, window)

        m_prev = m_ref[:]                          # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (bq, bk) f32
        alpha = jnp.exp(m_prev - m_new)            # (bq, 1)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        lsum = l_ref[:]
        l_safe = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:] + jnp.log(l_safe)  # (bq, 1)


def _flash_fwd(q, k, v, *, causal, block_q, block_k, out_dtype=None,
               window=0):
    """q: (B, H, S, D); k/v: (B, Hkv, Sk, D) with Hkv dividing H — GQA is
    expressed in the KV BlockSpec index maps (h → h // reps), so grouped
    KV heads are never materialized at H resolution in HBM.
    ``out_dtype``: output dtype (default q.dtype); ring callers pass
    f32 so per-block partials aren't rounded before the merge."""
    out_dtype = out_dtype or q.dtype
    B, H, S, D = q.shape
    Sk = k.shape[2]
    reps = H // k.shape[1]
    scale = D ** -0.5
    nq, nk = S // block_q, Sk // block_k
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window)

    out, lse = pl.pallas_call(
        kernel,
        name="dtt_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // reps, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // reps, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            # trailing dim of 1: satisfies the (8, 128)-or-full tiling
            # rule for the per-row logsumexp residual
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), out_dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
        interpret=not _platform_is_tpu(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, block_q, block_k, causal,
                   window=0):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = _block_needed(causal, q_start, k_start, block_q,
                           block_k, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        # MXU operands stay in the INPUT dtype (bf16 for the model
        # path); only accumulation is f32. Upcasting `do` here made
        # the dp matmul run f32xf32 — fractional MXU rate for zero
        # numerics benefit (the f32 work was discarded into a bf16-
        # rounded ds anyway). FlashAttention-2 semantics: bf16
        # operands, f32 accumulate, f32 softmax statistics.
        do = do_ref[0, 0].astype(v.dtype)
        lse = lse_ref[0, 0]                       # (bq, 1)
        delta = delta_ref[0, 0]                   # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _apply_causal_mask(s, q_start, k_start, block_q,
                                   block_k, window)
        p = jnp.exp(s - lse)                       # (bq, bk) f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, block_q,
                    block_k, causal, window=0):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = _block_needed(causal, q_start, k_start, block_q,
                           block_k, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        # Same operand-dtype discipline as the dq kernel (see note
        # there): p is rounded to the input dtype for the dv matmul
        # exactly as the forward rounds p for the pv matmul.
        do = do_ref[0, 0].astype(v.dtype)
        lse = lse_ref[0, 0]                       # (bq, 1)
        delta = delta_ref[0, 0]                   # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _apply_causal_mask(s, q_start, k_start, block_q,
                                   block_k, window)
        p = jnp.exp(s - lse)                       # (bq, bk) f32
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # (bq, bk)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, d)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                      *, scale, block_q, block_k, causal, window=0):
    """Single-pass backward: dq, dk, dv in ONE (ki, qi) sweep.

    The two-kernel FlashAttention-2 decomposition recomputes the
    block's softmax twice — s and dp matmuls plus the exp run in BOTH
    the dq and dkv kernels (7 matmuls + 2 exps per live block pair).
    Fusing shares them (5 matmuls + 1 exp) and streams q/k/v/do from
    HBM once instead of twice. The trick that makes single-pass
    possible on TPU's sequential grid: dq accumulates in a FULL
    (S, D) f32 VMEM scratch (dk/dv keep per-k-block scratch as
    before), written out on the final grid step — so dq's
    across-k-blocks accumulation no longer needs its own grid order.
    Callers guard VMEM residency (scratch + dq output block); see
    _flash_bwd.
    """
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nk = pl.num_programs(2)
    nq = pl.num_programs(3)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = _block_needed(causal, q_start, k_start, block_q,
                           block_k, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        # Operand-dtype discipline identical to the split kernels:
        # bf16 MXU operands, f32 accumulation, f32 softmax statistics.
        do = do_ref[0, 0].astype(v.dtype)
        lse = lse_ref[0, 0]                       # (bq, 1)
        delta = delta_ref[0, 0]                   # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _apply_causal_mask(s, q_start, k_start, block_q,
                                   block_k, window)
        p = jnp.exp(s - lse)                       # (bq, bk) f32
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # (bq, bk)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, d)
        dq_acc[pl.dslice(q_start, block_q), :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bq, d)

    @pl.when(qi == nq - 1)
    def _finalize_dkv():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == nk - 1, qi == nq - 1))
    def _finalize_dq():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


# VMEM budget for the fused backward's TOTAL estimated residency.
# An earlier gate budgeted only the whole-sequence dq scratch + dq
# output block (6 MiB) and ignored everything else resident with it —
# the f32 (block_q, block_k) softmax temporaries, dk/dv scratch, and
# the double-buffered q/k/v/do tiles — so shapes like S=8192, D=128
# passed the gate and then blew the ~16 MiB/core VMEM in Mosaic.
# The estimate is conservative-but-calibrated:
# the chip-proven split dq kernel runs the same (block_q, block_k)
# temporaries at 1024x1024 tiles, which bounds how many Mosaic keeps
# live simultaneously (~2 f32 copies; s/p and dp/ds alias).
_FUSED_BWD_VMEM_LIMIT_BYTES = 14 * 1024 * 1024


def _fused_bwd_vmem_estimate(S, D, block_q, block_k, in_itemsize,
                             g_itemsize) -> int:
    """Estimated peak VMEM residency (bytes) of _bwd_fused_kernel."""
    dq_resident = S * D * (4 + g_itemsize)       # f32 scratch + out blk
    softmax_tmp = 2 * block_q * block_k * 4      # live f32 (bq, bk)
    dkv_scratch = 2 * block_k * D * 4
    dkv_out = 2 * block_k * D * g_itemsize
    io_tiles = 2 * 2 * (block_q + block_k) * D * in_itemsize  # dbl-buf
    return dq_resident + softmax_tmp + dkv_scratch + dkv_out + io_tiles


def _fused_bwd_fits(S, D, block_q, block_k, in_dtype, grads_dtype=None):
    """Gate for the fused single-sweep backward; callers fall back to
    the chip-proven two-kernel split path when this is False."""
    g = jnp.dtype(grads_dtype or in_dtype).itemsize
    return _fused_bwd_vmem_estimate(
        S, D, block_q, block_k, jnp.dtype(in_dtype).itemsize,
        g) <= _FUSED_BWD_VMEM_LIMIT_BYTES


# DTT_FLASH_SPLIT_BWD=1 forces the two-kernel path (an on-chip A/B of
# the fused kernel against it). Read ONCE at import: the jit cache key
# does not include env vars, so a mid-process toggle after a shape has
# compiled would silently reuse the previously chosen kernel and
# invalidate an in-process A/B. Process-start-only by construction.
_FORCE_SPLIT_BWD = os.environ.get("DTT_FLASH_SPLIT_BWD", "0") not in (
    "", "0")


def _flash_bwd_fused(q, k, v, lse, do, delta, *, causal, block_q,
                     block_k, window=0, grads_dtype=None):
    """Fused single-sweep backward (see _bwd_fused_kernel)."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    reps = H // k.shape[1]
    scale = D ** -0.5
    nq, nk = S // block_q, Sk // block_k
    gdt = grads_dtype
    qi_spec = pl.BlockSpec((1, 1, block_q, D),
                           lambda b, h, ki, qi: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, ki, qi: (b, h // reps, ki, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, ki, qi: (b, h, qi, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale,
                          block_q=block_q, block_k=block_k,
                          causal=causal, window=window),
        name="dtt_flash_bwd_fused",
        grid=(B, H, nk, nq),
        in_specs=[qi_spec, kv_spec, kv_spec, qi_spec, row_spec,
                  row_spec],
        out_specs=[
            # dq: one whole-(S, D) block per (b, h), resident across
            # the entire sequential (ki, qi) sweep, stored once on the
            # last step from the f32 scratch.
            pl.BlockSpec((1, 1, S, D), lambda b, h, ki, qi: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), gdt or q.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), gdt or k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), gdt or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        # Both trailing dims carry accumulation order here.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=not _platform_is_tpu(),
    )(q, k, v, do, lse, delta)
    if reps > 1:
        dk = dk.reshape(B, H // reps, reps, Sk, D).sum(axis=2)
        dv = dv.reshape(B, H // reps, reps, Sk, D).sum(axis=2)
    return dq, dk, dv


def _flash_bwd(q, k, v, out, lse, do, *, causal, block_q, block_k,
               window=0,
               delta=None, grads_dtype=None):
    """``out`` is consumed only to derive ``delta``; callers that
    precompute delta (it is loop-invariant in the ring) pass
    ``out=None`` and skip that read entirely. ``grads_dtype`` overrides
    the dq/dk/dv dtype (default: match the inputs); ring callers pass
    f32 so per-block gradient partials aren't rounded before their
    cross-block accumulation."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    reps = H // k.shape[1]
    scale = D ** -0.5
    nq, nk = S // block_q, Sk // block_k
    if delta is None:
        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32),
            axis=-1, keepdims=True)  # (B, H, S, 1) — fuses in XLA

    if (not _FORCE_SPLIT_BWD
            and _fused_bwd_fits(S, D, block_q, block_k, q.dtype,
                                grads_dtype)):
        return _flash_bwd_fused(q, k, v, lse, do, delta, causal=causal,
                                block_q=block_q, block_k=block_k,
                                window=window, grads_dtype=grads_dtype)

    gdt = grads_dtype
    interp = not _platform_is_tpu()
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal,
                          window=window),
        name="dtt_flash_bwd_dq",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // reps, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // reps, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), gdt or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_DIM_SEMANTICS,
        interpret=interp,
    )(q, k, v, do, lse, delta)

    # dk/dv are computed per q-head (grid over H) and group-reduced to
    # Hkv afterwards; KV reads stay at Hkv resolution via the index map.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal,
                          window=window),
        name="dtt_flash_bwd_dkv",
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h // reps, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h // reps, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ki, qi: (b, h, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), gdt or k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), gdt or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
        interpret=interp,
    )(q, k, v, do, lse, delta)
    if reps > 1:
        dk = dk.reshape(B, H // reps, reps, Sk, D).sum(axis=2)
        dv = dv.reshape(B, H // reps, reps, Sk, D).sum(axis=2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom VJP over BHSD internals, BSHD at the boundary)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhsd(q, k, v, causal, block_q, block_k, window=0):
    out, _ = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, window=window)
    return out


def _flash_bhsd_fwd(q, k, v, causal, block_q, block_k, window=0):
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, window=window)
    # Checkpoint-name the residuals the backward consumes: under a
    # save_only_these_names remat policy, un-named residuals are
    # discarded and the whole forward kernel re-runs in the backward
    # (MEASURED r4, batch-32 trace: a 31.8 ms/step rematted pallas_call
    # — the policies' allow-lists carry these names so saving the
    # kernel output actually prevents the recompute it was meant to
    # prevent). The name is applied to the PRIMAL and that same value
    # is used as the residual: naming a residual-only copy would leave
    # the primal un-saved, and any downstream consumer being rematted
    # (the BSHD transpose feeding the output projection's wgrad) would
    # re-launch the kernel anyway. q/k/v residuals stay un-named on
    # purpose: their BSHD twins are already saved by the model's
    # q_rope/k_rope/v_proj tags, so their recompute is three cheap
    # transposes, not a kernel launch.
    name = jax.ad_checkpoint.checkpoint_name
    out = name(out, "flash_out")
    return out, (q, k, v, out, name(lse, "flash_lse"))


def _flash_bhsd_bwd(causal, block_q, block_k, window, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, causal=causal,
                            block_q=block_q, block_k=block_k,
                            window=window)
    return dq, dk, dv


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    block_q: int = 0,
                    block_k: int = 0,
                    window: int = 0,
                    layout: str = "bshd") -> jax.Array:
    """Flash attention over (B, S, H, D) inputs (GQA allowed).

    ``block_q``/``block_k`` = 0 take the measured seq-aware defaults
    (``default_blocks``); explicit values override, seq-clamped.
    ``window`` > 0 = sliding-window (Mistral-style) attention: query i
    attends keys in [i − window + 1, i]. Requires ``causal``; k-blocks
    outside the band are skipped, so cost is O(S·window).
    ``layout="bhsd"``: inputs/output already in the kernels' native
    (B, H, S, D) — skips the wrapper transposes entirely (the model's
    fast path emits this layout straight from its qkv einsums)."""
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("window > 0 requires causal=True")
    if layout not in ("bshd", "bhsd"):
        raise ValueError(f"unknown layout '{layout}'")
    native = layout == "bhsd"
    s_ax, h_ax = (2, 1) if native else (1, 2)
    S, D = q.shape[s_ax], q.shape[3]
    H, Hkv = q.shape[h_ax], k.shape[h_ax]
    Sk = k.shape[s_ax]
    if S != Sk and causal:
        raise ValueError(
            f"flash kernel's causal mask requires Sq == Sk, got "
            f"{S} vs {Sk}; use impl='naive'")
    if H % Hkv:
        raise ValueError(
            f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    bq, bk = _resolve_blocks(block_q, block_k, S, Sk, D)
    if not bq or not bk or S % bq or Sk % bk:
        raise ValueError(
            f"sequence lengths ({S}, {Sk}) must be divisible by "
            f"block sizes ({bq}, {bk}); pad or use impl='naive'")
    if native:
        return _flash_bhsd(q, k, v, causal, bq, bk, window)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = _flash_bhsd(qt, kt, vt, causal, bq, bk, window)
    return jnp.transpose(out, (0, 2, 1, 3))
