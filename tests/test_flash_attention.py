"""Flash attention numerics vs the naive reference (interpret mode on
CPU; the same kernels compile on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.ops.attention import _naive_attention
from distributed_training_tpu.ops.flash_attention import (flash_attention,
                                                          supported)

# This container's pinned jax runs the Pallas kernels in interpret
# mode and the ring/pipeline numerics at minutes per test — far over
# the tier-1 wall-clock budget (the whole file was broken-at-import
# at seed, so the fast gate never paid for it). The fast gate still
# COMPILES these paths every run (the analysis SPMD audit target
# lowers ring attention under the full sharded train step; the
# test_benchmarks contract tests compile the strategy matrix); the
# kernel/numerics suites here run via `pytest -m slow`.
pytestmark = pytest.mark.slow


def rand_qkv(B=1, S=256, H=2, D=32, Hkv=None, dtype=jnp.float32, seed=0):
    Hkv = Hkv or H
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_naive(causal):
    q, k, v = rand_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_forward_gqa():
    q, k, v = rand_qkv(H=4, Hkv=2)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_multi_block_seq():
    q, k, v = rand_qkv(S=512)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_naive(causal):
    q, k, v = rand_qkv(S=256, H=2, D=32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128) ** 2)

    def f_naive(q, k, v):
        return jnp.sum(_naive_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch")


def test_bf16_forward_close():
    q, k, v = rand_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2, atol=3e-2)


def test_supported_gate(monkeypatch):
    import distributed_training_tpu.ops.flash_attention as fa
    q, k, v = rand_qkv(S=256)
    # Off-TPU, auto-dispatch must never choose the (interpreted) kernel.
    assert not supported(q, k, v)
    monkeypatch.setattr(fa, "_platform_is_tpu", lambda: True)
    assert fa.supported(q, k, v)
    q2, k2, v2 = rand_qkv(S=100)  # not block-divisible
    assert not fa.supported(q2, k2, v2)
    assert not fa.supported(q.astype(jnp.float16), k, v)
    # cross-length causal offset not implemented
    qs, _, _ = rand_qkv(S=128)
    assert not fa.supported(qs, k, v)


def test_wrapper_validation_errors():
    q, k, v = rand_qkv(S=256, H=4, Hkv=4)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=96)
    q6, k4, v4 = rand_qkv(S=256, H=6)[0], *rand_qkv(S=256, H=4)[1:]
    with pytest.raises(ValueError, match="n_heads"):
        flash_attention(q6, k4, v4)
    qs = rand_qkv(S=128)[0]
    with pytest.raises(ValueError, match="Sq == Sk"):
        flash_attention(qs, k, v, causal=True)


def test_dispatch_auto_uses_flash_on_tpu_and_matches(monkeypatch):
    from distributed_training_tpu.ops.attention import dot_product_attention
    q, k, v = rand_qkv(S=256)
    # On CPU "auto" resolves to naive; force the kernel (interpret mode)
    # to check dispatch equivalence.
    out_flash = dot_product_attention(q, k, v, causal=True, impl="flash")
    out_auto = dot_product_attention(q, k, v, causal=True, impl="auto")
    np.testing.assert_allclose(np.asarray(out_auto), np.asarray(out_flash),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_auto_rejects_non_dividing_tile_override():
    """An explicit tile override that doesn't divide the sequence must
    raise under impl='auto', not silently measure the naive path under
    the override's label (ADVICE r3; mirrors ring's raise-don't-ignore)."""
    import pytest

    from distributed_training_tpu.ops.attention import dot_product_attention
    q, k, v = rand_qkv(S=256)
    with pytest.raises(ValueError, match="does not divide"):
        dot_product_attention(q, k, v, impl="auto", block_q=192)
    with pytest.raises(ValueError, match="does not divide"):
        dot_product_attention(q, k, v, impl="auto", block_k=96)
    # A dividing override stays legal.
    dot_product_attention(q, k, v, impl="auto", block_q=128, block_k=128)


def test_fused_bwd_matches_two_pass(monkeypatch):
    """The fused single-sweep backward (dq/dk/dv in one kernel, full
    (S, D) dq scratch) must produce the same gradients as the split
    FlashAttention-2 dq/dkv kernels it replaces on small-S shapes —
    including GQA group reduction and sliding windows. The split path
    is forced by shrinking the fused path's VMEM scratch budget."""
    from distributed_training_tpu.ops import flash_attention as fa

    def grads(**kw):
        q, k, v = rand_qkv(B=2, S=256, H=4, D=16, Hkv=2, seed=3)

        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64,
                **kw) ** 2)

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for kw in ({}, {"window": 96}):
        # The total-residency gate (ADVICE r4) must keep this small
        # shape on the fused path, and zeroing the budget forces split.
        assert fa._fused_bwd_fits(256, 16, 64, 64, jnp.float32)
        fused = grads(**kw)
        monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_LIMIT_BYTES", 0)
        split = grads(**kw)
        monkeypatch.undo()
        for a, b in zip(fused, split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_fused_bwd_vmem_gate_budgets_full_residency():
    """ADVICE r4 (medium): the fused-path gate must budget the softmax
    temporaries, dk/dv scratch, and double-buffered io tiles — not
    just the dq scratch. Pins the decision on the shapes that matter:
    the chip-proven headline stays fused; the S=8192 D=128 bf16 case
    that passed the old dq-only gate (6 MiB exactly) while its true
    residency exceeds VMEM now falls back to the split kernels."""
    from distributed_training_tpu.ops import flash_attention as fa

    # gpt2_125m headline: S=1024, D=64, seq-aware 1024x1024 tiles.
    assert fa._fused_bwd_fits(1024, 64, 1024, 1024, jnp.bfloat16)
    # The ADVICE overflow shape.
    assert not fa._fused_bwd_fits(8192, 128, 1024, 1024, jnp.bfloat16)
    # Ring callers (f32 grads) inflate dq residency ~1.5x.
    assert not fa._fused_bwd_fits(4096, 128, 1024, 1024, jnp.bfloat16,
                                  jnp.float32)


def test_auto_naive_choice_on_tpu_is_logged_once_with_reason(
        monkeypatch, caplog):
    """Where ``auto`` attention runs the naive path ON A TPU the log
    says so, once per reason, naming shape/tile/head count — so a
    run's log tells which kernel it measured. Off-TPU (every other
    test here) naive is the expected path and nothing is logged."""
    import logging

    import distributed_training_tpu.ops.flash_attention as fa
    from distributed_training_tpu.ops.attention import (
        dot_product_attention)

    q, k, v = rand_qkv(S=64)
    assert fa.unsupported_reason(q, k, v) == "platform is not tpu"
    with caplog.at_level(logging.WARNING, logger=fa.logger.name):
        dot_product_attention(q, k, v, impl="auto")
    assert not caplog.records

    monkeypatch.setattr(fa, "_platform_is_tpu", lambda: True)
    fa._warn_naive_once.cache_clear()
    assert fa.unsupported_reason(q, k, v) == "sequence 64 < 128"
    q100, k100, v100 = rand_qkv(S=1100)
    assert "no tile divides" in fa.unsupported_reason(q100, k100, v100)
    q5, k3, v3 = rand_qkv(S=256, H=5, Hkv=3)
    assert "n_heads 5" in fa.unsupported_reason(q5, k3, v3)
    assert fa.unsupported_reason(*rand_qkv(S=256)) is None
    with caplog.at_level(logging.WARNING, logger=fa.logger.name):
        for _ in range(3):
            out = dot_product_attention(q, k, v, impl="auto")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_naive_attention(q, k, v)))
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "sequence 64 < 128" in lines[0]
    fa._warn_naive_once.cache_clear()
