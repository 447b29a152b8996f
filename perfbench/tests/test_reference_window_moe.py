"""The plain reference ``reference/window_moe.py`` against
``models/window_moe.py`` at a toy size, in float32 on the CPU: logits,
loss and gradients, for the whole layer and for a rank's share of it,
over sequences three times the window. The reference's constants are
set to the toy's (its layouts have an entry a layer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common

from distributed_training_tpu.models import build_model

KW = dict(vocab_size=512, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
          head_dim=16, moe_d_ff=32, n_routed_experts=16, moe_top_k=3,
          window=32, window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
          qk_std=0.1, max_seq_len=128)
TOY = dict(N_KV_HEAD=2, HEAD_DIM=16, WINDOW=32, WINDOW_LAYOUT=(0, 1, 1, 1),
           ROPE_LAYOUT=(0, 1, 1, 1), NUM_EXPERTS_PER_TOK=3, Q_BLOCK=32)


@pytest.fixture(scope="module", params=[1, 2], ids=["whole", "rank0of2"])
def fixture(request):
    model = build_model("window_moe", dtype="float32",
                        ep_size=request.param, **KW)
    params = model.init(jax.random.PRNGKey(5))
    # Norm scales are ones at init; move every leaf, so that a reference
    # that dropped one would be caught.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    ref = common.load_reference({"reference": "window_moe"})
    for name, value in TOY.items():
        setattr(ref, name, value)
    rows = jnp.asarray(np.random.default_rng(0).integers(0, 500, (3, 97)),
                       jnp.int32)
    return model, params, ref, rows


def test_logits_agree(fixture):
    model, params, ref, rows = fixture
    got = model.apply(params, rows[:, :-1])
    want = jnp.stack([ref.logits(ref.from_program(params), r[:-1], 4)
                      for r in rows])
    # float32 against float32: only the order of summation differs.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_loss_and_gradients_agree(fixture):
    model, params, ref, rows = fixture
    mine = lambda p: model.loss(  # noqa: E731
        p, {"tokens": rows}, jax.random.PRNGKey(0), train=False)[0]
    theirs = lambda p: ref.loss(ref.from_program(p), rows, 4)  # noqa: E731
    assert abs(float(mine(params)) - float(theirs(params))) < 1e-4
    got, want = jax.grad(mine)(params), jax.grad(theirs)(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=2e-3)
