"""The plain reference ``reference/parallel_moe.py`` against
``models/parallel_moe.py`` at a toy size, in float32 on the CPU: logits,
loss and gradients, for the whole layer and for a rank's share of it,
over sequences three times the window; and with the stored values in
bfloat16, which ``from_program`` keeps and the reference widens where
it uses them. The reference's constants are set to the toy's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common

from distributed_training_tpu.models import build_model

KW = dict(vocab_size=512, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
          head_dim=16, moe_d_ff=32, n_routed_experts=16, moe_top_k=3,
          n_shared_experts=4, window=32, window_layout=(1, 1, 1, 0),
          rope_layout=(1, 1, 1, 0), qk_std=0.1, max_seq_len=128)
TOY = dict(N_KV_HEAD=2, HEAD_DIM=16, WINDOW=32,
           LAYER_TYPES=("sliding_attention",) * 3 + ("full_attention",),
           NUM_EXPERTS_PER_TOK=3, NUM_SHARED_EXPERTS=4, Q_BLOCK=32,
           V_BLOCK=128)


@pytest.fixture(scope="module", params=[1, 2], ids=["whole", "rank0of2"])
def fixture(request):
    model = build_model("parallel_moe", dtype="float32",
                        ep_size=request.param, **KW)
    params = model.init(jax.random.PRNGKey(5))
    # Norm scales are ones at init; move every leaf, so that a reference
    # that dropped one would be caught.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    ref = common.load_reference({"reference": "parallel_moe"})
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


def test_stored_bfloat16_is_kept_and_widened_where_it_is_used(fixture):
    """``from_program`` hands every matrix on in the dtype it is stored
    in (a second copy of the weights, not a float32 one of twice the
    size) and the forward widens it, which is exact: the logits of the
    bfloat16 tree are those of the same values stored in float32."""
    _model, params, ref, rows = fixture
    stored = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    kept = ref.from_program(stored)
    assert kept["embed"].dtype == jnp.bfloat16
    layer = kept["layers"][0]
    assert all(layer[k].dtype == jnp.bfloat16 for k in (
        "w_q", "w_k", "w_v", "w_o", "w_r", "e_gate", "e_up", "e_down",
        "s_gate", "s_up", "s_down"))
    assert layer["ln"].dtype == kept["norm"].dtype == jnp.float32
    assert layer["s_gate"].shape == (4, 64, 32)
    assert layer["s_down"].shape == (4, 32, 64)
    widened = ref.from_program(jax.tree.map(
        lambda x: x.astype(jnp.float32), stored))
    np.testing.assert_array_equal(
        np.asarray(ref.logits(kept, rows[0, :-1], 4)),
        np.asarray(ref.logits(widened, rows[0, :-1], 4)))
