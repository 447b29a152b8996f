"""The plain reference ``reference/mla_moe.py`` against
``models/latent_moe.py`` at a toy size, in float32 on the CPU, with the
reference's constants as committed (the toy keeps the head widths, the
experts a token and the RoPE base): logits, loss and gradients, for the
whole layer and for a rank's share of it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common

from distributed_training_tpu.models import build_model

KW = dict(vocab_size=512, d_model=64, n_layers=3, n_dense_layers=1,
          n_heads=2, q_lora_rank=32, kv_lora_rank=32, d_ff=64, moe_d_ff=32,
          n_routed_experts=32, max_seq_len=128)


@pytest.fixture(scope="module", params=[1, 2], ids=["whole", "rank0of2"])
def fixture(request):
    model = build_model("latent_moe", dtype="float32",
                        ep_size=request.param, **KW)
    params = model.init(jax.random.PRNGKey(5))
    # Norm scales are ones at init; move every leaf, so that a reference
    # that dropped one would be caught.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    ref = common.load_reference({"reference": "mla_moe"})
    rows = jnp.asarray(np.random.default_rng(0).integers(0, 500, (3, 33)),
                       jnp.int32)
    return model, params, ref, rows


def test_logits_agree(fixture):
    model, params, ref, rows = fixture
    got = model.apply(params, rows[:, :-1])
    want = jnp.stack([ref.logits(ref.from_program(params), r[:-1], 2)
                      for r in rows])
    # float32 against float32: only the order of summation differs.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_loss_and_gradients_agree(fixture):
    model, params, ref, rows = fixture
    mine = lambda p: model.loss(  # noqa: E731
        p, {"tokens": rows}, jax.random.PRNGKey(0), train=False)[0]
    theirs = lambda p: ref.loss(ref.from_program(p), rows, 2)  # noqa: E731
    assert abs(float(mine(params)) - float(theirs(params))) < 1e-4
    got, want = jax.grad(mine)(params), jax.grad(theirs)(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=2e-3)
