"""The plain reference ``reference/sparse_mla_moe.py`` against
``models/sparse_latent_moe.py`` at a toy size, in float32 on the CPU:
logits, loss and gradients, for the whole layer and for a rank's share
of it, over sequences several times the window and the selection. The
reference's constants are set to the toy's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common
from perfbench.tests.test_rehearsal_sparse import TOY, TYPES

from distributed_training_tpu.models import build_model

KW = dict(vocab_size=512, d_model=64, n_layers=5, n_dense_layers=1,
          layer_types=TYPES, n_heads=4, q_lora_rank=32, kv_lora_rank=16,
          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
          rope_theta=10000.0, index_n_heads=4, index_head_dim=16,
          index_topk=16, swa_n_heads=2, swa_q_lora_rank=32,
          swa_kv_lora_rank=32, swa_qk_nope_head_dim=24,
          swa_qk_rope_head_dim=8, swa_v_head_dim=16,
          swa_rope_theta=1000.0, window=9, d_ff=96, moe_d_ff=32,
          n_routed_experts=16, moe_top_k=3, qk_std=0.1, max_seq_len=128)


@pytest.fixture(scope="module", params=[1, 2], ids=["whole", "rank0of2"])
def fixture(request):
    model = build_model("sparse_latent_moe", dtype="float32",
                        ep_size=request.param, **KW)
    params = model.init(jax.random.PRNGKey(5))
    # Norm scales are ones at init; move every leaf, so that a reference
    # that dropped one would be caught.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    ref = common.load_reference({"reference": "sparse_mla_moe"})
    for name, value in {**TOY, "Q_BLOCK": 32, "ROW_BLOCK": 32}.items():
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
