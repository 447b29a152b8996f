"""The plain reference against ``models/transformer.py`` at a toy size,
in float32 on the CPU: logits and loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.models import build_model
from perfbench import common

KW = dict(vocab_size=512, d_model=64, n_layers=3, n_heads=4, max_seq_len=64,
          pos_encoding="learned", tie_embeddings=True)


@pytest.fixture(scope="module")
def fixture():
    model = build_model("gpt2", dtype="float32", attention_impl="naive",
                        **KW)
    params = model.init(jax.random.PRNGKey(5))
    # Biases and LayerNorm parameters are zeros and ones at init; move
    # them, so that a reference that dropped one would be caught.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    ref = common.load_reference({"reference": "gpt2"})
    rows = jnp.asarray(np.random.default_rng(0).integers(0, 500, (3, 33)),
                       jnp.int32)
    return model, params, ref, rows


def test_logits_agree(fixture):
    model, params, ref, rows = fixture
    got = model.apply(params, rows[:, :-1])[0]
    want = jnp.stack([ref.logits(ref.from_program(params), r[:-1], 4)
                      for r in rows])
    # float32 against float32: only the order of summation differs.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_loss_agrees(fixture):
    model, params, ref, rows = fixture
    got = model.loss(params, {"tokens": rows}, jax.random.PRNGKey(0),
                     train=False)[0]
    want = ref.loss(ref.from_program(params), rows, 4)
    assert abs(float(got) - float(want)) < 1e-4


def test_gradients_agree(fixture):
    model, params, ref, rows = fixture
    got = jax.grad(lambda p: model.loss(p, {"tokens": rows},
                                        jax.random.PRNGKey(0),
                                        train=False)[0])(params)
    want = jax.grad(lambda p: ref.loss(ref.from_program(p), rows, 4))(
        params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=2e-3)


@pytest.mark.parametrize("case,at_least", [
    ("layer left out", 0.9), ("positions left out", 0.9),
    ("float8 weights", 0.05), ("nothing", None)])
def test_the_training_check_sees_what_it_should(fixture, case, at_least):
    """``drivers/train.py::Distance`` at the toy size, in float32: the
    program as it is sits at rounding distance from the reference, and
    each tampering of ``check_sensitivity.py`` is far off."""
    from types import SimpleNamespace

    from perfbench.drivers import train
    from perfbench.tests import check_sensitivity as cs

    model, params, _ref, rows = fixture
    tamper = {"layer left out": lambda p: cs.layer_adds_nothing(p, 1),
              "positions left out": cs.no_positions,
              "float8 weights": lambda p: cs.rounded(p, "float8"),
              "nothing": lambda p: p}[case]
    ctx = SimpleNamespace(config={"reference": "gpt2", "n_head": 4})
    distance = train.Distance(ctx, model, None)
    found = distance(tamper(params), rows, jax.random.PRNGKey(0),
                     distance.reference(params, rows))
    if at_least:
        assert found["grad_gap"] > at_least
        assert found["grad_gap_whole"] > at_least / 5
    else:
        assert found["grad_gap"] < 2e-3 and found["loss_gap"] < 1e-4
        assert found["grad_gap_whole"] < 2e-3
