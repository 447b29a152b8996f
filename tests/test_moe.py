"""Routed MoE dispatch: numerics vs the dense reference, FLOPs scaling
independent of expert count, capacity-drop semantics.

VERDICT round-2 item 3: dense dispatch computed every expert for every
token (O(E) FLOPs); the routed path must cost ~top_k experts per token
regardless of E.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.models import build_model
from distributed_training_tpu.models.transformer import (
    TransformerConfig, _moe_group_size, _moe_mlp_dense, _moe_mlp_routed,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=4,
                max_seq_len=16, dtype="float32", param_dtype="float32",
                moe_num_experts=4, moe_top_k=2)
    base.update(kw)
    return TransformerConfig(**base)


def _mlp_params(c, seed=0):
    rng = np.random.default_rng(seed)
    E, D, F = c.moe_num_experts, c.d_model, c.d_ff
    return {
        "router": jnp.asarray(rng.standard_normal((D, E)), jnp.float32),
        "wi": jnp.asarray(
            rng.standard_normal((E, D, F)) * 0.05, jnp.float32),
        "wo": jnp.asarray(
            rng.standard_normal((E, F, D)) * 0.05, jnp.float32),
    }


def test_group_size_pads_up():
    """Group size never collapses for poorly-composite sequence
    lengths; S pads up (groups are per-row sequence chunks)."""
    assert _moe_group_size(1024, 1024) == (1024, 1024)
    assert _moe_group_size(2048, 1024) == (1024, 2048)
    assert _moe_group_size(992, 1024) == (992, 992)
    assert _moe_group_size(992, 500) == (500, 1000)
    assert _moe_group_size(7, 4) == (4, 8)
    assert _moe_group_size(2 * 1031, 1024) == (1024, 3072)


def test_routed_ragged_tokens_match_dense():
    """T not divisible by the group cap: pad rows must claim no
    capacity and contribute nothing (output still matches dense)."""
    c = _cfg(moe_top_k=2, moe_capacity_factor=4.0, moe_group_size=5)
    mlp = _mlp_params(c)
    h = jnp.asarray(
        np.random.default_rng(4).standard_normal((1, 13, 32)),
        jnp.float32)  # T=13, g=5 -> pads to 15
    out_r, aux_r = _moe_mlp_routed(h, mlp, c)
    out_d, aux_d = _moe_mlp_dense(h, mlp, c)
    np.testing.assert_allclose(out_r, out_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux_r, aux_d, rtol=1e-5, atol=0)


@pytest.mark.parametrize("top_k", [1, 2])
def test_routed_matches_dense_at_ample_capacity(top_k):
    """With capacity big enough that nothing drops, routed == dense
    (values and grads): same experts, same combine weights."""
    c = _cfg(moe_top_k=top_k,
             moe_capacity_factor=4.0,  # C = k*g: nothing can drop
             moe_group_size=32)
    mlp = _mlp_params(c)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((2, 8, 32)),
                    jnp.float32)

    out_r, aux_r = _moe_mlp_routed(h, mlp, c)
    out_d, aux_d = _moe_mlp_dense(h, mlp, c)
    np.testing.assert_allclose(out_r, out_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux_r, aux_d, rtol=1e-6, atol=0)

    gr = jax.grad(lambda m: jnp.sum(_moe_mlp_routed(h, m, c)[0]))(mlp)
    gd = jax.grad(lambda m: jnp.sum(_moe_mlp_dense(h, m, c)[0]))(mlp)
    for key in ("router", "wi", "wo"):
        np.testing.assert_allclose(gr[key], gd[key], rtol=1e-4,
                                   atol=1e-5)


def test_capacity_drops_tokens():
    """With capacity 1 per expert, overflowing tokens contribute
    nothing (out rows can be zero) and nothing NaNs."""
    c = _cfg(moe_top_k=1, moe_capacity_factor=1e-6, moe_group_size=16)
    mlp = _mlp_params(c)
    h = jnp.asarray(np.random.default_rng(2).standard_normal((1, 16, 32)),
                    jnp.float32)
    out, aux = _moe_mlp_routed(h, mlp, c)
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.isfinite(float(aux))
    # capacity C=1 per expert, 4 experts, 16 tokens -> at most 4 rows
    # received any expert output.
    nonzero_rows = np.sum(np.any(np.asarray(out[0]) != 0.0, axis=-1))
    assert nonzero_rows <= 4


def _model_flops(E: int, moe_impl: str) -> float:
    model = build_model("transformer", vocab_size=128, d_model=64,
                        n_layers=2, n_heads=4, max_seq_len=64,
                        dtype="float32", param_dtype="float32",
                        moe_num_experts=E, moe_top_k=2,
                        moe_impl=moe_impl, moe_group_size=256)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, 64), jnp.int32)
    lowered = jax.jit(
        lambda p, t: model.apply(p, t)[0]).lower(params, tokens)
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    return float(cost["flops"])


def test_routed_flops_independent_of_expert_count():
    """Doubling E at fixed top_k must not ~double routed FLOPs (it does
    for dense). Compiled-cost assertion, per VERDICT item 3."""
    r4, r16 = _model_flops(4, "routed"), _model_flops(16, "routed")
    d4, d16 = _model_flops(4, "dense"), _model_flops(16, "dense")
    assert d16 / d4 > 2.0, f"dense should scale with E: {d4} -> {d16}"
    assert r16 / r4 < 1.5, (
        f"routed FLOPs should be ~independent of E: {r4} -> {r16}")


def test_moe_model_trains_routed(cpu8):
    """End-to-end: routed-MoE transformer takes a finite training step
    under the trainer on the 8-device mesh (EP layout)."""
    from distributed_training_tpu.config import Config
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               SyntheticLMDataset)
    from distributed_training_tpu.train.trainer import Trainer

    cfg = Config()
    cfg.train.parallel_strategy = "fsdp"
    cfg.train.batch_size = 2
    cfg.train.log_every = 0
    cfg.train.min_shard_elems = 1
    cfg.train.dtype = "float32"
    model = build_model("transformer", vocab_size=128, d_model=32,
                        n_layers=2, n_heads=4, max_seq_len=16,
                        dtype="float32", moe_num_experts=4,
                        moe_group_size=64)
    ds = SyntheticLMDataset(size=32, seq_len=16, vocab_size=128, seed=0)
    loader = ShardedDataLoader(ds, cpu8, batch_size=2, shuffle=False)
    trainer = Trainer(cfg, cpu8, model, loader)
    batch = next(iter(loader.epoch(0)))
    m1 = trainer.train_step(batch)
    m2 = trainer.train_step(batch)
    assert np.isfinite(float(m1["loss"]))
    assert float(m2["loss"]) < float(m1["loss"]) + 1.0


def test_moe_composes_with_ulysses(cpu8):
    """Routed MoE under Ulysses sequence parallelism: attention
    re-shards (seq <-> heads) around an MLP whose token routing is
    oblivious to the sp layout — losses must match plain dp."""
    from distributed_training_tpu.config import Config
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               SyntheticLMDataset)
    from distributed_training_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from distributed_training_tpu.runtime import fake_cpu_runtime
    from distributed_training_tpu.train.trainer import Trainer

    losses = {}
    for tag, ndev, axes, impl in (("dp", 2, {}, "naive"),
                                  ("sp", 8, {"sp": 4}, "ulysses")):
        rt = fake_cpu_runtime(ndev, **axes)
        cfg = Config()
        cfg.train.batch_size = 2
        cfg.train.total_epochs = 1
        cfg.train.log_every = 0
        cfg.train.learning_rate = 0.01
        model = Transformer(TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4,
            max_seq_len=16, dtype="float32", attention_impl=impl,
            moe_num_experts=4, moe_top_k=2))
        ds = SyntheticLMDataset(size=8, seq_len=16, vocab_size=64,
                                seed=0)
        loader = ShardedDataLoader(ds, rt, batch_size=2, shuffle=False)
        trainer = Trainer(cfg, rt, model, loader)
        losses[tag] = [float(trainer.train_step(b)["loss"])
                       for b in loader.epoch(0)]
    np.testing.assert_allclose(losses["dp"], losses["sp"],
                               rtol=1e-5, atol=1e-6)


def test_topk_by_argmax_matches_lax_topk_fwd_and_bwd():
    """Routing selects via _topk_by_argmax (the SPMD partitioner
    cannot partition lax.top_k's TopK custom-call and all-gathered the
    routing probs across shards). Selection, ordering AND gradient must match lax.top_k exactly
    — including tied probs (a freshly-initialized router ties every
    expert; jnp.max's VJP would split the cotangent across ties,
    leaking gradient onto unselected experts)."""
    from distributed_training_tpu.models.transformer import (
        _topk_by_argmax,
    )

    cases = [
        jnp.asarray([0.5, 0.5, 0.1, 0.5]),          # ties
        jnp.asarray([0.25, 0.25, 0.25, 0.25]),      # all tied (init)
        jax.random.uniform(jax.random.PRNGKey(0), (3, 5, 7)),
    ]
    for x in cases:
        for k in (1, 2):
            v_ref, i_ref = jax.lax.top_k(x, k)
            v, i = _topk_by_argmax(x, k)
            np.testing.assert_array_equal(np.asarray(i_ref),
                                          np.asarray(i))
            np.testing.assert_allclose(np.asarray(v_ref),
                                       np.asarray(v))
            g_ref = jax.grad(
                lambda p: jnp.sum(jax.lax.top_k(p, k)[0] ** 2))(x)
            g = jax.grad(
                lambda p: jnp.sum(_topk_by_argmax(p, k)[0] ** 2))(x)
            np.testing.assert_allclose(np.asarray(g_ref),
                                       np.asarray(g))
