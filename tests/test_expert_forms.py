"""``models/experts.py::expert_layer``'s grouped product on the CPU
(its kernel interpreted) against the dense form, every held expert over
every row, for each router, activation and shared-expert kind of the
four expert models, under loads that stress the grouping; the counters
of both; the layer's gradient; a run's stacked experts read where they
lie; and the engine's programs of each expert block compiled for a v5e
with no chip."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.models import (build_model, experts,
                                             latent_moe, parallel_moe)

# Tiny widths of the three model families whose expert layers differ:
# sigmoid with a selection bias and a factor, silu, one shared expert
# (latent_moe); softmax over the chosen, relu, no shared expert
# (window_moe); plain sigmoid, silu, four shared experts averaged
# (parallel_moe).
KW = {
    "latent_moe": dict(vocab_size=96, d_model=32, n_layers=2,
                       n_dense_layers=1, n_heads=4, q_lora_rank=24,
                       kv_lora_rank=16, qk_nope_head_dim=8,
                       qk_rope_head_dim=4, v_head_dim=8, d_ff=48,
                       moe_d_ff=12, n_routed_experts=16, moe_top_k=4,
                       routed_scaling_factor=2.5, max_seq_len=64),
    "window_moe": dict(vocab_size=96, d_model=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, head_dim=8, moe_d_ff=12,
                       n_routed_experts=16, moe_top_k=3, window=32,
                       window_layout=(0, 1), rope_layout=(0, 1),
                       max_seq_len=64),
    "parallel_moe": dict(vocab_size=96, d_model=32, n_layers=2,
                         n_heads=4, n_kv_heads=2, head_dim=8,
                         moe_d_ff=12, n_routed_experts=16, moe_top_k=3,
                         n_shared_experts=4, window=32,
                         window_layout=(1, 0), rope_layout=(1, 0),
                         max_seq_len=64),
}
EXISTING = len(experts.COUNTERS) - 1        # all but moe_rows_computed
TILE = experts._TILE_ROWS


def params_of(name, ep_size=4, ep_rank=1, seed=5, **over):
    """``(cfg, mlp, shared)``: the first expert layer's parameters,
    every leaf moved off its init, and the model's ``shared=`` hook."""
    model = build_model(name, dtype="float32",
                        **{**KW[name], "ep_size": ep_size,
                           "ep_rank": ep_rank, **over})
    params = model.init(jax.random.PRNGKey(seed))
    stacked = (params["moe"] if name == "latent_moe"
               else params["runs"][0])
    mlp = jax.tree.map(lambda a: a[0], stacked["mlp"])
    leaves, tree = jax.tree.flatten(mlp)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    mlp = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    shared = (None if name != "parallel_moe" else
              lambda x, s, w: parallel_moe.shared_mean(x, s, w,
                                                       model.cfg))
    return model.cfg, mlp, shared


def dense(h, mlp, cfg, valid=None, logits=None, shared=None):
    """``(y, counts)``: the layer as every held expert over every row,
    weighted by the row's gate for it (0 where the row did not choose
    it), and the five counters that form kept: the reference the
    grouped product is held to."""
    dt = h.dtype
    idx, g = (experts.route(h, mlp, cfg) if logits is None
              else experts.route(h, mlp, cfg, logits))
    onehot = jax.nn.one_hot(idx - cfg.expert_offset, cfg.experts_held,
                            dtype=jnp.float32)
    combine = jnp.einsum("tk,tke->te", g, onehot)
    act = (experts._ACTS[cfg.expert_act](
        jnp.einsum("td,edf->tef", h, mlp["wg"].astype(dt)))
        * jnp.einsum("td,edf->tef", h, mlp["wu"].astype(dt)))
    y = jnp.einsum("tef,efd->td", act * combine.astype(dt)[..., None],
                   mlp["wd"].astype(dt))
    if "shared" in mlp:
        y = y + (shared(h, mlp["shared"], experts._cast)
                 if shared is not None else experts.gated_mlp(
                     h, mlp["shared"], act=cfg.expert_act))
    ok = (jnp.ones(h.shape[:1], bool) if valid is None else valid)
    load = jnp.sum(onehot * ok[:, None, None], axis=(0, 1))
    counts = jnp.stack([jnp.sum(ok) * cfg.moe_top_k, jnp.sum(load),
                        jnp.max(load), jnp.any(ok),
                        jnp.any(ok) * cfg.experts_held]).astype(jnp.int32)
    return y, counts


def both(cfg, mlp, h, valid=None, logits=None, shared=None):
    """``{form: (y, counts)}``: the layer (``"grouped"``) and the dense
    reference."""
    out = {}
    for form, fn in (("grouped", experts.expert_layer), ("dense", dense)):
        y, counts = jax.jit(lambda h, v, r: fn(
            h, mlp, cfg, v, logits=r, shared=shared))(h, valid, logits)
        out[form] = (np.asarray(y), np.asarray(counts))
    return out


def grouped_rows(load, tm):
    """The rows of the tiles a grouped product visits: a group of its
    expert's picks, the groups one after another from row 0."""
    ends = np.cumsum(load)
    return tm * int(sum((e + tm - 1) // tm - (e - n) // tm
                        for n, e in zip(load, ends) if n))


def held_load(cfg, mlp, h, valid, logits=None):
    idx, _ = (experts.route(h, mlp, cfg) if logits is None
              else experts.route(h, mlp, cfg, logits))
    local = np.asarray(idx) - cfg.expert_offset
    ok = np.ones(h.shape[0], bool) if valid is None else np.asarray(valid)
    return np.array([int(((local == e) & ok[:, None]).sum())
                     for e in range(cfg.experts_held)])


def check(cfg, mlp, h, valid=None, logits=None, shared=None):
    out = both(cfg, mlp, h, valid, logits, shared)
    (yd, cd), (yg, cg) = out["dense"], out["grouped"]
    rows = slice(None) if valid is None else np.asarray(valid)
    # float32 against float32: only the order of the sums differs.
    np.testing.assert_allclose(yg[rows], yd[rows], rtol=1e-5,
                               atol=1e-6 * float(np.abs(yd).max() + 1))
    np.testing.assert_array_equal(cg[:EXISTING], cd)
    load = held_load(cfg, mlp, h, valid, logits)
    assert cg[1] == load.sum()
    assert cg[-1] == grouped_rows(load, TILE)
    return yd, yg, cd, cg


CASES = [("latent_moe", 4, 1), ("latent_moe", 1, 0),
         ("window_moe", 4, 2), ("window_moe", 4, 0),
         ("parallel_moe", 4, 3), ("parallel_moe", 16, 0)]


@pytest.mark.parametrize("name,ep_size,ep_rank", CASES)
def test_grouped_is_dense_for_each_router_and_activation(name, ep_size,
                                                         ep_rank):
    """Sigmoid with a selection bias and a factor, silu, one shared
    expert; softmax, relu, none; plain sigmoid, silu, four shared
    experts averaged through ``shared=``: each with ``expert_offset``
    0 and past 0, and 300 rows, so that the groups straddle tiles."""
    cfg, mlp, shared = params_of(name, ep_size, ep_rank)
    assert cfg.expert_offset == ep_rank * cfg.experts_held
    assert (name == "window_moe") == (cfg.router_score == "softmax")
    h = jax.random.normal(jax.random.PRNGKey(2), (300, 32), jnp.float32)
    logits = (experts.router_logits(h, mlp["router"])
              if name != "latent_moe" else None)
    yd, _yg, cd, _cg = check(cfg, mlp, h, logits=logits, shared=shared)
    assert 0 < cd[1] < cd[0] or ep_size == 1


@pytest.mark.parametrize("name", list(KW))
def test_dead_rows_go_to_no_group(name):
    """Rows ``valid`` marks dead are routed by neither form's counters
    and in the grouped form take no tile; the live rows match."""
    cfg, mlp, shared = params_of(name)
    h = jax.random.normal(jax.random.PRNGKey(3), (260, 32), jnp.float32)
    valid = jnp.asarray(np.random.default_rng(0).random(260) < 0.6)
    _yd, _yg, cd, _cg = check(cfg, mlp, h, valid, shared=shared)
    assert cd[0] == int(valid.sum()) * cfg.moe_top_k


def test_every_pick_on_one_held_expert():
    """A router of one pick a row whose logits send every row to the
    same held expert: one group of all 300 rows, no tile of any other
    expert visited."""
    cfg, mlp, _ = params_of("window_moe", moe_top_k=1)
    h = jax.random.normal(jax.random.PRNGKey(4), (300, 32), jnp.float32)
    logits = jnp.zeros((300, cfg.n_routed_experts)).at[
        :, cfg.expert_offset + 2].set(10.0)
    _yd, _yg, _cd, cg = check(cfg, mlp, h, logits=logits)
    assert cg[1] == cg[2] == 300
    assert cg[-1] == -(-300 // experts._TILE_ROWS) * experts._TILE_ROWS


def test_every_row_on_the_same_held_experts_fills_the_capacity():
    """Every row's ``moe_top_k`` picks all held: the groups take all of
    the static ``rows x min(k, held)`` capacity."""
    cfg, mlp, _ = params_of("window_moe")
    h = jax.random.normal(jax.random.PRNGKey(5), (256, 32), jnp.float32)
    lo = cfg.expert_offset
    logits = jnp.zeros((256, cfg.n_routed_experts)).at[
        :, lo:lo + cfg.moe_top_k].set(jnp.asarray([9.0, 8.0, 7.0]))
    _yd, _yg, _cd, cg = check(cfg, mlp, h, logits=logits)
    assert cg[1] == 256 * cfg.moe_top_k


@pytest.mark.parametrize("name", list(KW))
def test_no_pick_on_any_held_expert(name):
    """Every pick lands on another rank's experts: every group is empty,
    the kernel visits no tile, and the routed part is zero in both
    forms (the shared experts are what is left)."""
    cfg, mlp, shared = params_of(name, ep_rank=0)
    h = jax.random.normal(jax.random.PRNGKey(6), (280, 32), jnp.float32)
    away = jnp.zeros((280, cfg.n_routed_experts)).at[
        :, cfg.experts_held:].set(5.0)
    if name == "latent_moe":
        mlp = dict(mlp, router_bias=mlp["router_bias"].at[
            :cfg.experts_held].set(-100.0))
        away = None
    yd, yg, _cd, cg = check(cfg, mlp, h, logits=away, shared=shared)
    assert cg[1] == 0 and cg[-1] == 0
    if "shared" not in mlp:
        assert not np.abs(yg).max() and not np.abs(yd).max()


def test_bfloat16_is_as_near_float32_as_the_dense_form():
    """In bfloat16 the grouped form rounds where the dense one does
    once fused (the gate / up products, the activation times them and
    the gate, one cast of the float32 sum over a row's picks): it lies
    as near the layer in float32 as the dense form does, and within a
    bfloat16 step of it."""
    cfg32, mlp, _ = params_of("latent_moe")
    cfg = build_model("latent_moe", dtype="bfloat16",
                      **{**KW["latent_moe"], "ep_size": 4,
                         "ep_rank": 1}).cfg
    h = jax.random.normal(jax.random.PRNGKey(7), (300, 32),
                          jnp.float32).astype(jnp.bfloat16)
    mlp16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), mlp)
    out = both(cfg, mlp16, h)
    yd = out["dense"][0].astype(np.float32)
    yg = out["grouped"][0].astype(np.float32)
    # The same weights and rows in float32: the layer without rounding.
    y32 = np.asarray(experts.expert_layer(
        h.astype(jnp.float32),
        jax.tree.map(lambda a: a.astype(jnp.float32), mlp16), cfg32)[0])
    step = np.abs(yd).max() * 2.0 ** -7
    assert np.abs(yg - yd).max() <= step
    assert np.abs(yg - y32).mean() <= 1.1 * np.abs(yd - y32).mean()
    np.testing.assert_array_equal(out["grouped"][1][:EXISTING],
                                  out["dense"][1][:EXISTING])


def test_a_layer_of_the_stack_is_the_sliced_layer():
    """``experts.layer_of`` leaves a run's held experts stacked with the
    layer's index beside them; the layer reads the same experts as the
    layer sliced out of the stack, and a run without routed experts is
    sliced whole."""
    model = build_model("latent_moe", dtype="float32",
                        **{**KW["latent_moe"], "n_layers": 3,
                           "ep_size": 4, "ep_rank": 1})
    params = model.init(jax.random.PRNGKey(8))
    run = params["moe"]
    h = jax.random.normal(jax.random.PRNGKey(9), (40, 32), jnp.float32)
    layer = experts.layer_of(run, jnp.int32(1))
    sliced = jax.tree.map(lambda a: a[1], run)
    assert layer["mlp"]["wg"].shape == run["mlp"]["wg"].shape
    assert int(layer["mlp"]["layer"]) == 1
    assert jax.tree.map(jnp.shape, layer["attn"]) == jax.tree.map(
        jnp.shape, sliced["attn"])
    ys = [np.asarray(jax.jit(lambda h, m: experts.expert_layer(
        h, m, model.cfg)[0])(h, m)) for m in (layer["mlp"], sliced["mlp"])]
    np.testing.assert_array_equal(*ys)
    dense_run = experts.layer_of(params["dense"], jnp.int32(0))
    assert "layer" not in dense_run["mlp"]
    assert jax.tree.map(jnp.shape, dense_run) == jax.tree.map(
        lambda a: a.shape[1:], params["dense"])


@pytest.mark.parametrize("name", list(KW))
def test_the_gradient_is_the_dense_forms(name):
    """The grouped kernel has no gradient of its own: the layer's is
    the dense form's, for the rows, the held experts, the router and
    the shared experts alike."""
    cfg, mlp, shared = params_of(name)
    h = jax.random.normal(jax.random.PRNGKey(10), (40, 32), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(11), (40, 32), jnp.float32)

    def loss(fn):
        return jax.jit(jax.grad(lambda h, m: jnp.sum(
            t * fn(h, m, cfg, shared=shared)[0]), argnums=(0, 1)))(h, mlp)

    grads = loss(experts.expert_layer), loss(dense)
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-6 * float(np.abs(b).max() + 1))
    assert float(np.abs(grads[0][1]["wd"]).max()) > 0


def test_a_hidden_width_in_blocks_and_one_that_does_not_divide(
        monkeypatch):
    """Where the three weight blocks of an expert's whole hidden width
    do not fit ``_WEIGHT_VMEM``, the kernel steps through it 128 columns
    at a time and sums every step; where no multiple of 128 divides the
    width it refuses, where it would have left columns out."""
    from distributed_training_tpu.ops import grouped_experts as ge

    # Room for a hidden step of 128 columns at 32 rows in float32.
    monkeypatch.setattr(ge, "_WEIGHT_VMEM", 2 * 3 * 32 * 4 * 128)
    assert ge.hidden_block(32, 384, 4) == 128
    assert ge.hidden_block(32, 96, 4) == 96
    cfg, mlp, _ = params_of("window_moe", moe_d_ff=384)
    h = jax.random.normal(jax.random.PRNGKey(12), (150, 32), jnp.float32)
    check(cfg, mlp, h)
    with pytest.raises(ValueError, match="divides the expert width 320"):
        ge.hidden_block(32, 320, 4)
    cfg, mlp, _ = params_of("window_moe", moe_d_ff=320)
    with pytest.raises(ValueError, match="divides the expert width 320"):
        experts.expert_layer(h, mlp, cfg)


# Tiny models of the four blocks that serve routed experts, at widths
# the kernel lowers at (its blocks are (128, D) rows and (D, F) weights),
# each with runs of two expert layers: a layer's experts sliced out of a
# run's stack have a shape of their own.
BLOCKS = {
    "latent_moe": {**KW["latent_moe"], "d_model": 128, "moe_d_ff": 128,
                   "n_layers": 3},
    "window_moe": {**KW["window_moe"], "d_model": 128, "moe_d_ff": 128,
                   "n_layers": 4, "window_layout": (0, 0, 1, 1),
                   "rope_layout": (0, 0, 1, 1)},
    "parallel_moe": {**KW["parallel_moe"], "d_model": 128,
                     "moe_d_ff": 128, "n_layers": 4,
                     "window_layout": (1, 1, 0, 0),
                     "rope_layout": (1, 1, 0, 0)},
    "sparse_latent_moe": dict(
        vocab_size=96, d_model=128, n_layers=3, n_dense_layers=1,
        layer_types=("full_attention",) * 3,
        n_heads=4, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, rope_theta=500.0,
        index_n_heads=4, index_head_dim=8, index_topk=8, swa_n_heads=2,
        swa_q_lora_rank=16, swa_kv_lora_rank=16, swa_qk_nope_head_dim=12,
        swa_qk_rope_head_dim=4, swa_v_head_dim=8, swa_rope_theta=100.0,
        window=5, d_ff=48, moe_d_ff=128, n_routed_experts=16,
        moe_top_k=3, max_seq_len=64),
}


@pytest.mark.parametrize("name", list(BLOCKS))
def test_engine_programs_run_the_kernel_compiled_for_a_v5e(
        monkeypatch, name):
    """The engine's programs of a tiny expert model of each block kind,
    compiled for a v5e with no chip (the kernel lowered by Mosaic, not
    interpreted): the resident decode program and the prefill program
    each hold one grouped kernel a run of expert layers, and neither
    slices a layer's experts out of the run's stack and copies them
    for it."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.serving.engine import (Engine,
                                                         EngineConfig)

    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")
    model = build_model(name, dtype="bfloat16",
                        **{**BLOCKS[name], "ep_size": 4})
    # bfloat16 weights, as served (perfbench/drivers/serve.py).
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init(jax.random.PRNGKey(0)))
    # The programs trace for the chip: the kernel is not interpreted.
    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    eng = Engine(model, params, EngineConfig(
        max_batch=4, page_size=8, num_pages=40, max_seq_len=64,
        prefill_chunk=16, prefill_slots=1, resident_k=4,
        prefix_sharing=False))
    runs = [layers["mlp"]["wg"].shape[0]
            for layers in eng.block.segments(params)
            if "router" in layers["mlp"]]
    assert runs and min(runs) == 2

    def shape(a):
        if not isinstance(a, jax.Array):
            return a
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=Format(
            Layout(major_to_minor=tuple(range(a.ndim))), chip))

    E, D, F = (model.cfg.experts_held, model.cfg.d_model,
               model.cfg.moe_d_ff)
    kernels = {}
    for fn, args in eng._warmup_calls():
        name_ = fn.__wrapped__.__name__
        text = fn.lower(*jax.tree.map(
            shape, (*eng._state_of(fn), *args))).compile().as_text()
        kernels[name_] = len(re.findall(
            r"%(dtt_grouped_experts\.\d+) = .*tpu_custom_call", text))
        # No instruction makes one layer's experts: the kernel reads
        # the run's stack.
        assert not re.findall(
            rf"= bf16\[(1,)?({E},{D},{F}|{E},{F},{D})\]", text), name_
    assert (kernels["serving_resident_decode"]
            == kernels["serving_prefill_batch"] == len(runs)), kernels


def test_sparse_programs_select_by_counting_compiled_for_a_v5e(
        monkeypatch):
    """The engine's programs of a tiny learned-selection model compiled
    for a v5e with no chip: the prefill program (a chunk of 16 over a
    table of 64: the masked form) and the resident decode program each
    find their exact top-k in ``dtt_select_topk``, lowered by Mosaic,
    hold no sort, and report ``select.threshold`` beside their attention
    form."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.serving.engine import (Engine,
                                                         EngineConfig)

    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")
    model = build_model("sparse_latent_moe", dtype="bfloat16",
                        **{**BLOCKS["sparse_latent_moe"], "ep_size": 4})
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init(jax.random.PRNGKey(0)))
    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    eng = Engine(model, params, EngineConfig(
        max_batch=4, page_size=8, num_pages=40, max_seq_len=64,
        prefill_chunk=16, prefill_slots=1, resident_k=4,
        prefix_sharing=False))

    def shape(a):
        if not isinstance(a, jax.Array):
            return a
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=Format(
            Layout(major_to_minor=tuple(range(a.ndim))), chip))

    kernels = {}
    for fn, args in eng._warmup_calls():
        name = fn.__wrapped__.__name__
        text = fn.lower(*jax.tree.map(
            shape, (*eng._state_of(fn), *args))).compile().as_text()
        kernels[name] = len(re.findall(
            r"%(dtt_select_topk\.\d+) = .*tpu_custom_call", text))
        assert not re.search(r"= \S+ sort\(", text), name
    forms = eng.paged_forms()
    assert kernels["serving_resident_decode"] >= 1, kernels
    assert kernels["serving_prefill_batch"] >= 1, kernels
    assert "flash.sparse" in forms["serving_prefill_batch"], forms
    for name in ("serving_resident_decode", "serving_prefill_batch"):
        assert forms[name].split("+").count("select.threshold") == 1, forms
