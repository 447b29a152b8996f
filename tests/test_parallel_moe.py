"""The parallel-block expert model (models/parallel_moe.py) against the
benchmark's plain reference (perfbench/reference/parallel_moe.py), on
the CPU at tiny widths: the full forward, prefill then decode through
the two-pool paged cache on every cadence of the engine past several
turns of the ring, what makes the block this block (one norm, LayerNorm,
adjacent RoPE pairs, shared experts averaged) each seen by the parity,
the shares of an expert layer, the forms paged attention takes at the
published head counts, and the reference's constants against the
configuration file."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perfbench import common

from distributed_training_tpu.models import (build_model, experts,
                                             parallel_moe, window_moe)
from distributed_training_tpu.ops import paged_attention as pa
from distributed_training_tpu.serving.engine import (Engine,
                                                     EngineConfig,
                                                     Request)
from distributed_training_tpu.serving.kv_cache import PoolLayout, Pools

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLIDING, FULL = "sliding_attention", "full_attention"
# One published period (three window RoPE layers, then a full NoPE
# layer); a window of 32 in sequences of up to 200: the ring of (32 + 8)
# / 4 = 10 pages turns five times.
KW = dict(vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
          head_dim=8, moe_d_ff=12, n_routed_experts=16, moe_top_k=3,
          n_shared_experts=4, window=32, window_layout=(1, 1, 1, 0),
          rope_layout=(1, 1, 1, 0), rope_theta=500.0, qk_std=0.2,
          max_seq_len=256)
REF = dict(N_KV_HEAD=2, HEAD_DIM=8, WINDOW=32,
           LAYER_TYPES=(SLIDING,) * 3 + (FULL,), ROPE_THETA=500.0,
           NUM_EXPERTS_PER_TOK=3, NUM_SHARED_EXPERTS=4, Q_BLOCK=16,
           V_BLOCK=32)
ENGINE = dict(max_batch=3, page_size=4, num_pages=160, max_seq_len=256,
              prefill_chunk=8, prefill_slots=2, prefix_sharing=False)


def moved(params, seed=6):
    """Norm scales are ones at init: move every leaf, so that a path
    that dropped one would be caught."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def build(dtype="float32", **over):
    model = build_model("parallel_moe", dtype=dtype, **{**KW, **over})
    return model, moved(model.init(jax.random.PRNGKey(5)))


@pytest.fixture()
def ref(monkeypatch):
    module = common.load_reference({"reference": "parallel_moe"})
    for name, value in REF.items():
        monkeypatch.setattr(module, name, value)
    return module


def ref_logits(ref, params, ids, rank=0):
    ref.EP_RANK = rank
    return np.asarray(ref.logits(ref.from_program(params),
                                 jnp.asarray(ids, jnp.int32), 4))


@pytest.mark.parametrize("ep_size,ep_rank", [(1, 0), (4, 0), (4, 2)])
def test_apply_matches_the_reference(ref, ep_size, ep_rank):
    model, params = build(ep_size=ep_size, ep_rank=ep_rank)
    rows = np.random.default_rng(0).integers(0, 96, (2, 90))
    got = np.asarray(model.apply(params, jnp.asarray(rows, jnp.int32)))
    for row, lg in zip(rows, got):
        # float32 against float32: only the order of summation differs.
        np.testing.assert_allclose(
            lg, ref_logits(ref, params, row, ep_rank), atol=2e-4,
            rtol=2e-4)


def test_loss_matches_the_reference(ref):
    model, params = build(ep_size=4)
    rows = jnp.asarray(np.random.default_rng(1).integers(0, 96, (3, 70)),
                       jnp.int32)
    got = model.loss(params, {"tokens": rows}, jax.random.PRNGKey(0))[0]
    want = ref.loss(ref.from_program(params), rows, 4)
    assert abs(float(got) - float(want)) < 1e-4


def serial_layer(self, layer, x, positions, rope, attend):
    """The block made serial: the experts read a second norm (the same
    scale) of ``x + a W_o``."""
    c = self.cfg
    h = parallel_moe.norm(x, layer["ln1"], c)
    attn = attend(*window_moe.project(h, layer["attn"], positions, rope,
                                      c))
    x = x + jnp.einsum("...hk,hkd->...d", attn, layer["attn"]["wo"])
    return x + parallel_moe.experts(
        parallel_moe.norm(x, layer["ln1"], c), layer["mlp"], c)[0]


TAMPERS = {
    "serial_block": lambda mp: mp.setattr(parallel_moe.ParallelMoE,
                                          "layer", serial_layer),
    "rms_norm": lambda mp: mp.setattr(
        parallel_moe, "norm", lambda x, scale, c: experts.rms_norm(
            x, scale, c.layer_norm_eps)),
    "rope_halves_paired": lambda mp: mp.setattr(
        parallel_moe.ParallelMoEConfig, "rope_pairs", "halves"),
    "rope_on_the_full_layer": lambda mp: mp.setitem(
        KW, "rope_layout", (1, 1, 1, 1)),
    "no_rope_on_sliding_layers": lambda mp: mp.setitem(
        KW, "rope_layout", (0, 0, 0, 0)),
    "window_ignored": lambda mp: mp.setitem(
        KW, "window_layout", (0, 0, 0, 0)),
    "shared_experts_summed": lambda mp: mp.setattr(
        parallel_moe, "shared_mean",
        lambda x, m, w, c: experts.gated_mlp(x, m, w, c.expert_act)),
    "router_bias_of_ones_on_half": lambda mp: mp.setattr(
        parallel_moe, "experts",
        lambda h, m, c, *a, **kw: experts.expert_layer(
            h, {**m, "router_bias": jnp.arange(16) % 2 * 1.0}, c,
            shared=lambda x, s, w: parallel_moe.shared_mean(x, s, w, c))),
}


@pytest.mark.parametrize("what", list(TAMPERS))
def test_the_parity_sees_each_mechanism(ref, monkeypatch, what):
    """The PROGRAM with one mechanism changed no longer agrees with the
    reference: a serial block, RMSNorm for LayerNorm, RoPE's halves
    paired, positions on the full layer or none on the sliding ones,
    the window ignored, the shared experts summed, a selection bias. So
    the agreement above holds each."""
    TAMPERS[what](monkeypatch)
    model, params = build(ep_size=4)
    row = np.random.default_rng(2).integers(0, 96, 90)
    got = np.asarray(model.apply(params, jnp.asarray(row[None])))[0]
    assert np.abs(got - ref_logits(ref, params, row)).max() > 1e-2


def test_sixteen_shares_add_up_to_the_whole_layer(ref):
    """The parts of all sixteen ranks, the shared experts counted once
    (every rank computes them alike: rank 0's here), are the uncut
    layer of the reference."""
    over = dict(n_routed_experts=32, moe_top_k=8)
    ref.NUM_EXPERTS_PER_TOK = 8
    whole, params = build(ep_size=1, **over)
    h = jax.random.normal(jax.random.PRNGKey(2), (24, 32), jnp.float32)
    mlp = jax.tree.map(lambda a: a[1], params["runs"][0]["mlp"])
    total = np.zeros((24, 32), np.float32)
    picks_held = 0
    for rank in range(16):
        part, _ = build(ep_size=16, ep_rank=rank, **over)
        cut = {k: mlp[k] for k in ("router",) + (("shared",) if rank == 0
                                                 else ())}
        for k in ("wg", "wu", "wd"):
            cut[k] = mlp[k][rank * 2:(rank + 1) * 2]
        y, counts = parallel_moe.experts(h, cut, part.cfg)
        total += np.asarray(y)
        picks_held += int(counts[1])
        assert int(counts[0]) == 24 * 8
    layer = ref.from_program(params)["layers"][1]
    want = np.asarray(ref.experts(h, layer))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # Every pick lands on exactly one rank, and the gates sum to 1.
    assert picks_held == 24 * 8
    _idx, g = experts.route(h, mlp, whole.cfg)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, atol=1e-6)


def test_the_stacked_shared_product_is_the_mean_of_four_experts():
    model, params = build()
    c = model.cfg
    shared = jax.tree.map(lambda a: a[0], params["runs"][0]["mlp"]["shared"])
    assert shared["wg"].shape == (32, 4 * 12)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 32), jnp.float32)
    got = parallel_moe.shared_mean(x, shared, experts._cast, c)
    four = [experts.gated_mlp(x, {
        "wg": shared["wg"][:, j * 12:(j + 1) * 12],
        "wu": shared["wu"][:, j * 12:(j + 1) * 12],
        "wd": shared["wd"][j * 12:(j + 1) * 12]}) for j in range(4)]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(sum(four) / 4), atol=1e-6)


def test_route_without_a_bias_is_route_with_a_zero_bias_and_factor_one():
    model, params = build()
    mlp = jax.tree.map(lambda a: a[0], params["runs"][0]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(4), (40, 32), jnp.float32)
    assert "router_bias" not in mlp
    assert not hasattr(model.cfg, "routed_scaling_factor")
    idx, g = experts.route(h, mlp, model.cfg)

    class WithFactor:
        router_score, moe_top_k, routed_scaling_factor = "sigmoid", 3, 1.0

    idx0, g0 = experts.route(h, {**mlp, "router_bias": jnp.zeros(16)},
                             WithFactor)
    assert (np.asarray(idx) == np.asarray(idx0)).all()
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g0))
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, atol=1e-6)


def test_the_router_reads_the_norms_float32_output():
    """In a bfloat16 program the router's logits are those of the
    norm's float32 output, not of ``h`` rounded to bfloat16 (in the
    first layer that rounding is a function of the token alone: a token
    on the top-k's edge would flip at every occurrence), and ``h`` is
    that output rounded."""
    model, params = build(dtype="bfloat16")
    c = model.cfg
    layer = jax.tree.map(lambda a: a[0], params["runs"][0])
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 32)).astype(
        jnp.bfloat16)
    h, logits = parallel_moe.normed_and_routed(x, layer, c)
    h32 = parallel_moe.norm(x.astype(jnp.float32), layer["ln1"], c)
    assert h.dtype == jnp.bfloat16 and logits.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(h),
                                  np.asarray(h32.astype(jnp.bfloat16)))
    want = experts.router_logits(h32, layer["mlp"]["router"])
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    rounded = experts.router_logits(h, layer["mlp"]["router"])
    assert np.abs(np.asarray(rounded - want)).max() > 1e-4


CADENCES = {
    "batched": dict(),
    "sampled_top1": dict(temperature=0.7, top_k=1),
    "spec": dict(spec_k=3),
    "resident": dict(resident_k=4),
    "resident_spec": dict(resident_k=3, spec_k=2),
}
PROMPTS = (150, 37, 5, 91)       # longer and shorter than the window
NEW = 50


def serve(model, params, cadence, prompts):
    eng = Engine(model, params, EngineConfig(**ENGINE,
                                             **CADENCES[cadence]))
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=NEW))
    records = []
    for _ in range(2000):
        if eng.idle:
            break
        records.append(eng.step())
    assert eng.idle
    return eng, records, {d["id"]: d["tokens"] for d in eng.completed}


def worst_gap(ref, params, prompts, done):
    """The largest gap between the reference's top logit and its logit
    of the streamed token, over every streamed token: logits, not
    tokens."""
    worst = 0.0
    for i, p in enumerate(prompts):
        toks = done[f"r{i}"]
        seq = np.concatenate([p, np.asarray(toks, np.int32)])
        rows = ref_logits(ref, params, seq[:-1])[len(p) - 1:]
        assert len(rows) == len(toks) == NEW
        worst = max(worst, max(float(row.max() - row[t])
                               for row, t in zip(rows, toks)))
    return worst


def prompts_of(seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in PROMPTS]


@pytest.mark.parametrize("cadence", list(CADENCES))
def test_engine_matches_the_reference(ref, cadence):
    """Prefill in chunks of 8 (the prompt of 150 straddles the ring's
    wrap at 40 rows three times), then 50 tokens of decode through the
    two pools, up to 200 positions: five turns of the ring of 40. Every
    streamed token is the argmax of the reference's full forward over
    what came before it, to a logit gap that float32 rounding explains
    (1e-4; the same engine in bfloat16 misses it, below)."""
    model, params = build(ep_size=4)
    prompts = prompts_of()
    eng, records, done = serve(model, params, cadence, prompts)
    assert worst_gap(ref, params, prompts, done) < 1e-4
    cfg = eng.cache.cfg
    # Local layers FIRST: the window layers are 0, 1, 2.
    assert cfg.ring_pages == 10 and cfg.window_layers == (0, 1, 2)
    assert cfg.block == "ParallelBlock"
    assert isinstance(eng.cache.k_pages, Pools)
    assert eng.cache.k_pages.full.shape == (1, 1, 160, 4, 128)
    assert eng.cache.v_pages.ring.shape == (1, 3, 3 * 10 + 1, 4, 128)
    assert eng.cache.pages_used == 0          # free returned both kinds
    decode = [r for r in records if r["op"] == "decode"]
    assert decode and all(
        r["pages_total_window"] == 30 and r["pages_total_global"] == 159
        and 0 <= r["window_bound_iters"] <= r["slot_iters"]
        and r["moe_picks"] > 0 for r in decode)
    assert sum(r["moe_picks_held"] for r in decode) > 0
    assert sum(r["window_bound_iters"] for r in decode) > NEW
    forms = eng.paged_forms()
    assert all(any(part.endswith(".window") for part in f.split("+"))
               for f in forms.values() if f), forms


@pytest.mark.parametrize("cadence", ["batched", "resident"])
def test_grouped_prefill_streams_the_dense_forms_tokens(ref, monkeypatch,
                                                        cadence):
    """Every streamed token of the engine, whose prompt chunks and
    decode iterations run the held experts as one grouped product, is
    the token of the same engine with the dense form (every held expert
    over every row) in its place, and the reference's to float32
    rounding; ``moe_rows_computed`` of every record that carries counts
    is the rows of the tiles the kernel visited."""
    model, params = build(ep_size=4)
    prompts = prompts_of()
    _, records, done = serve(model, params, cadence, prompts)
    with monkeypatch.context() as m:
        m.setattr(experts, "_routed", lambda act, x, g, local, mine,
                  load, *held: experts._dense(act, x, g, local, mine,
                                              *held))
        _, _, dense = serve(model, params, cadence, prompts)
    assert done == dense
    assert worst_gap(ref, params, prompts, done) < 1e-4
    counted = [r for r in records if "moe_picks_held" in r]
    assert {r["op"] for r in counted} >= {"prefill", "decode"}
    assert all(r["moe_rows_computed"] % experts._TILE_ROWS == 0
               and (r["moe_rows_computed"] > 0) == (r["moe_picks_held"] > 0)
               for r in counted)


def test_bfloat16_fails_the_float32_tolerance(ref):
    """The tolerance above is tight enough to see a lower precision:
    the same engine in bfloat16 misses it tenfold."""
    model, params = build(dtype="bfloat16", ep_size=4)
    prompts = prompts_of()
    _eng, _records, done = serve(model, params, "resident", prompts)
    assert worst_gap(ref, params, prompts, done) > 1e-3


def paged_logits(model, params, seq, sizes):
    """The logits after every position of ``seq``, through the engine's
    own chunk forward (``engine._chunk_hidden``: the two pools, the
    ring's coordinates, the block, paged attention) fed ``sizes`` rows
    at a time against an engine's cache: what the programs compute
    before they take an argmax."""
    from distributed_training_tpu.serving import engine as E

    eng = Engine(model, params, EngineConfig(**ENGINE))
    plan = E._plan(eng.block, eng.cfg, None)

    @jax.jit
    def forward(params, kp, vp, rows, tokens, start, n):
        x, _valid, counts, kp, vp = E._chunk_hidden(
            params, kp, vp, rows, tokens, start, n,
            jnp.ones((1,), bool), block=eng.block, plan=plan)
        return eng.block.logits(params, x), counts, kp, vp

    cache, out, at = eng.cache, [], 0
    kp, vp = (jax.tree.map(lambda p: p[0], pools)
              for pools in (cache.k_pages, cache.v_pages))
    cache.join("s")
    for n in sizes:
        assert cache.ensure("s", at + n)
        width = max(sizes)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :n] = seq[at:at + n]
        lg, counts, kp, vp = forward(
            params, kp, vp, jnp.asarray(cache.page_rows(["s"])),
            jnp.asarray(tokens), jnp.full((1,), at, jnp.int32),
            jnp.full((1,), n, jnp.int32))
        # The engine's own counter rides behind the block's five.
        assert int(counts[-1]) == int(at + n > 32)
        out.append(np.asarray(lg[0, :n], np.float32))
        cache.advance("s", n)
        at += n
    assert at == len(seq)
    return np.concatenate(out)


FEEDS = {
    # 24 chunks of 8, then one row at a time: the decode program's C = 1.
    "chunks_then_one_token": [8] * 24 + [1] * 8,
    # Chunks that start off a page and cross the ring's wrap unevenly.
    "ragged_chunks": [5, 8, 3, 8, 8, 7] * 5 + [5],
}


@pytest.mark.parametrize("feed,limit", [
    ("chunks_then_one_token", None), ("ragged_chunks", None),
    # Every call of the program through the flash form's kernel.
    ("ragged_chunks", 1 << 10)], ids=lambda v: str(v))
def test_paged_logits_match_apply_and_the_reference(ref, monkeypatch, feed,
                                                    limit):
    """Logits, not tokens: every position's, to what float32 rounding
    explains (2e-4), over 200 positions and five turns of the ring,
    against the model's own full forward and against the reference; and
    the same in bfloat16 is off by a hundred times that."""
    if limit:
        monkeypatch.setattr(pa, "_LOGITS_LIMIT", limit)
    sizes = FEEDS[feed]
    seq = np.random.default_rng(11).integers(0, 96, sum(sizes))
    model, params = build(ep_size=4)
    got = paged_logits(model, params, seq, sizes)
    np.testing.assert_allclose(got, ref_logits(ref, params, seq),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(
        got, np.asarray(model.apply(params, jnp.asarray(seq[None])))[0],
        atol=2e-4, rtol=2e-4)
    low, _ = build(dtype="bfloat16", ep_size=4)
    assert np.abs(paged_logits(low, params, seq, sizes)
                  - ref_logits(ref, params, seq)).max() > 2e-2


@pytest.mark.parametrize("shape,P,N,window,form", [
    # command-a-plus-ep16's resident decode: 8 tiles x 16 query rows a
    # kv head = 128 rows, exactly ``_RAGGED_ROWS`` ...
    ((32, 1, 128, 128), 320, 10241, 4096, "ragged.window"),
    ((32, 1, 128, 128), 512, 16385, None, "ragged"),
    # ... so two queries a slot are never offered the kernel ...
    ((32, 2, 128, 128), 320, 10241, 4096, "gather.window"),
    ((32, 2, 128, 128), 512, 16385, None, "gather"),
    # ... and the prompt chunk's float32 logits (2.7 and 4.3 GB in one
    # pass) take the flash form in both kinds of layer.
    ((1, 1024, 128, 128), 320, 10241, 4096, "flash.window"),
    ((1, 1024, 128, 128), 512, 16385, None, "flash"),
])
def test_the_forms_at_the_published_head_counts(shape, P, N, window,
                                                form):
    B, S, H, hd = shape
    layout = PoolLayout(8, hd)
    assert layout.lanes == 1024
    pool = layout.layer(jax.ShapeDtypeStruct(
        layout.shape(1, N, 16), jnp.bfloat16), 0)
    tiles, rows = 8, S * (H // 8)
    assert (tiles * rows <= pa._RAGGED_ROWS) == form.startswith("ragged")
    assert pa._one_pass_fits(shape, P * 16) == ("flash" not in form)
    with pa.observe_forms() as seen:
        out = jax.eval_shape(
            lambda *a: pa.paged_attention_chunk(
                *a, window=window, ring=bool(window)),
            jax.ShapeDtypeStruct(shape, jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((B, P), jnp.int32),
            jax.ShapeDtypeStruct((B, S), jnp.int32))
    assert seen == [form] and out.shape == shape


def test_what_moves_pages_by_one_table_names_the_block():
    model, params = build(ep_size=4)
    with pytest.raises(NotImplementedError,
                       match="ParallelBlock has window layers"):
        Engine(model, params, EngineConfig(**{**ENGINE,
                                              "prefix_sharing": True}))


def test_generate_cli_path_serves_the_model():
    """``generate.py``'s engine (one slot, no prefix sharing) gives the
    full forward's argmax through ``build_model`` and ``Engine``."""
    model, params = build(ep_size=4)
    eng = Engine(model, params, EngineConfig(
        max_batch=1, page_size=16, num_pages=9, max_seq_len=128,
        prefill_chunk=64, prefix_sharing=False))
    prompt = np.random.default_rng(4).integers(0, 96, 70).astype(np.int32)
    got = eng.generate(prompt, 20)
    plain = np.asarray(model.generate(params, jnp.asarray(prompt)[None],
                                      20))[0]
    assert got == plain.tolist()


def test_reference_constants_are_the_configuration_files():
    ref = common.load_reference({"reference": "parallel_moe"})
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "command-a-plus-ep16.json")) as f:
        conf = json.load(f)
    for const, key in [("N_KV_HEAD", "num_key_value_heads"),
                       ("HEAD_DIM", "head_dim"),
                       ("WINDOW", "sliding_window"),
                       ("ROPE_THETA", "rope_theta"),
                       ("LAYER_NORM_EPS", "layer_norm_eps"),
                       ("NUM_EXPERTS_PER_TOK", "num_experts_per_tok"),
                       ("NUM_SHARED_EXPERTS", "num_shared_experts"),
                       ("LOGIT_SCALE", "logit_scale")]:
        assert getattr(ref, const) == conf[key], const
    layers = conf["num_hidden_layers"]
    assert len(conf["layer_types"]) == conf["published"][
        "num_hidden_layers"] == 32
    assert list(ref.LAYER_TYPES) == conf["layer_types"][:layers]
    kw = conf["program"]["kwargs"]
    assert ref.EP_RANK == kw["ep_rank"]
    cfg = build_model(conf["program"]["build_model"], **kw).cfg
    assert cfg.experts_held == conf["num_experts"] == 8
    assert cfg.n_routed_experts == conf["published"]["num_experts"] == 128
    assert cfg.ep_size == 16
    assert conf["n_head"] == cfg.n_heads == conf["num_attention_heads"]
    assert conf["n_positions"] == cfg.max_seq_len \
        == conf["serving"]["engine"]["max_seq_len"]
    sliding = [int(t == SLIDING) for t in ref.LAYER_TYPES]
    assert list(cfg.window_layout) == list(cfg.rope_layout) == sliding
    assert conf["position_embedding_type"] == "rope_gptj" \
        and cfg.rope_pairs == "adjacent"
    assert conf["expert_selection_fn"] == cfg.router_score == "sigmoid"
    assert conf["hidden_act"] == cfg.expert_act == "silu"
    assert conf["use_parallel_block"] and conf["tie_word_embeddings"]
    for ours, theirs in [("d_model", "hidden_size"),
                         ("n_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("moe_d_ff", "intermediate_size"),
                         ("moe_top_k", "num_experts_per_tok"),
                         ("n_shared_experts", "num_shared_experts"),
                         ("n_layers", "num_hidden_layers"),
                         ("vocab_size", "vocab_size"),
                         ("window", "sliding_window"),
                         ("rope_theta", "rope_theta"),
                         ("layer_norm_eps", "layer_norm_eps"),
                         ("logit_scale", "logit_scale")]:
        assert getattr(cfg, ours) == conf[theirs], ours
    assert conf["program"]["token_vocab"] == cfg.vocab_size
    # The tied head: the parameters hold no second table.
    shapes = jax.eval_shape(build_model(
        conf["program"]["build_model"], **kw).init, jax.random.PRNGKey(0))
    assert "lm_head" not in shapes
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 4 * (8 * 3 * 4096 * 4096 + 4 * 3 * 4096 * 4096
                + 2 * 4096 * 16384 + 2 * 4096 * 1024 + 4096 * 128 + 4096) \
        + 32768 * 4096 + 4096
    # The yaml for generate.py and the server says the same.
    import yaml
    with open(os.path.join(ROOT, "conf", "model",
                           "command_a_plus_ep16.yaml")) as f:
        assert yaml.safe_load(f)["kwargs"] == kw
    # The engine's two pools at the file's geometry.
    from distributed_training_tpu.serving import engine as E
    ccfg = E._cache_config(build_model(
        conf["program"]["build_model"], **kw).serving_block(),
        EngineConfig(**conf["serving"]["engine"]), None, "bfloat16")
    assert ccfg.ring_pages == 320 and ccfg.window_num_pages == 10241
    assert ccfg.window_layers == (0, 1, 2)
    assert ccfg.num_pages == 32 * ccfg.pages_per_seq + 1 == 16385
    assert ccfg.kv_bytes_per_token() == 4 * 4096
    # Every key of the catalog's config that says something about the
    # shape is in the file, unchanged unless ``reduced`` names it.
    catalog = os.path.join(os.sep, "opt", "skills", "guides",
                           "model-configs", "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "command-a-plus-05-2026")
        assert conf["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in conf["reduced"]:
                assert conf[key] == value, key
