"""The latent-attention expert model (models/latent_moe.py) against the
benchmark's plain reference (perfbench/reference/mla_moe.py), on the
CPU at tiny widths: the full forward, prefill then decode through the
latent paged cache on every cadence of the engine, the attention forms,
the shares of an expert layer, and the reference's constants against
the configuration file."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perfbench import common

from distributed_training_tpu.models import build_model, latent_moe
from distributed_training_tpu.ops import paged_attention as pa
from distributed_training_tpu.serving.engine import (Engine,
                                                     EngineConfig,
                                                     Request)
from distributed_training_tpu.serving.kv_cache import as_layer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KW = dict(vocab_size=96, d_model=32, n_layers=3, n_dense_layers=1,
          n_heads=4, q_lora_rank=24, kv_lora_rank=16,
          qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, d_ff=48,
          moe_d_ff=12, n_routed_experts=16, moe_top_k=4, max_seq_len=64)
REF = dict(QK_NOPE_HEAD_DIM=8, QK_ROPE_HEAD_DIM=4, V_HEAD_DIM=8,
           NUM_EXPERTS_PER_TOK=4, Q_BLOCK=16)


def moved(params, seed=6):
    """Norm scales are ones at init: move every leaf, so that a path
    that dropped one would be caught."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def build(**over):
    model = build_model("latent_moe", dtype="float32", **{**KW, **over})
    return model, moved(model.init(jax.random.PRNGKey(5)))


@pytest.fixture()
def ref(monkeypatch):
    module = common.load_reference({"reference": "mla_moe"})
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
    rows = np.random.default_rng(0).integers(0, 96, (2, 40))
    got = np.asarray(model.apply(params, jnp.asarray(rows, jnp.int32)))
    for row, lg in zip(rows, got):
        # float32 against float32: only the order of summation differs.
        np.testing.assert_allclose(
            lg, ref_logits(ref, params, row, ep_rank), atol=2e-4,
            rtol=2e-4)


def test_loss_matches_the_reference(ref):
    model, params = build(ep_size=4)
    rows = jnp.asarray(np.random.default_rng(1).integers(0, 96, (3, 33)),
                       jnp.int32)
    got = model.loss(params, {"tokens": rows}, jax.random.PRNGKey(0))[0]
    want = ref.loss(ref.from_program(params), rows, 4)
    assert abs(float(got) - float(want)) < 1e-4


def expert_layer_of(model, params, h):
    layer = jax.tree.map(lambda a: a[0], params["moe"])
    return latent_moe.expert_layer(h, layer["mlp"], model.cfg)


def test_shares_add_up_to_the_whole_layer(ref):
    """The routed parts of all four ranks, plus the shared expert once,
    are the uncut layer of the reference."""
    whole, params = build(ep_size=1)
    h = jax.random.normal(jax.random.PRNGKey(2), (24, 32), jnp.float32)
    mlp = jax.tree.map(lambda a: a[0], params["moe"]["mlp"])
    shared = np.asarray(latent_moe.gated_mlp(h, mlp["shared"]))
    total = shared.copy()
    picks_held = 0
    for rank in range(4):
        part, _ = build(ep_size=4, ep_rank=rank)
        cut = dict(mlp)
        for k in ("wg", "wu", "wd"):
            cut[k] = mlp[k][rank * 4:(rank + 1) * 4]
        y, counts = latent_moe.expert_layer(h, cut, part.cfg)
        total += np.asarray(y) - shared
        picks_held += int(counts[1])
        assert int(counts[0]) == 24 * 4
    ref.EP_RANK = 0
    layer = ref.from_program(params)["layers"][1]
    want = np.asarray(ref.experts(h, layer))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # Every pick lands on exactly one rank.
    assert picks_held == 24 * 4


def test_no_token_dropped_when_all_pick_one_expert():
    """A selection bias that sends every token to expert 1 first: all
    40 tokens get its output at their own gate weight, and the counters
    say so."""
    model, params = build(ep_size=4)
    mlp = jax.tree.map(lambda a: a[0], params["moe"]["mlp"])
    mlp["router_bias"] = mlp["router_bias"].at[1].set(100.0)
    h = jax.random.normal(jax.random.PRNGKey(3), (40, 32), jnp.float32)
    y, counts = latent_moe.expert_layer(h, mlp, model.cfg)
    idx, g = latent_moe.route(h, mlp, model.cfg)
    assert (np.asarray(idx[:, 0]) == 1).all()
    assert int(counts[2]) == 40 and int(counts[3]) == 1
    want = np.asarray(latent_moe.gated_mlp(h, mlp["shared"]))
    for e in range(4):          # the experts held by rank 0
        one = {k: mlp[k][e] for k in ("wg", "wu", "wd")}
        w = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)
        want = want + np.asarray(w[:, None]
                                 * latent_moe.gated_mlp(h, one))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5,
                               rtol=2e-5)
    assert np.abs(np.asarray(
        latent_moe.gated_mlp(h, {k: mlp[k][1] for k in
                                 ("wg", "wu", "wd")}))).min() > 0


CADENCES = {
    "batched": dict(),
    # The sampled per-launch path: a draw over one candidate is the
    # argmax.
    "sampled_top1": dict(temperature=0.7, top_k=1),
    "spec": dict(spec_k=3),
    "resident": dict(resident_k=4),
    "resident_spec": dict(resident_k=3, spec_k=2),
}


@pytest.mark.parametrize("cadence", list(CADENCES))
def test_engine_matches_the_reference(ref, cadence):
    """Prefill, in chunks, then decode through the latent paged cache:
    every streamed token is the argmax of the reference's full forward
    over what came before it, to a logit gap that float32 rounding
    explains."""
    model, params = build(ep_size=4)
    eng = Engine(model, params, EngineConfig(
        max_batch=4, page_size=4, num_pages=80, max_seq_len=64,
        prefill_chunk=8, prefill_slots=2, **CADENCES[cadence]))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, n).astype(np.int32)
               for n in (5, 19, 11, 26, 9)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=9))
    records = []
    for _ in range(400):
        if eng.idle:
            break
        records.append(eng.step())
    done = {d["id"]: d["tokens"] for d in eng.completed}
    assert sorted(done) == [f"r{i}" for i in range(5)]
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, np.asarray(done[f"r{i}"], np.int32)])
        rows = ref_logits(ref, params, seq[:-1])[len(p) - 1:]
        assert len(rows) == 9
        for row, tok in zip(rows, done[f"r{i}"]):
            assert row.max() - row[tok] < 1e-3
    # The counters ride the records of the steps that fetched.
    decode = [r for r in records if r["op"] == "decode"]
    assert decode and all(
        r["moe_picks"] > 0 and 0 <= r["moe_picks_held"] <= r["moe_picks"]
        and r["moe_load_max"] >= 1 and r["moe_layer_calls"] >= 1
        for r in decode)
    if cadence == "batched":
        # One token a slot a step, 2 expert layers, 4 picks a token.
        assert all(r["moe_picks"] == r["tokens"] * 2 * 4
                   and r["moe_layer_calls"] == 2
                   and r["moe_expert_calls"] == 2 * 4 for r in decode)
    forms = eng.paged_forms()
    assert all(f is None or f in ("absorbed", "expanded")
               for f in forms.values()), forms
    assert eng.cache.cfg.kind == "latent"
    assert eng.cache.cfg.kv_bytes_per_token() == 3 * (16 + 4) * 4
    # A token's row of a layer in whole 128-lane tiles, token-major.
    assert eng.cache.k_pages.shape == (1, 3, 80, 4, 128)
    assert eng.cache.v_pages.shape == (1, 3, 80, 4, 128)


def latent_case(B, S, P, H=4, seed=0):
    """Queries and a filled latent pool for ``B`` sequences of ``S``
    queries over tables of ``P`` pages of 4."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    N = B * P + 1
    c_pages = jax.random.normal(ks[0], (1, N, 4, 16), jnp.float32)
    r_pages = jax.random.normal(ks[1], (1, N, 4, 4), jnp.float32)
    rows = jnp.arange(1, N, dtype=jnp.int32).reshape(B, P)
    q_nope = jax.random.normal(ks[2], (B, S, H, 8), jnp.float32)
    q_rope = jax.random.normal(ks[3], (B, S, H, 4), jnp.float32)
    w_uk = jax.random.normal(ks[4], (16, H, 8), jnp.float32)
    w_uv = jax.random.normal(ks[5], (16, H, 8), jnp.float32)
    q_pos = (P * 4 - S + jnp.arange(S, dtype=jnp.int32))[None].repeat(B, 0)
    q_pos = q_pos.at[0, 0].set(-1)     # a padding query
    return (q_nope, q_rope, as_layer(c_pages), as_layer(r_pages), rows,
            q_pos, w_uk, w_uv, c_pages, r_pages)


@pytest.mark.parametrize("form", ["absorbed", "expanded"])
def test_latent_attention_forms_agree(monkeypatch, form):
    """Each form against attention written out over the expanded keys
    and values, on a case the rule would give to another form too."""
    *args, c_pages, r_pages = latent_case(B=3, S=5, P=6)
    q_nope, q_rope, _c, _r, rows, q_pos, w_uk, w_uv = args
    monkeypatch.setattr(pa, "latent_form", lambda *a, **k: form)
    with pa.observe_forms() as seen:
        got = pa.latent_attention_chunk(*args)
    assert seen == [form]
    c = c_pages[0][rows].reshape(3, 24, 16)
    r = r_pages[0][rows].reshape(3, 24, 4)
    k = jnp.einsum("bkr,rhn->bkhn", c, w_uk)
    v = jnp.einsum("bkr,rhv->bkhv", c, w_uv)
    scores = (jnp.einsum("bshn,bkhn->bhsk", q_nope, k)
              + jnp.einsum("bshd,bkd->bhsk", q_rope, r)) / 12 ** 0.5
    seen_k = jnp.arange(24)[None, None, :] <= q_pos[:, :, None]
    scores = jnp.where(seen_k[:, None], scores, -jnp.inf)
    want = jnp.einsum("bhsk,bkhv->bshv",
                      jax.nn.softmax(scores, -1), v)
    want = jnp.where((q_pos >= 0)[:, :, None, None], want, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("shape,form", [
    ((32, 1, 32), "absorbed"),          # a resident decode iteration
    ((32, 4, 32), "absorbed"),          # a speculative one
    ((4, 64, 32), "absorbed"),          # few queries in all
    ((1, 256, 32), "absorbed"),
    ((4, 128, 32), "expanded"),         # batched prefill steps
    ((4, 256, 32), "expanded"),
    ((1, 1024, 32), "expanded"),
])
def test_latent_form_rule(shape, form):
    """The rule at the benchmark's widths (rank 512, heads of 128 and
    128) against the chip's table (benchmarks/latent_form_table.py):
    decode never expands, prompt chunks of 512 queries in all do."""
    assert pa.latent_form(shape, (512, 128, 128)) == form


def test_latent_form_rule_without_a_saving():
    """Where a latent row is no wider than half a key and a value,
    absorbed is the cheaper pair too and nothing is ever expanded."""
    assert pa.latent_form((1, 4096, 32), (128, 128, 128)) == "absorbed"


def test_reference_constants_are_the_configuration_files():
    ref = common.load_reference({"reference": "mla_moe"})
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "joyai-llm-flash-ep4.json")) as f:
        conf = json.load(f)
    for const, key in [("QK_NOPE_HEAD_DIM", "qk_nope_head_dim"),
                       ("QK_ROPE_HEAD_DIM", "qk_rope_head_dim"),
                       ("V_HEAD_DIM", "v_head_dim"),
                       ("ROPE_THETA", "rope_theta"),
                       ("RMS_NORM_EPS", "rms_norm_eps"),
                       ("NUM_EXPERTS_PER_TOK", "num_experts_per_tok"),
                       ("ROUTED_SCALING_FACTOR",
                        "routed_scaling_factor")]:
        assert getattr(ref, const) == conf[key], const
    kw = conf["program"]["kwargs"]
    assert ref.EP_RANK == kw["ep_rank"]
    cfg = build_model(conf["program"]["build_model"], **kw).cfg
    assert cfg.experts_held == conf["n_routed_experts"] == 64
    assert conf["n_head"] == cfg.n_heads == conf["num_attention_heads"]
    assert conf["n_positions"] == cfg.max_seq_len \
        == conf["serving"]["engine"]["max_seq_len"]
    for ours, theirs in [("d_model", "hidden_size"),
                         ("q_lora_rank", "q_lora_rank"),
                         ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("d_ff", "intermediate_size"),
                         ("moe_d_ff", "moe_intermediate_size"),
                         ("moe_top_k", "num_experts_per_tok"),
                         ("n_layers", "num_hidden_layers"),
                         ("n_dense_layers", "first_k_dense_replace"),
                         ("n_shared_experts", "n_shared_experts"),
                         ("vocab_size", "vocab_size"),
                         ("rope_theta", "rope_theta"),
                         ("rms_norm_eps", "rms_norm_eps"),
                         ("routed_scaling_factor",
                          "routed_scaling_factor")]:
        assert getattr(cfg, ours) == conf[theirs], ours
    assert cfg.n_routed_experts == 256 and cfg.ep_size == 4


def test_engine_serves_experts_that_drop_nothing_and_refuses_the_rest():
    """``Engine`` no longer refuses a model for having experts: the
    GPT-2 block with ``moe_impl="dense"`` decodes what the model's own
    forward gives, and only the capacity-bounded layer is refused."""
    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
              max_seq_len=32, moe_num_experts=4, moe_top_k=2)
    model = build_model("transformer", dtype="float32",
                        attention_impl="naive", moe_impl="dense", **kw)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(
        max_batch=2, page_size=4, num_pages=17, max_seq_len=32,
        prefill_chunk=8))
    prompt = np.arange(3, 12, dtype=np.int32)
    got = np.asarray(eng.generate(prompt, 6))
    seq = prompt
    for tok in got:
        lg = model.apply(params, jnp.asarray(seq)[None])[0][0, -1]
        assert float(lg.max() - lg[tok]) < 1e-4
        seq = np.append(seq, tok)
    routed = build_model("transformer", dtype="float32", **kw)
    with pytest.raises(ValueError, match="drop"):
        Engine(routed, routed.init(jax.random.PRNGKey(0)),
               EngineConfig(max_seq_len=32))
