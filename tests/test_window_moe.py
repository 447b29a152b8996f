"""The window-and-global expert model (models/window_moe.py) against the
benchmark's plain reference (perfbench/reference/window_moe.py), on the
CPU at tiny widths: the full forward, prefill then decode through the
two-pool paged cache on every cadence of the engine past several turns
of the ring, both forms of paged attention over a ring, the shares of
an expert layer, the cache's two allocators, what moves pages, and the
reference's constants against the configuration file."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perfbench import common

from distributed_training_tpu.models import build_model, experts
from distributed_training_tpu.ops import paged_attention as pa
from distributed_training_tpu.serving.engine import (Engine,
                                                     EngineConfig,
                                                     Request)
from distributed_training_tpu.serving.kv_cache import (PagedCacheConfig,
                                                       PagedKVCache,
                                                       Pools, as_layer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Two periods of (global NoPE, three window RoPE layers); a window of
# 32 in sequences of up to 200: the ring of (32 + 8) / 4 = 10 pages
# turns five times.
KW = dict(vocab_size=96, d_model=32, n_layers=8, n_heads=4, n_kv_heads=2,
          head_dim=8, moe_d_ff=12, n_routed_experts=16, moe_top_k=3,
          window=32, window_layout=(0, 1, 1, 1) * 2,
          rope_layout=(0, 1, 1, 1) * 2, rope_theta=500.0, qk_std=0.2,
          max_seq_len=256)
REF = dict(N_KV_HEAD=2, HEAD_DIM=8, WINDOW=32,
           WINDOW_LAYOUT=(0, 1, 1, 1) * 2, ROPE_LAYOUT=(0, 1, 1, 1) * 2,
           ROPE_THETA=500.0, NUM_EXPERTS_PER_TOK=3, Q_BLOCK=16)
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
    model = build_model("window_moe", dtype=dtype, **{**KW, **over})
    return model, moved(model.init(jax.random.PRNGKey(5)))


@pytest.fixture()
def ref(monkeypatch):
    module = common.load_reference({"reference": "window_moe"})
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


@pytest.mark.parametrize("what", ["window", "rope", "router_input"])
def test_the_reference_sees_each_mechanism(ref, monkeypatch, what):
    """The reference with one mechanism taken away no longer agrees
    with the model: the window on window layers, positions on RoPE
    layers, the router reading the attention's input (fed the
    feed-forward's instead). So the agreement above holds each."""
    model, params = build(ep_size=4)
    row = np.random.default_rng(2).integers(0, 96, 90)
    got = np.asarray(model.apply(params, jnp.asarray(row[None])))[0]
    if what == "window":
        monkeypatch.setattr(ref, "WINDOW_LAYOUT", (0,) * 8)
    elif what == "rope":
        monkeypatch.setattr(ref, "ROPE_LAYOUT", (0,) * 8)
    else:
        def block(x, p, n_head, windowed, rotated):
            x = x + ref.attention(ref.rms(x, p["ln_1"]), p, n_head,
                                  windowed, rotated)
            h2 = ref.rms(x, p["ln_2"])
            return x + ref.experts(h2, h2 @ p["w_r"], p)
        monkeypatch.setattr(ref, "block", block)
    assert np.abs(got - ref_logits(ref, params, row)).max() > 1e-2


def test_shares_add_up_to_the_whole_layer(ref):
    """The routed parts of all four ranks are the uncut layer of the
    reference, the router's logits handed in as the model hands them."""
    whole, params = build(ep_size=1)
    h = jax.random.normal(jax.random.PRNGKey(2), (24, 32), jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(3), (24, 16), jnp.float32)
    mlp = jax.tree.map(lambda a: a[0], params["runs"][1]["mlp"])
    total = np.zeros((24, 32), np.float32)
    picks_held = 0
    for rank in range(4):
        part, _ = build(ep_size=4, ep_rank=rank)
        cut = dict(mlp)
        for k in ("wg", "wu", "wd"):
            cut[k] = mlp[k][rank * 4:(rank + 1) * 4]
        y, counts = experts.expert_layer(h, cut, part.cfg, logits=r)
        total += np.asarray(y)
        picks_held += int(counts[1])
        assert int(counts[0]) == 24 * 3
    ref.EP_RANK = 0
    layer = ref.from_program(params)["layers"][1]
    want = np.asarray(ref.experts(h, r, layer))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # Every pick lands on exactly one rank, and the gates sum to 1.
    assert picks_held == 24 * 3
    _idx, g = experts.route(h, mlp, whole.cfg, logits=r)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, atol=1e-6)


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
        # A sequence never holds more than a ring of window pages.
        assert all(eng.cache.ring_pages_of(s.req.id)
                   <= eng.cache.cfg.ring_pages
                   for s in eng.slots if s is not None)
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
    (1e-4: the model's logits are of order 1 and float32 sums of a few
    hundred terms differ in the sixth digit, so a streamed token's
    logit lies that near the reference's largest or is it; the same
    engine in bfloat16 reads 1.6e-3, below)."""
    model, params = build(ep_size=4)
    prompts = prompts_of()
    eng, records, done = serve(model, params, cadence, prompts)
    assert worst_gap(ref, params, prompts, done) < 1e-4
    cfg = eng.cache.cfg
    assert cfg.ring_pages == 10 and cfg.window_layers == (1, 2, 3, 5, 6, 7)
    # Two pools: 2 global layers in the table's pages, 6 window layers
    # in a ring a slot and a scratch page.
    assert isinstance(eng.cache.k_pages, Pools)
    assert eng.cache.k_pages.full.shape == (1, 2, 160, 4, 128)
    assert eng.cache.v_pages.ring.shape == (1, 6, 3 * 10 + 1, 4, 128)
    assert eng.cache.pages_used == 0          # free returned both kinds
    decode = [r for r in records if r["op"] == "decode"]
    assert decode and all(
        r["pages_total_window"] == 30 and r["pages_total_global"] == 159
        and r["pages_total"] == 189
        and r["pages_used"] == (r["pages_used_window"]
                                + r["pages_used_global"])
        and 0 <= r["window_bound_iters"] <= r["slot_iters"]
        and r["moe_picks"] > 0 for r in decode)
    # The prompt of 150 is past the window from its first decode step.
    assert sum(r["window_bound_iters"] for r in decode) > NEW
    forms = eng.paged_forms()
    assert all(set(f.split("+")) <= {"pool", "gather", "pool.window",
                                     "gather.window"}
               and any(part.endswith(".window") for part in f.split("+"))
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
    # Every call of the program (1 x 8 rows against a ring of 40 slots
    # and a table of 256) through the flash form's kernel.
    ("ragged_chunks", 1 << 10)], ids=lambda v: str(v))
def test_paged_logits_match_the_reference(ref, monkeypatch, feed, limit):
    """Logits, not tokens: every position's, to what float32 rounding
    explains (2e-4, as ``apply`` above), over 200 positions and five
    turns of the ring; and the same in bfloat16 is off by fifty times
    that (0.019: the held experts' product rounds once where XLA's
    fused dense form does, 0.031 when every step of it rounded)."""
    if limit:
        monkeypatch.setattr(pa, "_LOGITS_LIMIT", limit)
    sizes = FEEDS[feed]
    seq = np.random.default_rng(11).integers(0, 96, sum(sizes))
    model, params = build(ep_size=4)
    want = ref_logits(ref, params, seq)
    np.testing.assert_allclose(paged_logits(model, params, seq, sizes),
                               want, atol=2e-4, rtol=2e-4)
    low, _ = build(dtype="bfloat16", ep_size=4)
    assert np.abs(paged_logits(low, params, seq, sizes) - want).max() \
        > 1e-2


def test_a_uniform_model_keeps_the_single_pool():
    model = build_model("transformer", dtype="float32",
                        attention_impl="naive", vocab_size=64, d_model=32,
                        n_layers=2, n_heads=4, max_seq_len=32)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), EngineConfig(
        max_batch=2, page_size=4, num_pages=17, max_seq_len=32,
        prefill_chunk=8))
    assert not isinstance(eng.cache.k_pages, Pools)
    assert eng.cache.cfg.ring_pages == 0 and eng.cache.cfg.row_width == 8
    assert eng.cache.pages_total == 16 and eng.cache.pages_by_kind() == {}
    assert eng._counters == ()


@pytest.mark.parametrize("window", [0, 6])
def test_dense_block_honours_its_attention_window(window):
    """GPT-2's block served with ``attention_window``: every streamed
    token is what the model's own windowed forward gives (the engine
    used to drop the window without a word)."""
    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
              max_seq_len=48, pos_encoding="rope",
              attention_window=window)
    model = build_model("transformer", dtype="float32",
                        attention_impl="naive", **kw)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(
        max_batch=2, page_size=4, num_pages=25, max_seq_len=48,
        prefill_chunk=8, resident_k=4))
    prompt = np.arange(3, 24, dtype=np.int32)
    got = np.asarray(eng.generate(prompt, 12))
    full = build_model("transformer", dtype="float32",
                       attention_impl="naive", **{**kw,
                                                  "attention_window": 0})
    seq, differs = prompt, False
    for tok in got:
        lg = model.apply(params, jnp.asarray(seq)[None])[0][0, -1]
        assert float(lg.max() - lg[tok]) < 1e-4
        other = full.apply(params, jnp.asarray(seq)[None])[0][0, -1]
        differs |= bool(np.abs(np.asarray(lg - other)).max() > 1e-3)
        seq = np.append(seq, tok)
    assert differs == bool(window)


def ring_case(B, S, R, window, last, seed=0, ps=4, H=4, Hkv=2, hd=8):
    """``B`` sequences whose newest query is at position ``last[b]``,
    ``S`` queries each, over rings of ``R`` pages holding the rows the
    engine would have left there: position ``p`` in ring slot ``p %
    (R * ps)``. Returns the call's arguments and the dense keys and
    values by position."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    T = max(last) + 1
    k = jax.random.normal(ks[0], (B, T, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[1], (B, T, Hkv, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    N = B * R + 1
    kp = np.zeros((Hkv, N, ps, hd), np.float32)
    vp = np.zeros((Hkv, N, ps, hd), np.float32)
    rows = np.arange(1, N, dtype=np.int32).reshape(B, R)
    for b in range(B):
        for p in range(last[b] + 1):        # later rows overwrite
            page, off = rows[b, p // ps % R], p % ps
            kp[:, page, off] = np.asarray(k[b, p])
            vp[:, page, off] = np.asarray(v[b, p])
    q_pos = np.stack([np.arange(n - S + 1, n + 1) for n in last]
                     ).astype(np.int32)
    q_pos[0, 0] = -1                        # a dead query
    return (q, as_layer(jnp.asarray(kp)), as_layer(jnp.asarray(vp)),
            jnp.asarray(rows), jnp.asarray(q_pos)), k, v


@pytest.mark.parametrize("form", ["pool", "gather", "flash"])
@pytest.mark.parametrize("S,last", [(1, (70, 9, 41)), (8, (70, 12, 43)),
                                    (3, (39, 40, 41))])
def test_ring_attention_forms_agree(monkeypatch, form, S, last):
    """Each form over a ring of 10 pages of 4 against attention written
    out over the dense keys: one query a slot, a chunk of 8 that
    straddles the wrap (positions 36..43), sequences shorter than the
    window and the ring, a dead query."""
    window, R = 32, 10
    args, k, v = ring_case(3, S, R, window, last)
    q, _kp, _vp, _rows, q_pos = args
    monkeypatch.setattr(pa, "chunk_form", lambda *a, **kw: "gather"
                        if form == "flash" else form)
    if form == "flash":
        monkeypatch.setattr(pa, "_LOGITS_LIMIT", 0)
    with pa.observe_forms() as seen:
        got = pa.paged_attention_chunk(*args, window=window, ring=True)
    assert seen == [form + ".window"]
    T = k.shape[1]
    kk, vv = (jnp.repeat(a, 2, axis=2) for a in (k, v))
    scores = jnp.einsum("bshd,bkhd->bhsk", q, kk) / 8 ** 0.5
    back = q_pos[:, :, None] - jnp.arange(T)[None, None, :]
    seen_k = (back >= 0) & (back < window)
    scores = jnp.where(seen_k[:, None], scores, -jnp.inf)
    want = jnp.einsum("bhsk,bkhd->bshd", jax.nn.softmax(scores, -1), vv)
    want = jnp.where((q_pos >= 0)[:, :, None, None], want, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def cache(**over):
    return PagedKVCache(PagedCacheConfig(**{**dict(
        n_layers=4, n_kv_heads=2, head_dim=8, page_size=4, num_pages=40,
        max_seq_len=64, window=8, window_layers=(1, 2, 3), max_write=4,
        slots=2, block="WindowBlock"), **over}))


def test_the_cache_counts_both_pools():
    c = cache()
    assert c.cfg.ring_pages == 3 and c.cfg.row_width == 16 + 3
    assert c.cfg.window_num_pages == 2 * 3 + 1
    assert c.pages_total == 39 + 6 and c.pages_used == 0
    c.join("a")
    assert c.ensure("a", 5)                 # 2 pages of each kind
    assert (c.pages_of("a"), c.ring_pages_of("a")) == (4, 2)
    assert c.ensure("a", 40)                # 10 pages; the ring stops at 3
    assert (c.pages_of("a"), c.ring_pages_of("a")) == (13, 3)
    row = c.page_row("a")
    assert (row[:10] > 0).all() and (row[10:16] == 0).all()
    assert (row[16:] > 0).all()
    c.advance("a", 6)
    assert c.trim("a", 6) == 8 + 1          # 2 pages of each kind stay
    assert (c.pages_of("a"), c.ring_pages_of("a")) == (4, 2)
    by_kind = c.pages_by_kind()
    assert by_kind == {"pages_used_global": 2, "pages_total_global": 39,
                       "pages_used_window": 2, "pages_total_window": 6}
    assert c.occupancy()["pages_used"] == 4
    c.join("b")
    assert c.ensure("b", 64) and c.token_capacity("b") == 64
    c.join("c")
    # The window pool holds a ring a slot: a third sequence finds the
    # global pool willing and the window pool short, and takes nothing.
    assert not c.can_admit(8) and not c.ensure("c", 8)
    assert c.pages_of("c") == 0
    assert c.free("a") == 4 and c.free("b") == 16 + 3 and c.free("c") == 0
    assert c.pages_used == 0
    assert [p["kind"] for p in c.pools()] == ["global", "window"]
    assert c.pools()[1]["ring_pages"] == 3
    assert c.footprint()["pool_bytes_tiled"] == sum(
        p["bytes"] for p in c.pools())


@pytest.mark.parametrize("feature", [
    "attach", "register_prefix", "privatize", "rename", "read_pages",
    "write_pages", "prefix_sharing", "adopt_batch", "session",
    "export_kv", "import_kv"])
def test_what_moves_pages_by_one_table_refuses_window_layers(feature):
    """Each names the block and what it is; none reads a ring as a
    table."""
    from distributed_training_tpu.serving import disagg

    c = cache()
    c.join("a")
    c.ensure("a", 8)
    c.advance("a", 8)
    model, params = build(ep_size=4)
    calls = {
        "attach": lambda: c.attach("a", [1], 4),
        "register_prefix": lambda: c.register_prefix("a", list(range(8))),
        "privatize": lambda: c.privatize("a"),
        "rename": lambda: c.rename("a", "b"),
        "read_pages": lambda: c.read_pages(np.zeros(1, np.int32),
                                           np.ones(1, np.int32)),
        "write_pages": lambda: c.write_pages(
            np.zeros(1, np.int32), np.ones(1, np.int32),
            np.zeros((1, 4, 2, 4, 8), np.float32),
            np.zeros((1, 4, 2, 4, 8), np.float32)),
        "export_kv": lambda: disagg.export_kv(c, "a"),
        "import_kv": lambda: disagg.import_kv(
            c, "a", np.zeros((4, 2, 8, 8), np.float32),
            np.zeros((4, 2, 8, 8), np.float32)),
        "prefix_sharing": lambda: Engine(model, params, EngineConfig(
            **{**ENGINE, "prefix_sharing": True})),
        "adopt_batch": lambda: Engine(
            model, params, EngineConfig(**ENGINE)).adopt_batch([]),
        "session": lambda: Engine(
            model, params, EngineConfig(**ENGINE)).submit(Request(
                id="s", prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=2, session="chat")),
    }
    with pytest.raises(NotImplementedError,
                       match="WindowBlock has window layers"):
        calls[feature]()


@pytest.mark.parametrize("how", ["preempt", "export_in_flight"])
def test_preemption_round_trips(ref, how):
    """What frees pages and starts again carries both rows: a storm
    preempted (or exported: a block with window layers has no dense
    export, so everything comes back as a fresh request) mid-decode
    returns every page of both pools and, resubmitted, streams what an
    undisturbed engine streams."""
    model, params = build(ep_size=4)
    prompts = prompts_of()[:2]
    _eng, _records, want = serve(model, params, "resident", prompts)
    eng = Engine(model, params, EngineConfig(**ENGINE, resident_k=4))
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=NEW))
    while not any(s is not None and len(s.generated) > 10
                  for s in eng.slots):
        eng.step()
    if how == "preempt":
        lost = eng.preempt()
    else:
        out = eng.export_in_flight()
        assert out["adoptable"] == []
        lost = out["requests"]
    assert len(lost) == 2 and eng.cache.pages_used == 0
    for req in lost:
        eng.submit(req)
    eng.run_until_drained()
    got = {d["id"]: d["tokens"] for d in eng.completed}
    assert got == want and eng.cache.pages_used == 0


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
    ref = common.load_reference({"reference": "window_moe"})
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "smallthinker-21b-ep4.json")) as f:
        conf = json.load(f)
    for const, key in [("N_KV_HEAD", "num_key_value_heads"),
                       ("HEAD_DIM", "head_dim"),
                       ("WINDOW", "sliding_window_size"),
                       ("ROPE_THETA", "rope_theta"),
                       ("RMS_NORM_EPS", "rms_norm_eps"),
                       ("NUM_EXPERTS_PER_TOK",
                        "moe_num_active_primary_experts")]:
        assert getattr(ref, const) == conf[key], const
    layers = conf["num_hidden_layers"]
    assert list(ref.WINDOW_LAYOUT) == conf["sliding_window_layout"][:layers]
    assert list(ref.ROPE_LAYOUT) == conf["rope_layout"][:layers]
    kw = conf["program"]["kwargs"]
    assert ref.EP_RANK == kw["ep_rank"]
    cfg = build_model(conf["program"]["build_model"], **kw).cfg
    assert cfg.experts_held == conf["moe_num_primary_experts"] == 16
    assert cfg.n_routed_experts == 64 and cfg.ep_size == 4
    assert conf["n_head"] == cfg.n_heads == conf["num_attention_heads"]
    assert conf["n_positions"] == cfg.max_seq_len \
        == conf["max_position_embeddings"] \
        == conf["serving"]["engine"]["max_seq_len"]
    assert list(cfg.window_layout) == list(ref.WINDOW_LAYOUT)
    assert list(cfg.rope_layout) == list(ref.ROPE_LAYOUT)
    for ours, theirs in [("d_model", "hidden_size"),
                         ("n_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("moe_d_ff", "moe_ffn_hidden_size"),
                         ("moe_top_k", "moe_num_active_primary_experts"),
                         ("n_layers", "num_hidden_layers"),
                         ("vocab_size", "vocab_size"),
                         ("window", "sliding_window_size"),
                         ("rope_theta", "rope_theta"),
                         ("rms_norm_eps", "rms_norm_eps")]:
        assert getattr(cfg, ours) == conf[theirs], ours
    assert conf["program"]["token_vocab"] == cfg.vocab_size
    # The yaml for generate.py and the server says the same.
    import yaml
    with open(os.path.join(ROOT, "conf", "model",
                           "smallthinker_21b_ep4.yaml")) as f:
        assert yaml.safe_load(f)["kwargs"] == kw
    # The engine's two pools at the file's geometry.
    from distributed_training_tpu.serving import engine as E
    ccfg = E._cache_config(build_model(
        conf["program"]["build_model"], **kw).serving_block(),
        EngineConfig(**conf["serving"]["engine"]), None, "bfloat16")
    assert ccfg.ring_pages == 320 and ccfg.window_num_pages == 10241
    assert ccfg.num_pages == 32 * ccfg.pages_per_seq + 1
    assert ccfg.kv_bytes_per_token() == 12 * 2048


@pytest.mark.parametrize("resident_k", [1, 4])
def test_prefill_lanes_go_first_come_first_served(resident_k):
    """A prompt that is being prefilled keeps the one lane when a later
    request lands in a lower slot: the lanes go by admission, not by
    slot (which slot a request finds free is chance, and with it the
    order of two waiting prompts and every time after)."""
    model, params = build(ep_size=4)
    eng = Engine(model, params, EngineConfig(
        **{**ENGINE, "prefill_slots": 1}, resident_k=resident_k))
    rng = np.random.default_rng(3)
    short, long_, late = (rng.integers(0, 96, n).astype(np.int32)
                          for n in (4, 40, 24))
    firsts = []
    for rid in ("short", "long", "late"):
        eng.add_token_listener(
            rid, lambda tok, done, rid=rid: rid in firsts
            or firsts.append(rid))
    eng.submit(Request(id="short", prompt=short, max_new_tokens=1))
    eng.submit(Request(id="long", prompt=long_, max_new_tokens=2))
    submitted = False
    for _ in range(200):
        eng.step()
        if not submitted and eng.slots[0] is None:
            # ``short`` is gone from slot 0 and ``long`` is in slot 1,
            # somewhere in its five chunks: ``late`` lands in slot 0.
            assert eng.slots[1] is not None \
                and eng.slots[1].req.id == "long"
            eng.submit(Request(id="late", prompt=late, max_new_tokens=2))
            submitted = True
        if submitted and eng.idle:
            break
    assert eng.idle and submitted
    assert {d["id"] for d in eng.completed} == {"short", "long", "late"}
    assert firsts == ["short", "long", "late"]


def flash_case(B, S, H, Hkv, hd, P, positions, ps=4, seed=0):
    """The arguments of one layer's call: ``B`` sequences of ``S``
    queries at ``positions`` (a row a sequence), a float32 pool whose
    every slot holds something, each sequence ``P`` pages of its
    own."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    N = B * P + 1
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    kp, vp = (as_layer(jax.random.normal(k, (Hkv, N, ps, hd),
                                         jnp.float32)) for k in ks[1:])
    rows = np.random.default_rng(seed).permutation(N - 1)[:B * P] + 1
    return (q, kp, vp, jnp.asarray(rows.reshape(B, P), jnp.int32),
            jnp.asarray(np.asarray(positions, np.int32)))


def run_of(start, n, S):
    """Positions of a chunk of ``S`` rows, ``n`` of them live from
    ``start`` on and the tail padding."""
    return [start + i if i < n else -1 for i in range(S)]


# name: (shape, window, ring, positions). Blocks of 16 queries against
# 128 slots everywhere, so that every case has several of each and some
# to skip.
FLASH = {
    # Tables of 256 slots: the first sequence sees one key block of two.
    "table": (dict(B=2, S=32, H=4, Hkv=2, hd=16, P=64), None, False,
              [run_of(90, 32, 32), run_of(200, 32, 32)]),
    # ... and with a window the second no longer sees the first block.
    "table_window": (dict(B=2, S=32, H=4, Hkv=2, hd=16, P=64), 48, False,
                     [run_of(90, 32, 32), run_of(200, 32, 32)]),
    # A ring of 160 slots for a window of 128 and chunks of 32, past
    # five turns (837 = 5 * 160 + 37), the chunk's own rows in it: the
    # seen slots wrap round the ring's end; and a sequence shorter than
    # the window.
    "ring_five_turns": (dict(B=2, S=32, H=4, Hkv=2, hd=16, P=40), 128,
                        True, [run_of(837, 32, 32), run_of(50, 32, 32)]),
    # Dead queries inside a chunk, and a sequence wholly dead.
    "dead": (dict(B=3, S=32, H=4, Hkv=2, hd=16, P=40), 128, True,
             [[-1 if i % 5 == 0 else 700 + i for i in range(32)],
              [-1] * 32, run_of(170, 32, 32)]),
    # smallthinker's heads: 7 query heads a kv head of 128, a tile each.
    "gqa7_heads_of_128": (dict(B=1, S=32, H=14, Hkv=2, hd=128, P=64),
                          None, False, [run_of(150, 32, 32)]),
    # gpt2's: heads of 64 two a tile, the third kv head alone in its.
    "two_heads_a_tile": (dict(B=2, S=32, H=6, Hkv=3, hd=64, P=40), 128,
                         True, [run_of(400, 32, 32), run_of(0, 32, 32)]),
    # 13 live rows of a chunk of 24, which is no whole number of query
    # blocks either.
    "padding_tail": (dict(B=2, S=24, H=4, Hkv=2, hd=16, P=64), None,
                     False, [run_of(131, 13, 24), run_of(7, 24, 24)]),
}


@pytest.mark.parametrize("kernel", ["flash", "ragged"])
@pytest.mark.parametrize("name", list(FLASH))
def test_flash_kernel_agrees_with_one_pass(name, kernel):
    """The flash form's kernel (interpreted here), float32 operands,
    against ``_tile_attention`` in one pass over the same gathered
    copy under ``_visible``'s mask: to float32 rounding, zeros where a
    query is dead, never NaN. And the ragged form's kernel over the
    same cases, chunks of 24 and 32 queries where the rule would never
    offer it: the walk of a sequence's seen pages eight a step (tables
    a third seen, rings past five turns, a sequence wholly dead),
    which must give what the copy gives."""
    shape, window, ring, positions = FLASH[name]
    args = flash_case(**shape, positions=positions)
    want = pa._gather_attention(*args, window=window, ring=ring)
    if kernel == "ragged":
        got = pa._ragged_attention(*args, window=window, ring=ring,
                                   pages=8)
        assert got.shape == want.shape and got.dtype == want.dtype
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
        dead = np.asarray(positions) < 0
        assert not got[dead].any() and np.abs(got[~dead]).max() > 0.1
        return
    got = pa._flash_attention(*args, window=window, ring=ring,
                              blocks=(16, 128))
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    dead = np.asarray(positions) < 0
    assert not got[dead].any() and np.abs(got[~dead]).max() > 0.1
    # The blocks the shapes would get (one key block here) say the same.
    np.testing.assert_allclose(
        np.asarray(pa._flash_attention(*args, window=window, ring=ring)),
        want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("shape,P,N,window,form", [
    # gpt2-xl's prefill program stays what it was, the two resident
    # decode programs read their pages in place (PR 37) ...
    ((4, 128, 25, 64), 64, 385, None, "gather"),
    ((16, 1, 25, 64), 64, 385, None, "ragged"),
    ((32, 1, 28, 128), 320, 10241, 4096, "ragged.window"),
    ((32, 1, 28, 128), 1024, 32769, None, "ragged"),
    # ... and smallthinker-21b-ep4's prompt chunk takes the flash kernel
    # in both kinds of layer.
    ((1, 1024, 28, 128), 320, 10241, 4096, "flash.window"),
    ((1, 1024, 28, 128), 1024, 32769, None, "flash"),
])
def test_the_rule_that_says_where_the_kernel_runs(shape, P, N, window,
                                                  form):
    """``_LOGITS_LIMIT`` over the static shapes alone: no shape of the
    cells the benchmark had before PR 32 reaches it, a chunk of 1,024
    against 5,120 and 16,384 slots does."""
    from distributed_training_tpu.serving.kv_cache import PoolLayout

    B, S, H, hd = shape
    layout = PoolLayout(H if hd == 64 else 4, hd)
    pool = layout.layer(jax.ShapeDtypeStruct(
        layout.shape(1, N, 16), jnp.bfloat16), 0)
    assert pa._one_pass_fits(shape, P * 16) == ("flash" not in form)
    with pa.observe_forms() as seen:
        out = jax.eval_shape(
            lambda *a: pa.paged_attention_chunk(
                *a, window=window, ring=bool(window)),
            jax.ShapeDtypeStruct(shape, jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((B, P), jnp.int32),
            jax.ShapeDtypeStruct((B, S), jnp.int32))
    assert seen == [form] and out.shape == shape


def test_engine_prefill_through_the_kernel_matches_the_reference(
        ref, monkeypatch):
    """With ``_LOGITS_LIMIT`` low enough that the prefill program (two
    lanes of 8 rows) takes the kernel in both kinds of layer, every
    streamed token is still the reference's argmax to 1e-4, past five
    turns of the ring; the form is reported a program."""
    monkeypatch.setattr(pa, "_LOGITS_LIMIT", 8000)
    model, params = build(ep_size=4)
    prompts = prompts_of()
    eng, _records, done = serve(model, params, "resident", prompts)
    assert worst_gap(ref, params, prompts, done) < 1e-4
    assert eng.paged_forms()["serving_prefill_batch"] \
        == "flash+flash.window"


@pytest.mark.parametrize("cadence", ["spec", "resident"])
def test_engine_in_the_ragged_form_emits_what_the_gather_form_emits(
        monkeypatch, cadence):
    """The toy at heads of 128 and pages of 16 (a page of a layer 16 KB
    in float32), where the rule gives the decode program the ragged
    form in BOTH kinds of layer (the kernel ``dtt_paged_decode``,
    interpreted here): prompts longer and shorter than the window,
    decode past two turns of the ring of 3 pages, every streamed token
    what the same engine streams with ``chunk_form`` forced to
    ``"gather"``; the form is reported a program, and the decode step
    records count the walk of both pools."""
    model, params = build(ep_size=4, head_dim=128)
    geometry = {**ENGINE, "page_size": 16, "num_pages": 64}
    program = {"spec": "serving_spec_decode",
               "resident": "serving_resident_decode"}[cadence]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, n).astype(np.int32)
               for n in (70, 37, 5)]

    def run():
        eng = Engine(model, params, EngineConfig(**geometry,
                                                 **CADENCES[cadence]))
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=24))
        records = []
        for _ in range(1000):
            if eng.idle:
                break
            records.append(eng.step())
        assert eng.idle
        return eng, records, {d["id"]: d["tokens"]
                              for d in eng.completed}

    eng, records, done = run()
    assert eng.cache.cfg.ring_pages == 3      # (32 + 8) rows of 16
    assert eng.paged_forms()[program] == "ragged+ragged.window"
    steps = [r for r in records if r["op"] == "decode"]
    # Two global layers' tables of 16 pages and six window layers'
    # rings of 3, every slot; a live slot walks at most 6 pages of a
    # table (94 positions) and all 3, or 4 by a run that starts inside
    # a page, of a ring.
    assert steps and all(
        r["kv_pages_tabled"] == r["iters"] * 3 * (2 * 16 + 6 * 3)
        and 8 * r["slot_iters"] <= r["kv_pages_walked"]
        <= (2 * 6 + 6 * 4) * r["slot_iters"] for r in steps)
    monkeypatch.setattr(pa, "chunk_form", lambda *a, **kw: "gather")
    forced, forced_records, want = run()
    assert forced.paged_forms()[program] == "gather+gather.window"
    assert not any("kv_pages_walked" in r for r in forced_records)
    assert done == want and all(len(t) == 24 for t in done.values())


@pytest.mark.parametrize("ring", [False, True], ids=["table", "ring"])
def test_the_form_tables_many_query_case_rehearses(ring):
    """``chip_smoke.paged_prefill_case`` (the rows
    ``benchmarks/paged_form_table.py`` times on the chip: the kernel
    against the XLA form, queries a block at a time) at a tiny size on
    the CPU, bfloat16 as there: the two agree within its band."""
    import sys
    sys.path.insert(0, ROOT)
    import chip_smoke

    row = chip_smoke.paged_prefill_case(
        2, 16, 4, 2, N=81, hd=64, ps=4, reps=1, start=100,
        **(dict(P=12, window=32, ring=True) if ring else dict(P=32)))
    assert row["ok"] and row["max_abs_diff"] < 0.05
    assert row["rule"] == ("gather.window" if ring else "gather")


@pytest.mark.parametrize("case", ["table", "ring", "forms"])
def test_the_form_tables_few_query_cases_rehearse(case):
    """``chip_smoke.paged_decode_case`` (the decode rows
    ``benchmarks/paged_form_table.py`` times on the chip: the ragged
    form's kernel against the gather and the pool form, tables part
    live and a ring that has turned) and ``paged_forms_case``'s ragged
    column, at a tiny size on the CPU, bfloat16 as there: the forms
    agree within the band, the walked pages are the live ones, and the
    fit of the rule's two constants reads rows like these."""
    import sys
    sys.path.insert(0, ROOT)
    import chip_smoke
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import paged_form_table

    if case == "forms":
        row = chip_smoke.paged_forms_case(4, 1, 4, 2, P=8, N=41, hd=64,
                                          ps=4, reps=1)
        assert {"gather_ms", "pool_ms", "ragged_ms"} <= set(row)
    else:
        row = chip_smoke.paged_decode_case(
            3, 2, 4, 2, N=40, hd=64, ps=4, reps=1, pool=True,
            **(dict(P=12, window=16, ring=True, context=67)
               if case == "ring" else dict(P=13, context=30)))
        # Two live sequences (the last is dead): positions 28 and 29
        # see pages 0..7 of a table, 13..16 of the ring's turns.
        assert row["pages"] == 2 * (5 if case == "ring" else 8)
        assert row["bytes"] == row["pages"] * 4 * 2 * 2 * 64 * 2
    assert row["ok"] and row["max_abs_diff"] < 0.05
    fit = paged_form_table.fit_ragged(
        [row, {**row, "ragged_ms": 2 * row["ragged_ms"],
               "pages": 3 * row["pages"]},
         {**row, "ragged_ms": 3 * row["ragged_ms"],
          "bytes": 2 * row["bytes"]}, {"name": "a row without it"}])
    assert fit["rows"] == 3 and np.isfinite(fit["_RAGGED_READ"])
