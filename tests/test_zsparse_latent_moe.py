"""The two-latent, learned-selection expert model
(models/sparse_latent_moe.py) against the benchmark's plain reference
(perfbench/reference/sparse_mla_moe.py), on the CPU at tiny widths: the
full forward, prefill then decode through the three-pool paged cache on
every cadence of the engine past several turns of the ring and past
``index_topk``, the selection against dense attention, latent attention
over a ring, the exact top-k, the shares of an expert layer, the new
counters against a count by hand, the cache's layouts a kind and its
index pool, and the reference's constants against the configuration
file. Collected after every other test file (the name sorts last)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perfbench import common

from distributed_training_tpu.models import build_model, experts
from distributed_training_tpu.ops import paged_attention as pa
from distributed_training_tpu.serving import engine as E
from distributed_training_tpu.serving.engine import (Engine,
                                                     EngineConfig,
                                                     Request)
from distributed_training_tpu.serving.kv_cache import (PagedCacheConfig,
                                                       PagedKVCache,
                                                       PoolLayout, Pools)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL, SLIDING = "full_attention", "sliding_attention"
TYPES = (FULL, FULL, SLIDING, SLIDING, SLIDING)
# One dense full layer, then a period of (full, three window layers): a
# window of 5 and a selection of 8 in sequences of up to 64, so that
# both bind from early on and the ring of (5 + 8) / 4 = 4 pages turns
# near four times.
KW = dict(vocab_size=96, d_model=32, n_layers=5, n_dense_layers=1,
          layer_types=TYPES, n_heads=4, q_lora_rank=16, kv_lora_rank=8,
          qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
          rope_theta=500.0, index_n_heads=4, index_head_dim=8,
          index_topk=8, swa_n_heads=2, swa_q_lora_rank=16,
          swa_kv_lora_rank=16, swa_qk_nope_head_dim=12,
          swa_qk_rope_head_dim=4, swa_v_head_dim=8, swa_rope_theta=100.0,
          window=5, d_ff=48, moe_d_ff=12, n_routed_experts=16,
          moe_top_k=3, qk_std=0.2, max_seq_len=64)
REF = dict(LAYER_TYPES=TYPES, FIRST_K_DENSE=1, QK_NOPE=8, QK_ROPE=4,
           ROPE_THETA=500.0, INDEX_TOPK=8, SWA_N_HEAD=2, SWA_QK_NOPE=12,
           SWA_QK_ROPE=4, SWA_ROPE_THETA=100.0, WINDOW=5, RESCALE=True,
           NUM_EXPERTS_PER_TOK=3, INDEX_KEY_DTYPE=None, Q_BLOCK=16,
           HEAD_BLOCK=2, ROW_BLOCK=16)
ENGINE = dict(max_batch=3, page_size=4, num_pages=60, max_seq_len=64,
              prefill_chunk=8, prefill_slots=2, prefix_sharing=False)


def moved(params, seed=6):
    """Norm scales are ones and biases zeros at init: move every leaf,
    so that a path that dropped one would be caught."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def build(dtype="float32", **over):
    model = build_model("sparse_latent_moe", dtype=dtype,
                        **{**KW, **over})
    return model, moved(model.init(jax.random.PRNGKey(5)))


@pytest.fixture()
def ref(monkeypatch):
    module = common.load_reference({"reference": "sparse_mla_moe"})
    for name, value in REF.items():
        monkeypatch.setattr(module, name, value)
    return module


def ref_logits(ref, params, ids, rank=0):
    ref.EP_RANK = rank
    return np.asarray(ref.logits(ref.from_program(params),
                                 jnp.asarray(ids, jnp.int32), 4))


@pytest.mark.parametrize("ep_size,ep_rank", [(1, 0), (8, 0), (8, 5)])
def test_apply_matches_the_reference(ref, ep_size, ep_rank):
    model, params = build(ep_size=ep_size, ep_rank=ep_rank)
    rows = np.random.default_rng(0).integers(0, 96, (2, 50))
    got = np.asarray(model.apply(params, jnp.asarray(rows, jnp.int32)))
    for row, lg in zip(rows, got):
        # float32 against float32: only the order of summation differs.
        np.testing.assert_allclose(
            lg, ref_logits(ref, params, row, ep_rank), atol=2e-4,
            rtol=2e-4)


def test_loss_matches_the_reference(ref):
    model, params = build(ep_size=8)
    rows = jnp.asarray(np.random.default_rng(1).integers(0, 96, (3, 40)),
                       jnp.int32)
    got = model.loss(params, {"tokens": rows}, jax.random.PRNGKey(0))[0]
    want = ref.loss(ref.from_program(params), rows, 4)
    assert abs(float(got) - float(want)) < 1e-4


@pytest.mark.parametrize("what", ["selection", "half_the_selection",
                                  "window", "gate", "rescale",
                                  "index_rope"])
def test_the_reference_sees_each_mechanism(ref, monkeypatch, what):
    """The reference with one mechanism taken away no longer agrees
    with the model: the selection (every earlier position kept), half
    of it, the window, the headwise gate, the rescale of the latents,
    RoPE on the indexer. So the agreement above holds each."""
    model, params = build(ep_size=8)
    row = np.random.default_rng(2).integers(0, 96, 50)
    got = np.asarray(model.apply(params, jnp.asarray(row[None])))[0]
    if what == "selection":
        monkeypatch.setattr(ref, "INDEX_TOPK", 64)
    elif what == "half_the_selection":
        monkeypatch.setattr(ref, "INDEX_TOPK", 4)
    elif what == "window":
        monkeypatch.setattr(ref, "WINDOW", 64)
    elif what == "gate":
        monkeypatch.setattr(ref, "head_gate", lambda h, a: jnp.ones(
            (h.shape[0], a["wg"].shape[-1])))
    elif what == "rescale":
        monkeypatch.setattr(ref, "RESCALE", False)
    else:
        monkeypatch.setattr(ref, "index_rope", lambda x, pos: x)
    assert np.abs(got - ref_logits(ref, params, row)).max() > 1e-2


def test_shares_add_up_to_the_whole_layer(ref):
    """The routed parts of all eight ranks and the shared expert,
    counted once, are the uncut layer of the reference."""
    whole, params = build(ep_size=1)
    h = jax.random.normal(jax.random.PRNGKey(2), (24, 32), jnp.float32)
    run = params["runs"][1]["mlp"]
    mlp = jax.tree.map(lambda a: a[0], run)
    shared = np.asarray(experts.gated_mlp(h, mlp["shared"]))
    total = np.zeros((24, 32), np.float32)
    picks_held = 0
    for rank in range(8):
        part, _ = build(ep_size=8, ep_rank=rank)
        cut = dict(mlp)
        for k in ("wg", "wu", "wd"):
            cut[k] = mlp[k][rank * 2:(rank + 1) * 2]
        y, counts = experts.expert_layer(h, cut, part.cfg)
        total += np.asarray(y) - shared       # every rank computes it
        picks_held += int(counts[1])
        assert int(counts[0]) == 24 * 3
    ref.EP_RANK = 0
    want = np.asarray(ref.experts(h, run, 0))
    np.testing.assert_allclose(total + shared, want, atol=2e-5,
                               rtol=2e-5)
    # Every pick lands on exactly one rank; routed_scaling_factor 1.
    assert picks_held == 24 * 3
    _idx, g = experts.route(h, mlp, whole.cfg)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, atol=1e-6)


CADENCES = {
    "batched": dict(),
    "sampled_top1": dict(temperature=0.7, top_k=1),
    "spec": dict(spec_k=3),
    "resident": dict(resident_k=4),
    "resident_spec": dict(resident_k=3, spec_k=2),
    # A chunk of 16: its 16 x 8 chosen rows are more than a table's 64.
    "resident_chunk16": dict(resident_k=4, prefill_chunk=16),
}
PROMPTS = (40, 13, 3, 25)   # longer and shorter than window and top-k
NEW = 20


def serve(model, params, cadence, prompts):
    eng = Engine(model, params, EngineConfig(**{**ENGINE,
                                                **CADENCES[cadence]}))
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=NEW))
    records = []
    for _ in range(2000):
        if eng.idle:
            break
        records.append(eng.step())
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
    """Prefill in chunks, then 20 tokens of decode through the three
    pools, up to 60 positions: near four turns of the ring of 16 rows
    and seven times ``index_topk``. Every streamed token is the argmax
    of the reference's full forward over what came before it, to a logit
    gap that float32 rounding explains (1e-4, as
    tests/test_window_moe.py; bfloat16 misses the logits' tolerance a
    hundredfold, below)."""
    model, params = build(ep_size=8)
    prompts = prompts_of()
    eng, records, done = serve(model, params, cadence, prompts)
    assert worst_gap(ref, params, prompts, done) < 1e-4
    cfg = eng.cache.cfg
    chunk = eng.cfg.prefill_chunk
    assert cfg.window_layers == (2, 3, 4) and cfg.global_layers == (0, 1)
    assert cfg.ring_pages == -(-(5 + chunk) // 4)
    # Three pools in k_pages, two in v_pages: 2 full layers in the
    # table's pages with their index keys beside them, 3 window layers
    # in a ring a slot; each row in one 128-lane tile.
    kp, vp = eng.cache.k_pages, eng.cache.v_pages
    assert isinstance(kp, Pools) and isinstance(vp, Pools)
    assert kp.full.shape == kp.index.shape == (1, 2, 60, 4, 128)
    assert kp.ring.shape == vp.ring.shape == (
        1, 3, 3 * cfg.ring_pages + 1, 4, 128)
    assert vp.full.shape == (1, 2, 60, 4, 128) and vp.index is None
    assert eng.cache.pages_used == 0
    decode = [r for r in records if r["op"] == "decode"]
    assert decode and all(
        0 <= r["sparse_bound_iters"] <= r["window_bound_iters"]
        <= r["slot_iters"]
        and 0 < r["index_keys_kept"] <= r["index_keys_scored"]
        and r["moe_picks"] > 0 for r in decode)
    # The prompt of 40 is past window and top-k from its first step.
    assert sum(r["sparse_bound_iters"] for r in decode) > NEW
    assert sum(r["index_keys_kept"] for r in decode) < sum(
        r["index_keys_scored"] for r in decode)
    forms = eng.paged_forms()
    sparse = {"absorbed.sparse", "flash.sparse"}
    window = {"absorbed.window", "expanded.window"}
    assert all(set(f.split("+")) <= sparse | window | {"select.threshold"}
               and "select.threshold" in f.split("+")
               and set(f.split("+")) & sparse
               and set(f.split("+")) & window
               for f in forms.values() if f), forms
    # The masked form where a chunk's queries choose more rows than the
    # table has (16 x 8 over 64), the gather form in every other
    # program.
    assert {name for name, f in forms.items()
            if f and "flash.sparse" in f} == (
        {"serving_prefill_batch"} if chunk == 16 else set()), forms


def paged_logits(model, params, seq, sizes, **engine):
    """The logits after every position of ``seq`` through the engine's
    own chunk forward (``engine._chunk_hidden``) fed ``sizes`` rows at a
    time against an engine's cache, and the counters of every call."""
    eng = Engine(model, params, EngineConfig(**{**ENGINE, **engine}))
    plan = E._plan(eng.block, eng.cfg, None)

    @jax.jit
    def forward(params, kp, vp, rows, tokens, start, n):
        x, _valid, counts, kp, vp = E._chunk_hidden(
            params, kp, vp, rows, tokens, start, n,
            jnp.ones((1,), bool), block=eng.block, plan=plan)
        return eng.block.logits(params, x), counts, kp, vp

    cache, out, counted, at = eng.cache, [], [], 0
    kp, vp = (jax.tree.map(lambda p: p[0], pools)
              for pools in (cache.k_pages, cache.v_pages))
    cache.join("s")
    width = max(sizes)
    for n in sizes:
        assert cache.ensure("s", at + n)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :n] = seq[at:at + n]
        lg, counts, kp, vp = forward(
            params, kp, vp, jnp.asarray(cache.page_rows(["s"])),
            jnp.asarray(tokens), jnp.full((1,), at, jnp.int32),
            jnp.full((1,), n, jnp.int32))
        counted.append(dict(zip(eng._counters, np.asarray(counts))))
        out.append(np.asarray(lg[0, :n], np.float32))
        cache.advance("s", n)
        at += n
    assert at == len(seq)
    return np.concatenate(out), counted


FEEDS = {
    # 6 chunks of 8, then one row at a time: the decode program's C = 1.
    "chunks_then_one_token": ([8] * 6 + [1] * 12, {}),
    # Chunks that start off a page and cross the ring's wrap unevenly.
    "ragged_chunks": ([5, 8, 3, 8, 8, 7, 6, 8, 5] + [1] * 5, {}),
    # Chunks of 16: more chosen rows than the table has.
    "wide_chunks": ([16, 11, 16, 9] + [1] * 8, dict(prefill_chunk=16)),
}


@pytest.mark.parametrize("feed,limit", [
    ("chunks_then_one_token", None), ("ragged_chunks", None),
    ("wide_chunks", None),
    # Every call with more than 8 queries a block of 8 queries at a
    # time, the indexer's scores with them.
    ("wide_chunks", 1 << 10)], ids=lambda v: str(v))
def test_paged_logits_match_the_reference(ref, monkeypatch, feed, limit):
    """Logits, not tokens: every position's, to what float32 rounding
    explains (2e-4, as ``apply`` above), over 60 positions; the same in
    bfloat16 is off by a hundred times that (one feed). And the counters
    of every call against a count by hand."""
    if limit:
        monkeypatch.setattr(pa, "_LATENT_LOGITS_LIMIT", limit)
    sizes, engine = FEEDS[feed]
    seq = np.random.default_rng(11).integers(0, 96, sum(sizes))
    model, params = build(ep_size=8)
    want = ref_logits(ref, params, seq)
    with pa.observe_forms() as forms:
        got, counted = paged_logits(model, params, seq, sizes, **engine)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # Chunks of 16 attend under the mask in one kernel (the selection
    # made a block of 8 queries at a time where the limit says so).
    assert ("flash.sparse" in forms) == (feed == "wide_chunks"), forms
    at = 0
    for n, counts in zip(sizes, counted):
        ends = np.arange(at + 1, at + n + 1)     # positions seen, a query
        # Two full layers score every visible position and keep 8.
        assert counts["index_keys_scored"] == 2 * ends.sum()
        assert counts["index_keys_kept"] == 2 * np.minimum(ends, 8).sum()
        assert counts["window_bound_iters"] == int(at + n > 5)
        assert counts["sparse_bound_iters"] == int(at + n > 8)
        assert counts["moe_picks"] == 4 * 3 * n  # four expert layers
        at += n
    if feed == "ragged_chunks":
        low, _ = build(dtype="bfloat16", ep_size=8)
        assert np.abs(paged_logits(low, params, seq, sizes, **engine)[0]
                      - want).max() > 2e-2


def _topk_case(case: str):
    """``(scores (4, 8, Sk) float32, seen, k)`` of one case of
    ``test_select_topk_is_exact``: 32 query rows, one block of the
    kernel's."""
    rng = np.random.default_rng(3)
    Sk, k = 40, 9
    if case == "past_the_live_blocks":
        Sk = 512
    scores = rng.integers(0, 6, (4, 8, Sk)).astype(np.float32)
    seen = rng.random((4, 8, Sk)) < 0.6
    seen[0, 0] = False                      # a row with none seen
    seen[1, 1, 3:] = False                  # fewer than k seen
    if case == "ties":
        # Five values over 40: the k-th place lies inside a run of
        # equal scores in every row that sees more than k.
        pass
    elif case == "negative":
        scores = rng.normal(size=scores.shape).astype(np.float32) - 1.0
        scores[2, :, ::3] = scores[2, :, :1]        # ties among them
    elif case == "signed_zeros":
        # -0.0 and +0.0 are one score: the lower position goes first.
        scores = np.where(rng.random(scores.shape) < 0.5, -0.0, 0.0
                          ).astype(np.float32)
        scores[:, :, ::5] = rng.normal(size=scores[:, :, ::5].shape)
        assert np.signbit(scores[scores == 0]).any()
    elif case == "fewer_than_k_and_dead":
        seen[:, :4] = False                 # dead queries
        seen[2] &= np.arange(Sk) < 5        # fewer than k
        scores[3, 4:, :7] = -np.inf         # seen, and lowest of all
    elif case == "k_is_one":
        k = 1
    elif case == "k_is_every_position":
        k = Sk
    elif case == "past_the_live_blocks":
        # Every row sees only the first 100 positions of 512: the key
        # blocks past them are never counted, and ties straddle the
        # k-th place.
        seen &= np.arange(Sk) < 100
        k = 30
    return scores, seen, k


@pytest.mark.parametrize("case", [
    "ties", "negative", "signed_zeros", "fewer_than_k_and_dead",
    "k_is_one", "k_is_every_position", "past_the_live_blocks"])
def test_select_topk_is_exact(monkeypatch, case):
    """Against a sort by hand (``dtt_select_topk`` interpreted, the
    positions compacted out of its mask): ties, negative scores,
    ``-0.0`` beside ``+0.0``, rows with fewer than k seen or none, dead
    queries, k of 1 and of every position, and a block whose seen
    positions end before its last key blocks. Equal scores go to the
    lower position; the positions come in position order."""
    scores, seen, k = _topk_case(case)
    if case == "past_the_live_blocks":
        monkeypatch.setattr(pa, "_SELECT_LANES", 128)
    with pa.observe_forms() as took:
        positions, kept, chosen = map(np.asarray, pa.select_topk(
            jnp.asarray(scores), jnp.asarray(seen), k))
    assert took == ["select.threshold"]
    for idx in np.ndindex(*scores.shape[:2]):
        order = sorted(np.flatnonzero(seen[idx]),
                       key=lambda s: (-scores[idx][s], s))[:k]
        assert list(positions[idx][kept[idx]]) == sorted(order), idx
        assert sorted(np.flatnonzero(chosen[idx])) == sorted(order), idx
        assert kept[idx].sum() == min(k, seen[idx].sum())
        assert not positions[idx][~kept[idx]].any()
    if case in ("ties", "past_the_live_blocks"):
        # The k-th place inside a run of equal scores: the cutoff by
        # position decided it.
        s = np.where(seen, scores, -np.inf)
        edge = -np.sort(-s, axis=-1)[..., k - 1:k + 1]
        assert (edge[..., 0] == edge[..., 1])[np.isfinite(edge[..., 1])
                                              ].mean() > 0.5


def latent_case(B, S, last, ring_pages=None, seed=0, ps=4, H=4, rank=8,
                nope=8, rope=4, v=8, J=2, d=8, table_pages=16,
                scattered=False, ties=False, dtype=jnp.float32):
    """``B`` sequences whose newest query is at ``last[b]``, ``S``
    queries each, over a latent cache holding what the engine would
    have left: position ``p`` in table slot ``p``, or of a ring of
    ``ring_pages`` pages in ring slot ``p % (ring_pages * ps)``. Returns
    the call's arguments, the index pool's layer with the indexer's
    queries and weights, and the dense rows by position. ``scattered``:
    the sequences' pages dealt out of order from one pool; ``ties``:
    index keys, queries and weights whole numbers of a few values, so
    that many scores are equal."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    T = max(last) + 1
    c = jax.random.normal(ks[0], (B, T, rank), jnp.float32)
    r = jax.random.normal(ks[1], (B, T, rope), jnp.float32)
    ki = jax.random.normal(ks[2], (B, T, d), jnp.float32)
    q_nope = jax.random.normal(ks[3], (B, S, H, nope), jnp.float32)
    q_rope = jax.random.normal(ks[4], (B, S, H, rope), jnp.float32)
    w_uk = jax.random.normal(ks[5], (rank, H, nope), jnp.float32)
    w_uv = jax.random.normal(ks[6], (rank, H, v), jnp.float32)
    qi = jax.random.normal(ks[7], (B, S, J, d), jnp.float32)
    wi = jax.random.normal(ks[8], (B, S, J), jnp.float32)
    if ties:
        ki, qi, wi = (jnp.round(x) for x in (ki, qi, wi))
    P = ring_pages or table_pages
    N = B * P + 1
    pools = [np.zeros((N, ps, 128), np.float32) for _ in range(3)]
    rows = np.arange(1, N, dtype=np.int32)
    if scattered:
        rows = np.random.default_rng(seed).permutation(rows)
    rows = rows.reshape(B, P)
    for b in range(B):
        for p in range(last[b] + 1):        # later rows overwrite
            page, off = rows[b, p // ps % P], p % ps
            for pool, x in zip(pools, (c, r, ki)):
                pool[page, off, :x.shape[-1]] = np.asarray(x[b, p])
    zero = jnp.zeros((), jnp.int32)
    layers = [PoolLayout(1, width).layer(
        jnp.asarray(pool, dtype)[None], zero)
        for pool, width in zip(pools, (rank, rope, d))]
    q_pos = np.stack([np.arange(n - S + 1, n + 1) for n in last]
                     ).astype(np.int32)
    q_pos[0, 0] = -1                        # a dead query
    q_nope, q_rope, w_uk, w_uv, qi = (
        x.astype(dtype) for x in (q_nope, q_rope, w_uk, w_uv, qi))
    args = (q_nope, q_rope, layers[0], layers[1], jnp.asarray(rows),
            jnp.asarray(q_pos), w_uk, w_uv)
    return args, (qi, wi, layers[2]), (c, r, ki)


def dense_latent(args, dense, seen):
    """Latent attention written out over the dense rows where ``seen
    (B, S, T)`` holds."""
    q_nope, q_rope, _c, _r, _rows, q_pos, w_uk, w_uv = args
    c, r, _ki = dense
    k = jnp.einsum("bkr,rhn->bkhn", c, w_uk)
    scores = (jnp.einsum("bshn,bkhn->bhsk", q_nope, k)
              + jnp.einsum("bshe,bke->bhsk", q_rope, r)
              ) / (q_nope.shape[-1] + q_rope.shape[-1]) ** 0.5
    scores = jnp.where(seen[:, None], scores, -jnp.inf)
    want = jnp.einsum("bhsk,bkhv->bshv", jax.nn.softmax(scores, -1),
                      jnp.einsum("bkr,rhv->bkhv", c, w_uv))
    return jnp.where((q_pos >= 0)[:, :, None, None], want, 0.0)


@pytest.mark.parametrize("blocked", [False, True])
@pytest.mark.parametrize("S,last", [(1, (50, 9, 31)), (8, (50, 12, 33)),
                                    (3, (15, 16, 17))])
def test_latent_attention_over_a_ring(monkeypatch, S, last, blocked):
    """Latent attention over a ring of 4 pages of 4 against attention
    written out over the dense rows: one query a slot, a chunk of 8 that
    straddles the wrap, sequences shorter than the window, a dead
    query; in one pass and a block of queries at a time."""
    if blocked:
        monkeypatch.setattr(pa, "_LATENT_LOGITS_LIMIT", 1 << 8)
    window, R = 5, 4
    args, _index, dense = latent_case(3, S, last, ring_pages=R)
    with pa.observe_forms() as seen:
        got = pa.latent_attention_chunk(*args, window=window, ring=True)
    assert len(seen) == 1 and seen[0].endswith(".window")
    back = args[5][:, :, None] - jnp.arange(dense[0].shape[1])[None, None]
    want = dense_latent(args, dense, (back >= 0) & (back < window))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,topk,form", [
    (1, 8, "absorbed.sparse"),        # decode: the chosen rows read alone
    (3, 8, "absorbed.sparse"),        # speculative verify
    (8, 8, "absorbed.sparse"),        # a chunk: every query its rows
    (16, 8, "flash.sparse"),          # a wider one: the table under a mask
])
def test_sparse_equals_dense_up_to_index_topk(S, topk, form):
    """A selection of ``topk`` positions changes nothing while a
    sequence is no longer than ``topk`` (sequence 1, at 7), and attends
    exactly the chosen positions where it is longer, against attention
    written out with the selection as a mask."""
    last = (40, 7, 25) if S <= 8 else (40, 15, 25)
    args, (qi, wi, ip), dense = latent_case(3, S, last)
    select = pa.Selection(qi, wi, ip, topk)
    with pa.observe_forms() as seen:
        got = pa.latent_attention_chunk(*args, select=select)
    T = dense[0].shape[1]
    assert seen == [form, "select.threshold"]
    plain = pa.latent_attention_chunk(*args)
    back = args[5][:, :, None] - jnp.arange(T)[None, None]
    scores = jnp.einsum("bsjk,bsj->bsk", jax.nn.relu(jnp.einsum(
        "bsjd,bkd->bsjk", qi, dense[2])), wi)
    chosen = pa.select_topk(scores, back >= 0, topk)[2]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense_latent(args, dense, chosen)),
        atol=2e-5, rtol=2e-5)
    # Queries at positions under topk: the dense result; past it: not.
    short = np.asarray(args[5]) < topk
    np.testing.assert_allclose(np.asarray(got)[short],
                               np.asarray(plain)[short], atol=2e-5,
                               rtol=2e-5)
    assert np.abs(np.asarray(got)[~short]
                  - np.asarray(plain)[~short]).max() > 1e-2


def forced(monkeypatch, form):
    monkeypatch.setattr(pa, "sparse_form", lambda *a: form)


MASKED = {
    # A chunk past top-k in every sequence; the one query block is
    # padded from 16 to 32 and holds a dead query.
    "chunk": dict(S=16, last=(40, 31, 25)),
    # Sequences shorter than top-k beside longer ones.
    "fewer_than_topk_seen": dict(S=16, last=(15, 40, 17)),
    # Whole-number scores: the edge of the selection lies inside a run
    # of equal scores, which go to the lower positions.
    "tied_scores": dict(S=16, last=(40, 31, 25), ties=True),
    # Pages dealt out of order from the pool.
    "scattered_pages": dict(S=16, last=(40, 31, 25), scattered=True),
    # Blocks of 8 queries and 16 rows: the last query block is half
    # padding, and contexts of 22 and 37 end inside a key block, the
    # blocks past them skipped.
    "small_blocks": dict(S=20, last=(63, 21, 36), blocks=(8, 16)),
    # The selection made 8 queries at a time, attended at once.
    "selection_in_blocks": dict(S=16, last=(40, 31, 25), limit=1 << 10),
}


@pytest.mark.parametrize("dtype,atol", [("float32", 5e-6),
                                        ("bfloat16", 3e-2)])
@pytest.mark.parametrize("case", list(MASKED))
def test_the_masked_form_is_the_gather_form(monkeypatch, case, dtype,
                                            atol):
    """``dtt_sparse_prefill`` (interpreted) against the gather form of
    the same call and against attention written out under the
    selection's mask, as the reference's ``attend`` has it: the same
    softmax over the same rows, in float32 to the order of summation
    and in bfloat16 to the repo's band."""
    kw = dict(MASKED[case])
    S, last = kw.pop("S"), kw.pop("last")
    blocks, limit = kw.pop("blocks", None), kw.pop("limit", None)
    args, (qi, wi, ip), dense = latent_case(3, S, last,
                                            dtype=jnp.dtype(dtype), **kw)
    select = pa.Selection(qi, wi, ip, 8)
    if limit:
        monkeypatch.setattr(pa, "_LATENT_LOGITS_LIMIT", limit)
    scores = pa.index_scores(select, ip.layout.unpack(
        ip.pages(args[4]))[:, :, 0])
    seen = pa._visible(args[5], jnp.arange(64)[None], None, None)
    chosen = pa.select_topk(scores, seen, 8)[2]
    if blocks:
        masked = pa._sparse_flash_attention(
            *args[:6], chosen.astype(jnp.int8), *args[6:], blocks=blocks)
    else:
        with pa.observe_forms() as took:
            masked = pa.latent_attention_chunk(*args, select=select)
        assert took == ["flash.sparse", "select.threshold"]
    forced(monkeypatch, "absorbed")
    with pa.observe_forms() as took:
        gathered = pa.latent_attention_chunk(*args, select=select)
    assert took == ["absorbed.sparse", "select.threshold"]
    T = dense[0].shape[1]
    want = dense_latent(args, dense, chosen[:, :, :T])
    for got in (gathered, want):
        np.testing.assert_allclose(
            np.asarray(masked, np.float32), np.asarray(got, np.float32),
            atol=atol * max(1.0, float(jnp.abs(want).max())), rtol=atol)
    # A dead query gives zeros; the case is not an empty one.
    assert not np.asarray(masked, np.float32)[0, 0].any()
    kept = np.asarray(chosen).sum(-1)
    assert kept.max() == 8 and (kept[np.asarray(args[5]) >= 8] == 8).all()
    if case == "tied_scores":
        s = np.where(np.asarray(seen), np.asarray(scores), -np.inf)
        edge = np.sort(s, axis=-1)[..., -9:-7]       # 9th and 8th largest
        assert (edge[..., 0] == edge[..., 1])[np.isfinite(
            edge[..., 0])].mean() > 0.3
    if case == "fewer_than_topk_seen":
        np.testing.assert_allclose(
            np.asarray(masked, np.float32)[np.asarray(args[5]) < 8],
            np.asarray(pa.latent_attention_chunk(*args), np.float32)[
                np.asarray(args[5]) < 8], atol=atol * 10, rtol=atol)


DOTS3 = dict(rank=512, nope=128, rope=64, v=128)


@pytest.mark.parametrize("B,S,pages,form", [
    (32, 1, 1024, "absorbed.sparse"),    # the resident decode program
    (1, 1024, 1024, "flash.sparse"),     # the prefill lane
    (1, 16384, 1024, "flash.sparse"),    # a teacher-forced pass
    (1, 4, 1024, "absorbed.sparse"),     # a speculative verify
    # The published 524,288 positions: dense arithmetic over the table
    # loses to 2,048 gathered rows a query.
    (1, 1024, 32768, "absorbed.sparse"),
], ids=["decode_32x1", "prefill_1x1024", "forced_1x16384", "spec_1x4",
        "table_524288"])
def test_the_form_under_a_selection_follows_the_shapes(B, S, pages, form):
    """``sparse_form`` at the shapes of ``dots3-note-ep8``'s programs,
    as ``observe_forms`` reports it when a call is traced (no program
    is compiled): static shapes alone, no option."""
    f32, H = jnp.float32, 128
    N = B * pages + 1

    def layer(width):
        lay = PoolLayout(1, width)
        return lay.layer(jax.ShapeDtypeStruct(lay.shape(1, N, 16), f32),
                         0)

    def call(qn, qr, c, r, t, qp, uk, uv, iq, iw, ip):
        return pa.latent_attention_chunk(
            qn, qr, c, r, t, qp, uk, uv,
            select=pa.Selection(iq, iw, ip, 2048))

    d = DOTS3
    shapes = [(B, S, H, d["nope"]), (B, S, H, d["rope"])]
    with pa.observe_forms() as took:
        out = jax.eval_shape(
            call, *(jax.ShapeDtypeStruct(x, f32) for x in shapes),
            layer(d["rank"]), layer(d["rope"]),
            jax.ShapeDtypeStruct((B, pages), jnp.int32),
            jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((d["rank"], H, d["nope"]), f32),
            jax.ShapeDtypeStruct((d["rank"], H, d["v"]), f32),
            jax.ShapeDtypeStruct((B, S, 64, 128), f32),
            jax.ShapeDtypeStruct((B, S, 64), f32), layer(128))
    assert took == [form, "select.threshold"]
    assert out.shape == (B, S, H, d["v"])
    assert pa.sparse_form((B, S, H), pages * 16, 2048,
                          tuple(d.values())) == form.split(".")[0]


def cache(**over):
    return PagedKVCache(PagedCacheConfig(**{**dict(
        n_layers=5, n_kv_heads=1, head_dim=512, v_head_dim=64,
        kind="latent", page_size=16, num_pages=40, max_seq_len=256,
        window=513, window_layers=(2, 3, 4), max_write=64, slots=2,
        dtype="bfloat16", block="SparseLatentBlock",
        window_head_dim=1024, window_v_head_dim=64, index_dim=128,
        index_topk=2048), **over}))


def test_the_cache_has_layouts_a_kind_and_an_index_pool():
    c = cache()
    ring = -(-(513 + 64) // 16)
    assert c.cfg.ring_pages == min(16, ring) == 16
    kp, vp = c.k_pages, c.v_pages
    assert kp.full.shape == (1, 2, 40, 16, 512)
    assert kp.ring.shape == (1, 3, 2 * 16 + 1, 16, 1024)
    assert kp.index.shape == (1, 2, 40, 16, 128)
    assert vp.full.shape == (1, 2, 40, 16, 128) and vp.index is None
    assert vp.ring.shape == (1, 3, 33, 16, 128)
    plan = PagedKVCache.plan(c.cfg)
    assert plan.of("global")[0].width == 512
    assert plan.of("window")[0].width == 1024
    assert plan.index.width == 128 and plan.index_topk == 2048
    # Bytes a token: each kind's layers at that kind's widths, and the
    # index key of every global layer (bfloat16).
    assert c.cfg.kv_bytes_per_token() == 2 * (
        2 * (512 + 64 + 128) + 3 * (1024 + 64))
    pools = c.pools()
    assert [p["kind"] for p in pools] == ["global", "window", "index"]
    assert [p["row_lanes"] for p in pools] == [[512, 128], [1024, 128],
                                               [128]]
    assert [p["row_bytes"] for p in pools] == [1152, 2176, 256]
    assert pools[2]["pages"] == pools[0]["pages"] == 40
    foot = c.footprint()
    assert foot["pool_bytes_tiled"] == sum(p["bytes"] for p in pools)
    assert foot["pool_bytes"] == 16 * (
        40 * 2 * (1152 + 256) + 33 * 3 * 2176)
    assert len(foot["pool_shapes"]) == 5
    # One allocator a kind still: a page of the table is a page of the
    # index pool, and freeing returns both kinds.
    c.join("a")
    assert c.ensure("a", 100)
    assert c.pages_used == 7 + 7 and c.pages_total == 39 + 32
    c.free("a")
    assert c.pages_used == 0
    c.k_pages.delete()
    c.v_pages.delete()
    assert all(a.is_deleted() for a in jax.tree.leaves((kp, vp)))


def test_widths_that_do_not_belong_are_refused():
    with pytest.raises(ValueError, match="no window_layers"):
        PagedCacheConfig(n_layers=2, n_kv_heads=1, head_dim=8,
                         window_head_dim=16)
    with pytest.raises(ValueError, match="come together"):
        PagedCacheConfig(n_layers=2, n_kv_heads=1, head_dim=8,
                         index_dim=16)


def test_an_index_pool_without_window_layers():
    """All layers full: the index pool beside the one table, no ring."""
    model, params = build(layer_types=(FULL,) * 5, ep_size=8)
    eng = Engine(model, params, EngineConfig(**ENGINE))
    kp, vp = eng.cache.k_pages, eng.cache.v_pages
    assert isinstance(kp, Pools) and kp.ring is None
    assert kp.index.shape == kp.full.shape == (1, 5, 60, 4, 128)
    assert not isinstance(vp, Pools)
    assert eng._counters[-1] == "sparse_bound_iters"
    assert "window_bound_iters" not in eng._counters
    prompt = np.arange(3, 30, dtype=np.int32)
    got = np.asarray(eng.generate(prompt, 6))
    seq = np.concatenate([prompt, got])
    rows = np.asarray(jax.jit(model.apply)(params, jnp.asarray(seq)[None])
                      )[0, len(prompt) - 1:-1]
    assert all(float(row.max() - row[tok]) < 1e-4
               for row, tok in zip(rows, got))


@pytest.mark.parametrize("types", [TYPES, (FULL,) * 5])
def test_what_moves_pages_by_one_table_is_refused_by_name(types):
    model, params = build(layer_types=types, ep_size=8)
    with pytest.raises(NotImplementedError,
                       match="prefix_sharing=False.*SparseLatentBlock"):
        Engine(model, params, EngineConfig(**{**ENGINE,
                                              "prefix_sharing": True}))
    eng = Engine(model, params, EngineConfig(**ENGINE))
    for move in (lambda: eng.cache.read_pages([0], [1]),
                 lambda: eng.cache.attach("x", [1], 4),
                 lambda: eng.cache.register_prefix("x", [1, 2, 3, 4])):
        with pytest.raises(NotImplementedError,
                           match="SparseLatentBlock"):
            move()


def test_the_reference_holds_the_configuration_files_numbers():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "dots3-note-ep8.json")) as f:
        config = json.load(f)
    module = common.load_reference(config)
    kw = config["program"]["kwargs"]
    n = config["num_hidden_layers"]
    assert module.LAYER_TYPES == tuple(config["layer_types"][:n]) \
        == tuple(kw["layer_types"])
    for name, key in (("FIRST_K_DENSE", "first_k_dense_replace"),
                      ("QK_NOPE", "qk_nope_head_dim"),
                      ("QK_ROPE", "qk_rope_head_dim"),
                      ("ROPE_THETA", "rope_theta"),
                      ("INDEX_TOPK", "index_topk"),
                      ("SWA_N_HEAD", "swa_num_attention_heads"),
                      ("SWA_QK_NOPE", "swa_qk_nope_head_dim"),
                      ("SWA_QK_ROPE", "swa_qk_rope_head_dim"),
                      ("SWA_ROPE_THETA", "swa_rope_theta"),
                      ("WINDOW", "sliding_window_size"),
                      ("RESCALE", "apply_mla_qkv_lora_rescale"),
                      ("RMS_NORM_EPS", "rms_norm_eps"),
                      ("NUM_EXPERTS_PER_TOK", "num_experts_per_tok"),
                      ("ROUTED_SCALING", "routed_scaling_factor")):
        assert getattr(module, name) == config[key], name
    assert module.EP_RANK == kw["ep_rank"]
    assert config["n_routed_experts"] == kw["n_routed_experts"] \
        // kw["ep_size"]
    assert config["vocab_size"] == kw["vocab_size"] \
        == config["program"]["token_vocab"]
    # The program is built at the published widths.
    for key, arg in (("hidden_size", "d_model"),
                     ("num_attention_heads", "n_heads"),
                     ("q_lora_rank", "q_lora_rank"),
                     ("kv_lora_rank", "kv_lora_rank"),
                     ("v_head_dim", "v_head_dim"),
                     ("index_n_heads", "index_n_heads"),
                     ("index_head_dim", "index_head_dim"),
                     ("swa_q_lora_rank", "swa_q_lora_rank"),
                     ("swa_kv_lora_rank", "swa_kv_lora_rank"),
                     ("swa_v_head_dim", "swa_v_head_dim"),
                     ("intermediate_size", "d_ff"),
                     ("moe_intermediate_size", "moe_d_ff"),
                     ("n_shared_experts", "n_shared_experts"),
                     ("num_experts_per_tok", "moe_top_k")):
        assert config[key] == kw[arg], key
    # The yaml for generate.py and the server says the same.
    import yaml
    with open(os.path.join(ROOT, "conf", "model",
                           "dots3_note_ep8.yaml")) as f:
        assert yaml.safe_load(f)["kwargs"] == kw
    # The engine's three pools at the file's geometry: the issue's
    # arithmetic (full latent 1.34 GB, index keys 0.27 GB, rings
    # 0.34 GB; 97 pages a ring).
    ccfg = E._cache_config(build_model(
        config["program"]["build_model"], **kw).serving_block(),
        EngineConfig(**config["serving"]["engine"]), None, "bfloat16")
    assert ccfg.ring_pages == 97 and ccfg.window_num_pages == 32 * 97 + 1
    assert ccfg.num_pages == 32 * ccfg.pages_per_seq + 1
    assert ccfg.kv_bytes_per_token() == 2 * (
        2 * (512 + 64 + 128) + 3 * (1024 + 64))
    shapes = PagedKVCache.pool_shapes(ccfg)
    assert shapes[0] == Pools((1, 2, 32769, 16, 512),
                              (1, 3, 3105, 16, 1024),
                              (1, 2, 32769, 16, 128))
    assert shapes[1] == Pools((1, 2, 32769, 16, 128),
                              (1, 3, 3105, 16, 128), None)
    import math
    total = 2 * sum(math.prod(s) for pools in shapes for s in pools
                    if s is not None)
    assert round(total / 1e9, 2) == 1.95


def test_a_stream_that_delivers_is_not_timed_out():
    """``generate_stream``'s ``timeout`` bounds the wait for the NEXT
    token, not the whole request: ten tokens a quarter second apart
    outlast a timeout of one second, and an engine that delivers
    nothing for longer than that still frees the handler."""
    import time

    from distributed_training_tpu.serving.server import ServingServer

    model = build_model("transformer", dtype="float32",
                        attention_impl="naive", vocab_size=64, d_model=32,
                        n_layers=2, n_heads=4, max_seq_len=32)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), EngineConfig(
        max_batch=2, page_size=4, num_pages=17, max_seq_len=32,
        prefill_chunk=8))
    eng.warmup()
    step, pause = eng.step, [0.25]

    def slow_step():
        time.sleep(pause[0])
        return step()
    eng.step = slow_step
    srv = ServingServer(eng, port=0)
    assert srv.start() is not None
    try:
        t0 = time.monotonic()
        items = list(srv.generate_stream(
            np.asarray([5, 7, 11], np.int32), 10, timeout=1.0))
        assert time.monotonic() - t0 > 1.5
        assert len(items) == 11 and items[-1]["done"]
        pause[0] = 2.5
        with pytest.raises(TimeoutError, match="mid-stream"):
            list(srv.generate_stream(np.asarray([5, 7], np.int32), 4,
                                     timeout=1.0))
    finally:
        pause[0] = 0.0
        srv.stop()
