"""Serving subsystem: paged KV cache, continuous batching, disagg.

The correctness contracts the subsystem ships on:

- paged-attention decode == dense full-context attention (exact on
  the CPU mesh) — both at the op level and end-to-end (engine greedy
  tokens vs re-running the full context per token);
- page alloc/free accounting never leaks under randomized join/evict;
- a sequence's output is independent of which other sequences share
  the continuous batch;
- join/evict never recompile the engine's programs;
- the metrics endpoint exports the pinned ``dtt_serving_*`` schema;
- export provenance gates the weight store (stamped plan fingerprint
  must match the committed plan; legacy artifacts warn);
- the disaggregated two-plan pipeline decodes token-for-token what
  the co-located engine decodes;
- the committed decode plan's program audits reshard-clean
  (SPMD001 == 0, the serving_decode_planned pin).
"""

import dataclasses
import functools
import json
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from distributed_training_tpu.models.transformer import (  # noqa: E402
    Transformer,
    TransformerConfig,
)
from distributed_training_tpu.serving.engine import (  # noqa: E402
    Engine,
    EngineConfig,
    Request,
)
from distributed_training_tpu.serving.kv_cache import (  # noqa: E402
    PagedCacheConfig,
    PagedKVCache,
    PoolLayout,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, max_seq_len=128, dtype="float32",
        param_dtype="float32", pos_encoding="rope",
        tie_embeddings=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _engine(model, params, **over) -> Engine:
    kw = dict(max_batch=4, page_size=8, num_pages=64, max_seq_len=64,
              prefill_chunk=8)
    kw.update(over)
    return Engine(model, params, EngineConfig(**kw))


def _full_context_greedy(model, params, prompt, n):
    """The old/original decode discipline: re-run the FULL context
    through model.apply for every token, argmax — the reference the
    paged path must match token-for-token."""
    ids = list(int(t) for t in prompt)
    out = []
    for _ in range(n):
        logits, _aux = model.apply(params,
                                   jnp.asarray([ids], jnp.int32))
        t = int(jnp.argmax(logits[0, -1]))
        out.append(t)
        ids.append(t)
    return out


# ---------------------------------------------------------------------------
# op-level parity
# ---------------------------------------------------------------------------


def _as_layer(pages, dtype=jnp.float32):
    """Head-major ``(Hkv, N, ps, hd)`` keys or values as paged
    attention is handed them: the middle layer of a pool the cache
    would store (``PoolLayout``), the layers around it NaN, so a read
    of any layer but the one named shows."""
    Hkv, _N, _ps, hd = pages.shape
    layout = PoolLayout(Hkv, hd)
    stored = layout.stored(np.asarray(pages).transpose(1, 2, 0, 3))
    pool = np.full((3,) + stored.shape, np.nan, np.float32)
    pool[1] = stored
    return layout.layer(jnp.asarray(pool, dtype), jnp.int32(1))


def test_paged_attention_matches_dense_reference():
    """paged_attention_chunk at one query a sequence (the decode
    program's call) over scattered pages, ragged lengths == naive
    attention over the equivalent dense K/V, exactly (same fp32
    softmax path)."""
    from distributed_training_tpu.ops.attention import (
        _naive_attention)
    from distributed_training_tpu.ops.paged_attention import (
        paged_attention_chunk)

    rng = np.random.default_rng(0)
    B, H, Hkv, hd, ps, P = 3, 4, 2, 16, 8, 4
    N = 1 + B * P  # scratch + enough pages
    lengths = np.asarray([5, 17, 32], np.int32)  # ragged
    k_pages = np.zeros((Hkv, N, ps, hd), np.float32)
    v_pages = np.zeros((Hkv, N, ps, hd), np.float32)
    tables = np.zeros((B, P), np.int32)
    dense_k = rng.standard_normal((B, P * ps, Hkv, hd)).astype(
        np.float32)
    dense_v = rng.standard_normal((B, P * ps, Hkv, hd)).astype(
        np.float32)
    # Scatter each sequence's positions into DELIBERATELY shuffled
    # physical pages (the non-contiguity is the whole point).
    perm = rng.permutation(np.arange(1, N))
    pi = 0
    for b in range(B):
        for j in range(-(-int(lengths[b]) // ps)):
            pid = int(perm[pi]); pi += 1
            tables[b, j] = pid
            chunk = slice(j * ps, (j + 1) * ps)
            k_pages[:, pid] = dense_k[b, chunk].transpose(1, 0, 2)
            v_pages[:, pid] = dense_v[b, chunk].transpose(1, 0, 2)
    q = rng.standard_normal((B, H, hd)).astype(np.float32)
    got = paged_attention_chunk(
        jnp.asarray(q[:, None]), _as_layer(k_pages),
        _as_layer(v_pages), jnp.asarray(tables),
        jnp.asarray(lengths - 1)[:, None])[:, 0]
    for b in range(B):
        n = int(lengths[b])
        ref = _naive_attention(
            jnp.asarray(q[b][None, None]),           # (1,1,H,hd)
            jnp.asarray(dense_k[b, :n][None]),
            jnp.asarray(dense_v[b, :n][None]), causal=True)
        np.testing.assert_allclose(np.asarray(got[b]),
                                   np.asarray(ref[0, 0]),
                                   rtol=1e-5, atol=1e-5)


def _paged_chunk_case(S, group, dtype, seed=0):
    """A pool as the engine deals it, with every hazard of the pool
    form in it at once: ragged lengths over shuffled physical pages,
    a first page SHARED by sequences 0 and 1 (copy-on-write sharing
    before the write), table tails on scratch page 0 while page 0
    holds large finite garbage, a padding query in a live row and one
    all-inactive row. Returns the call's arguments and, per sequence,
    the dense K/V the tables describe."""
    rng = np.random.default_rng(seed)
    Hkv, hd, ps = 2, 16, 8
    H = Hkv * group
    B = 4
    P = -(-(S + 40) // ps)
    N = 1 + B * P
    # Last query's position + 1: ragged, one ending inside the shared
    # first page's successor, one filling its table.
    lengths = np.asarray([S + 3, S + 17, P * ps, S + 9], np.int64)
    k_pages = np.zeros((Hkv, N, ps, hd), np.float32)
    v_pages = np.zeros((Hkv, N, ps, hd), np.float32)
    k_pages[:, 0] = 1e4
    v_pages[:, 0] = -1e4
    tables = np.zeros((B, P), np.int32)
    dense_k = rng.standard_normal((B, P * ps, Hkv, hd)).astype(
        np.float32)
    dense_v = rng.standard_normal((B, P * ps, Hkv, hd)).astype(
        np.float32)
    dense_k[1, :ps] = dense_k[0, :ps]
    dense_v[1, :ps] = dense_v[0, :ps]
    perm = iter(rng.permutation(np.arange(1, N)))
    for b in range(B):
        for j in range(-(-int(lengths[b]) // ps)):
            pid = tables[0, 0] if (b, j) == (1, 0) else int(next(perm))
            tables[b, j] = pid
            chunk = slice(j * ps, (j + 1) * ps)
            k_pages[:, pid] = dense_k[b, chunk].transpose(1, 0, 2)
            v_pages[:, pid] = dense_v[b, chunk].transpose(1, 0, 2)
    q_pos = (lengths[:, None] - S + np.arange(S)[None, :]).astype(
        np.int32)
    q_pos[3] = -1                       # an inactive slot
    if S > 1:
        q_pos[0, -1] = -1               # a padding query
    q = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    args = (jnp.asarray(q, dtype), _as_layer(k_pages, dtype),
            _as_layer(v_pages, dtype), jnp.asarray(tables),
            jnp.asarray(q_pos))
    return args, dense_k, dense_v


def _dense_reference(args, dense_k, dense_v):
    """Naive attention in float64 over the dense K/V, from the inputs
    as the dtype rounded them: query (b, s) over logical positions
    ``<= q_pos[b, s]``, zeros where ``q_pos < 0``."""
    q, k_pages, _v, _tables, q_pos = args
    q = np.asarray(q.astype(jnp.float32), np.float64)
    q_pos = np.asarray(q_pos)
    as_dtype = lambda x: np.asarray(            # noqa: E731
        jnp.asarray(x, k_pages.dtype).astype(jnp.float32), np.float64)
    dense_k, dense_v = as_dtype(dense_k), as_dtype(dense_v)
    B, S, H, hd = q.shape
    group = H // dense_k.shape[2]
    out = np.zeros((B, S, H, hd))
    for b, s, h in np.ndindex(B, S, H):
        n = int(q_pos[b, s]) + 1
        if n <= 0:
            continue
        logits = dense_k[b, :n, h // group] @ q[b, s, h] * hd ** -0.5
        w = np.exp(logits - logits.max())
        out[b, s, h] = (w / w.sum()) @ dense_v[b, :n, h // group]
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("S", [1, 4, 128],
                         ids=["decode", "spec4", "prefill128"])
def test_paged_chunk_forms_match_dense_reference(S, group, dtype):
    """Both forms of ``paged_attention_chunk`` are the same attention:
    each against naive dense attention and against the other, at the
    decode, speculative and prefill-chunk shapes, with and without
    GQA. Each form is reached through its private function, as the
    rule would pick either on a pool this small."""
    from distributed_training_tpu.ops import paged_attention as pa

    args, dense_k, dense_v = _paged_chunk_case(S, group,
                                               jnp.dtype(dtype))
    want = _dense_reference(args, dense_k, dense_v)
    got = {form: np.asarray(jax.jit(fn)(*args).astype(jnp.float32))
           for form, fn in (("gather", pa._gather_attention),
                            ("pool", pa._pool_attention))}
    tol = 1e-5 if dtype == "float32" else 2e-2
    q_pos = np.asarray(args[4])
    for form, out in got.items():
        assert np.isfinite(out).all(), form
        # Exact zeros, not small numbers: the garbage on page 0 is
        # 1e4 and a leak of it would be anything but.
        assert not out[q_pos < 0].any(), form
        np.testing.assert_allclose(out, want, rtol=tol, atol=tol,
                                   err_msg=form)
    np.testing.assert_allclose(got["pool"], got["gather"], rtol=tol,
                               atol=tol)
    public = pa.paged_attention_chunk(*args).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(public), want, rtol=tol,
                               atol=tol)


def _ragged_case(S, H, Hkv, hd, P, tops, dtype, ps=4, ring=False,
                 dead=(), seed=0):
    """One layer's call for the ragged form against the gather form:
    a pool whose EVERY slot holds something (so a mask that lets one
    too many through shows), scratch page 0 large finite garbage, a
    sequence the ``S`` queries up to position ``tops[b]`` (negative:
    a dead sequence), ``dead`` queries ``(b, s)`` among the live. A
    table row holds the pages of its positions and its unused tail is
    scratch page 0; a ring row holds all ``P`` of its pages."""
    rng = np.random.default_rng(seed)
    B = len(tops)
    N = B * P + 1
    kp, vp = (rng.standard_normal((Hkv, N, ps, hd)).astype(np.float32)
              for _ in range(2))
    kp[:, 0], vp[:, 0] = 1e4, -1e4
    tables = (rng.permutation(N - 1)[:B * P] + 1).reshape(B, P)
    q_pos = np.asarray([[top - S + 1 + s if top >= 0 else -1
                         for s in range(S)] for top in tops], np.int32)
    for b, s in dead:
        q_pos[b, s] = -1
    if not ring:
        for b, top in enumerate(tops):
            tables[b, top // ps + 1 if top >= 0 else 0:] = 0
    q = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    return (jnp.asarray(q, dtype), _as_layer(kp, dtype),
            _as_layer(vp, dtype), jnp.asarray(tables, jnp.int32),
            jnp.asarray(q_pos))


# name: (shape, window, ring, dead queries). Pages of 4 in tables and
# rings of 8: lengths 1, one under / at / one over a page boundary, a
# whole table, a dead sequence; a ring of 32 slots for a window of 20
# whose query sits at ``ring + 3`` and later turns.
_LENGTHS = [0, 2, 3, 4, 31, -1]
_RAGGED = {
    "table": (dict(S=1, H=4, Hkv=2, hd=16, P=8, tops=_LENGTHS),
              None, False, ()),
    "table_window": (dict(S=1, H=4, Hkv=2, hd=16, P=8, tops=_LENGTHS),
                     6, False, ()),
    "table_spec4": (dict(S=4, H=4, Hkv=2, hd=16, P=8,
                         tops=[3, 4, 5, 18, 31, -1]), None, False,
                    ((0, 0), (3, 3))),
    "table_window_spec4": (dict(S=4, H=4, Hkv=2, hd=16, P=8,
                                tops=[3, 4, 5, 18, 31, -1]), 6, False,
                           ((3, 0),)),
    "ring_turned": (dict(S=1, H=4, Hkv=2, hd=16, P=8,
                         tops=[35, 0, 18, 19, 20, 167, -1]), 20, True,
                    ()),
    "ring_spec4": (dict(S=4, H=4, Hkv=2, hd=16, P=8,
                        tops=[35, 3, 21, 34, 129, -1]), 20, True,
                   ((0, 1), (4, 0))),
    # smallthinker's heads: 7 query heads a kv head of 128, a tile each.
    "gqa7_heads_of_128": (dict(S=1, H=14, Hkv=2, hd=128, P=8,
                               tops=[0, 4, 30]), None, False, ()),
    "gqa7_heads_of_128_ring": (dict(S=4, H=14, Hkv=2, hd=128, P=8,
                                    tops=[35, 7, 99]), 20, True, ()),
    # gpt2's: heads of 64 two a tile, the third kv head alone in its.
    "two_heads_a_tile": (dict(S=1, H=3, Hkv=3, hd=64, P=8,
                              tops=[3, 4, 31, -1]), None, False, ()),
    "two_heads_a_tile_spec4": (dict(S=4, H=6, Hkv=3, hd=64, P=8,
                                    tops=[3, 17, 31]), 9, False,
                               ((1, 3),)),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(_RAGGED))
def test_ragged_form_agrees_with_the_gather_form(name, dtype):
    """The ragged form's kernel (``dtt_paged_decode``, interpreted
    here: a sequence's live pages walked where they lie in the pool, a
    DMA a page, the softmax online) against the gather form over the
    same pool under ``_visible``'s mask: float32 to rounding, bfloat16
    to the forms' band; zeros where a query is dead, never NaN, though
    the layers around the one named are NaN and scratch page 0 is
    1e4. Two pages a step, so that runs end inside a step, at its end
    and one page into the next; then the pages the shapes would get."""
    from distributed_training_tpu.ops import paged_attention as pa

    shape, window, ring, dead = _RAGGED[name]
    args = _ragged_case(**shape, dtype=jnp.dtype(dtype), ring=ring,
                        dead=dead)
    want = pa._gather_attention(*args, window=window, ring=ring)
    tol = 2e-4 if dtype == "float32" else 2e-2
    q_pos = np.asarray(args[4])
    for pages in (2, None):
        got = jax.jit(lambda *a, pages=pages: pa._ragged_attention(
            *a, window=window, ring=ring, pages=pages))(*args)
        assert got.shape == want.shape and got.dtype == want.dtype
        got = np.asarray(got.astype(jnp.float32))
        assert np.isfinite(got).all(), pages
        np.testing.assert_allclose(
            got, np.asarray(want.astype(jnp.float32)), atol=tol,
            rtol=tol, err_msg=str(pages))
        assert not got[q_pos < 0].any()
        assert np.abs(got[q_pos >= 0]).max() > 0.1


# name: (q (B, S, H, hd), pool (Hkv, N, ps, hd), P), the form PERF.md
# section 6 (PR 37; PR 29 before the ragged form) records as the faster
# on the chip: the thirteen shapes of benchmarks/paged_form_table.py,
# then its decode rows (smallthinker-21b-ep4's table and ring, gpt2-xl's
# resident decode and speculative verify) and two prompt chunks, which
# are never offered the ragged form.
_XL, _SMALL = (25, 385, 16, 64), (12, 3073, 16, 64)
_THINKER = (32, 1, 28, 128)
_CALIBRATION = {
    "xl.resident_16x1": ((16, 1, 25, 64), _XL, 64, "ragged"),
    "xl.prefill_batch_4x128": ((4, 128, 25, 64), _XL, 64, "gather"),
    "xl.prefill_cont_1x128": ((1, 128, 25, 64), _XL, 64, "gather"),
    "xl.spec_16x4": ((16, 4, 25, 64), _XL, 64, "ragged"),
    "small.resident_64x1": ((64, 1, 12, 64), _SMALL, 64, "ragged"),
    "xl.16x8": ((16, 8, 25, 64), _XL, 64, "pool"),
    "xl.16x16": ((16, 16, 25, 64), _XL, 64, "gather"),
    "xl.16x32": ((16, 32, 25, 64), _XL, 64, "gather"),
    "xl.4x32": ((4, 32, 25, 64), _XL, 64, "gather"),
    "small.spec_64x4": ((64, 4, 12, 64), _SMALL, 64, "ragged"),
    "small.prefill_batch_8x128": ((8, 128, 12, 64), _SMALL, 64,
                                  "gather"),
    "small.16x1": ((16, 1, 12, 64), _SMALL, 64, "ragged"),
    "xl.gqa_16x1": ((16, 1, 25, 64), (5, 385, 16, 64), 64, "ragged"),
    "thinker.table_32x1": (_THINKER, (4, 32769, 16, 128), 1024,
                           "ragged"),
    "thinker.ring_32x1": (_THINKER, (4, 10241, 16, 128), 320, "ragged"),
    "thinker.table_spec_32x4": ((32, 4, 28, 128), (4, 32769, 16, 128),
                                1024, "ragged"),
    "thinker.table_1x1024": ((1, 1024, 28, 128), (4, 32769, 16, 128),
                             1024, "gather"),
    "thinker.ring_1x1024": ((1, 1024, 28, 128), (4, 10241, 16, 128),
                            320, "gather"),
}


@pytest.mark.parametrize("name", sorted(_CALIBRATION))
def test_chunk_form_rule_at_calibration_shapes(name):
    """The rule alone: the shapes it was calibrated on give the forms
    the chip found faster (PERF.md section 6, PR 37 and PR 29)."""
    from distributed_training_tpu.ops.paged_attention import (
        chunk_form)

    q_shape, pool_shape, P, want = _CALIBRATION[name]
    assert chunk_form(q_shape, pool_shape, (q_shape[0], P), 2) == want


@pytest.mark.parametrize("name", ["xl.resident_16x1", "xl.spec_16x4",
                                  "small.16x1", "thinker.table_32x1",
                                  "thinker.ring_32x1"])
def test_chunk_form_keeps_a_sharded_pool_off_the_kernel(name):
    """A pool whose heads are sharded is not offered the ragged form
    (one chip's kernel cannot read it): the rule then gives what it
    gave before the form was there, PR 29's table."""
    from distributed_training_tpu.ops.paged_attention import (
        chunk_form)

    q_shape, pool_shape, P, _ragged = _CALIBRATION[name]
    want = {"xl.resident_16x1": "pool", "xl.spec_16x4": "pool",
            "small.16x1": "gather", "thinker.table_32x1": "gather",
            "thinker.ring_32x1": "gather"}[name]
    assert chunk_form(q_shape, pool_shape, (q_shape[0], P), 2,
                      ragged=False) == want


def test_observe_forms_sees_the_form_when_traced_not_when_run():
    """``observe_forms`` collects at trace time: a cached program's
    second call is seen by nobody."""
    from distributed_training_tpu.ops import paged_attention as pa

    args, _k, _v = _paged_chunk_case(1, 1, jnp.float32)
    want = pa.chunk_form(args[0].shape, pa._held(args[1]),
                         args[3].shape, 4)
    fn = jax.jit(pa.paged_attention_chunk)
    with pa.observe_forms() as seen:
        fn(*args)
    assert seen == [want]
    with pa.observe_forms() as again:
        fn(*args)
    assert again == []
    assert pa._observers == []


# ---------------------------------------------------------------------------
# allocator accounting
# ---------------------------------------------------------------------------


def test_page_accounting_never_leaks_under_random_join_evict():
    cfg = PagedCacheConfig(n_layers=2, n_kv_heads=2, head_dim=16,
                           page_size=8, num_pages=32, max_seq_len=64)
    cache = PagedKVCache(cfg)
    rng = np.random.default_rng(7)
    live: dict[int, int] = {}
    next_id = 0
    for _ in range(500):
        total_pages = sum(-(-n // cfg.page_size)
                          for n in live.values() if n)
        assert cache.pages_used == total_pages
        assert cache.pages_used + len(cache._free) == \
            cfg.usable_pages
        op = rng.integers(0, 3)
        if op == 0 and len(live) < 8:
            cache.join(next_id)
            live[next_id] = 0
            next_id += 1
        elif op == 1 and live:
            sid = int(rng.choice(list(live)))
            want = min(live[sid] + int(rng.integers(1, 20)),
                       cfg.max_seq_len)
            if cache.ensure(sid, want):
                cache.advance(sid, want - live[sid])
                live[sid] = want
        elif op == 2 and live:
            sid = int(rng.choice(list(live)))
            cache.free(sid)
            del live[sid]
    for sid in list(live):
        cache.free(sid)
    assert cache.pages_used == 0
    assert len(cache._free) == cfg.usable_pages


def test_pool_exhaustion_is_backpressure_not_corruption(tiny_model):
    """A pool too small for every request stalls admission (requests
    queue) but still drains correctly as pages free up."""
    model, params = tiny_model
    # 9 usable pages: at 8-token pages and 24-token requests, two
    # sequences at full length need 8 pages — a third must wait.
    eng = _engine(model, params, num_pages=10, max_batch=4)
    prompts = [np.arange(3 + i, dtype=np.int32) % 256
               for i in range(5)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=12))
    eng.run_until_drained(max_steps=2000)
    assert len(eng.completed) == 5
    assert eng.cache.pages_used == 0
    solo = _engine(model, params, max_batch=1)
    for i, p in enumerate(prompts):
        assert solo.generate(p, 12) == next(
            r["tokens"] for r in eng.completed if r["id"] == f"r{i}")


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------


# The widths the pool's stored layout (kv_cache.PoolLayout) has to
# serve, by what they do to a token's row of 128-lane tiles.
_LAYOUT_MODELS = {
    # 2 kv heads of 16 under 4 query heads: a quarter of one tile.
    "tiny_gqa16": dict(d_model=64, n_heads=4, n_kv_heads=2,
                       dtype="float32"),
    # 3 heads of 64: two tiles, the last half empty (gpt2-xl's 25).
    "odd64": dict(d_model=192, n_heads=3, n_kv_heads=3,
                  dtype="float32"),
    "odd64_bf16": dict(d_model=192, n_heads=3, n_kv_heads=3,
                       dtype="bfloat16"),
    # 4 heads of 64: two full tiles (gpt2-small's 12).
    "even64_bf16": dict(d_model=256, n_heads=4, n_kv_heads=4,
                        dtype="bfloat16"),
    # 3 kv heads of 64 under 6 query heads: tile-mates AND a group.
    "gqa64": dict(d_model=384, n_heads=6, n_kv_heads=3,
                  dtype="float32"),
    # Heads of 128: a head is a tile, nothing is packed.
    "h128_bf16": dict(d_model=256, n_heads=2, n_kv_heads=2,
                      dtype="bfloat16"),
}
_LAYOUT_CADENCES = {
    "plain": dict(),
    "spec4": dict(spec_k=4),
    "resident8": dict(resident_k=8),
    # A categorical draw over ONE candidate is the argmax: the sampled
    # per-launch path, held to the same greedy reference.
    "sampled_top1": dict(temperature=0.7, top_k=1),
}


@functools.lru_cache(maxsize=None)
def _layout_model(shape):
    cfg = TransformerConfig(
        vocab_size=256, n_layers=2, max_seq_len=128,
        param_dtype="float32", pos_encoding="rope",
        tie_embeddings=False, **_LAYOUT_MODELS[shape])
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # The dense reference: the same weights through ``model.apply`` in
    # float32, the full context at once.
    dense = Transformer(dataclasses.replace(cfg, dtype="float32"))
    return model, params, jax.jit(
        lambda ids: dense.apply(params, ids)[0])


@pytest.mark.parametrize("cadence", list(_LAYOUT_CADENCES))
@pytest.mark.parametrize("shape", list(_LAYOUT_MODELS))
def test_paged_engine_matches_full_context_greedy(shape, cadence):
    """The satellite pin: the serving KV-cache decode produces
    token-for-token what re-running the full context per token
    produces (greedy), whatever the kv heads' widths make of a
    token's row in the pool (``_LAYOUT_MODELS``) and through every
    cadence. In float32 every streamed token IS the dense reference's
    argmax over what came before it; a bfloat16 engine's token may be
    another where two logits lie nearer than its rounding, so it is
    held to the reference's top logit by a gap."""
    model, params, dense = _layout_model(shape)
    eng = _engine(model, params, **_LAYOUT_CADENCES[cadence])
    # 10 tokens cross the 8-chunk; 3 and 20 end inside a page.
    prompts = _ragged_prompts()
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=12))
    eng.run_until_drained()
    done = {r["id"]: r["tokens"] for r in eng.completed}
    exact = model.cfg.dtype == "float32"
    for i, p in enumerate(prompts):
        got = done[f"r{i}"]
        assert len(got) == 12
        ids = np.concatenate([p, np.asarray(got[:-1], np.int32)])
        rows = np.asarray(dense(jnp.asarray(ids[None], jnp.int32)))[
            0, len(p) - 1:]
        if exact:
            assert got == [int(t) for t in rows.argmax(-1)], f"r{i}"
        else:
            gaps = rows.max(-1) - rows[np.arange(12), got]
            assert gaps.max() < 0.05, (f"r{i}", gaps)


def test_batch_composition_independence(tiny_model):
    """A sequence decodes the same tokens alone as in a full batch
    (continuous batching must not couple sequences)."""
    model, params = tiny_model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, size=int(rng.integers(3, 16)))
               .astype(np.int32) for _ in range(6)]
    eng = _engine(model, params, max_batch=6, num_pages=96)
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=8))
    eng.run_until_drained()
    batched = {r["id"]: r["tokens"] for r in eng.completed}
    solo = _engine(model, params, max_batch=1)
    assert solo.generate(prompts[2], 8) == batched["r2"]
    assert solo.generate(prompts[5], 8) == batched["r5"]


@pytest.mark.parametrize("cadence", ["plain", "resident8",
                                     "sampled_top1", "spec4"])
def test_no_recompiles_across_join_evict_storm(tiny_model, cadence):
    """``Engine.warmup`` compiles every program at the signature the
    storm calls it with, in every cadence: the carried slot table
    included, through the prefill program that writes it, the upload
    for sequences no prefill launch wrote (a whole-prompt prefix hit,
    a prompt that extends one) and a drain."""
    model, params = tiny_model
    eng = _engine(model, params, max_batch=3, num_pages=96,
                  **_CADENCES[cadence])
    counts = eng.warmup()
    rng = np.random.default_rng(5)
    for i in range(7):
        eng.submit(Request(
            id=f"r{i}",
            prompt=rng.integers(0, 256,
                                size=int(rng.integers(2, 20)))
            .astype(np.int32),
            max_new_tokens=int(rng.integers(1, 10))))
    eng.run_until_drained()
    assert len(eng.completed) == 7
    assert eng.compile_counts() == counts, \
        "join/evict changed a traced shape"
    # A session turn, the same prompt again (every page of it is
    # resident: no prefill launch) and a prompt that extends it.
    turn = rng.integers(0, 256, size=16).astype(np.int32)
    eng.submit(Request(id="t0", prompt=turn, max_new_tokens=6,
                       session="s"))
    eng.run_until_drained()
    launches = eng.prefill_launches
    eng.submit(Request(id="t1", prompt=turn, max_new_tokens=6))
    eng.submit(Request(id="t2", prompt=np.concatenate(
        [turn, turn[:5]]), max_new_tokens=6))
    for _ in range(3):
        eng.step()
    eng.drain()
    done = {r["id"]: r["tokens"] for r in eng.completed}
    assert done["t1"] == done["t0"]
    assert eng.prefill_launches == launches + 1     # t2's tail alone
    assert eng.prefix_stats["hit_tokens"] == 32
    assert eng.compile_counts() == counts, \
        "a prefix hit or a drain changed a traced shape"


def test_preempt_resume_is_token_transparent(tiny_model):
    model, params = tiny_model
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 256, size=8).astype(np.int32)
               for _ in range(5)]

    def submit_all(eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"r{i}", prompt=p,
                               max_new_tokens=8))

    ref = _engine(model, params, num_pages=96)
    submit_all(ref)
    ref.run_until_drained()
    want = {r["id"]: r["tokens"] for r in ref.completed}

    eng = _engine(model, params, num_pages=96)
    submit_all(eng)
    for _ in range(9):
        eng.step()
    lost = eng.preempt()
    assert eng.cache.pages_used == 0  # preemption frees every page
    for r in lost:
        eng.submit(r)
    eng.run_until_drained()
    assert {r["id"]: r["tokens"] for r in eng.completed} == want


def test_mid_prefill_pool_stall_falls_back_to_decode(tiny_model):
    """Regression: a prompt arriving mid-storm whose next chunk
    cannot get a page must NOT livelock a prefill-priority engine —
    decode must keep running so finishing sequences free the pages
    the prefill is waiting for."""
    model, params = tiny_model
    # 4 usable pages of 4 tokens. A: 4 prompt + 8 new = 3 pages.
    eng = _engine(model, params, max_batch=2, page_size=4,
                  num_pages=5, max_seq_len=16, prefill_chunk=4)
    eng.submit(Request(id="a",
                       prompt=np.asarray([1, 2, 3, 4], np.int32),
                       max_new_tokens=8))
    for _ in range(6):  # prefill + enough decode to hold 3 pages
        eng.step()
    assert eng.cache.pages_used >= 3
    # B needs 3 pages total; its first chunk fits (1 page free), the
    # second stalls until A completes and frees.
    eng.submit(Request(id="b",
                       prompt=np.asarray([9] * 8, np.int32),
                       max_new_tokens=2))
    eng.run_until_drained(max_steps=200)
    assert {r["id"] for r in eng.completed} == {"a", "b"}
    assert eng.cache.pages_used == 0


def test_engine_request_validation(tiny_model):
    model, params = tiny_model
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(id="e",
                           prompt=np.zeros((0,), np.int32),
                           max_new_tokens=4))
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(Request(id="big",
                           prompt=np.zeros((10,), np.int32),
                           max_new_tokens=1000))
    # An over-long adopt must neither crash later nor leak the
    # joined cache entry.
    k = np.zeros((2, 2, 100, 16), np.float32)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.adopt(Request(id="h", prompt=np.zeros((100,), np.int32),
                          max_new_tokens=8), 0, k, k)
    assert eng.cache.seqs == 0 and eng.cache.pages_used == 0


def test_server_survives_invalid_requests(tiny_model):
    """A bad request answers 400; the engine thread stays alive and
    serves the next valid request."""
    import urllib.error
    import urllib.request

    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    srv = ServingServer(_engine(model, params), port=0)
    assert srv.start() is not None
    try:
        def post(payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return json.loads(
                urllib.request.urlopen(req, timeout=60).read())

        for bad in ({"prompt_ids": [], "max_new_tokens": 4},
                    {"prompt_ids": [1, 2], "max_new_tokens": 999},
                    {"prompt_ids": [999], "max_new_tokens": 4},
                    {"max_new_tokens": 4}):
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(bad)
            assert ei.value.code == 400
        good = post({"prompt_ids": [5, 7, 11], "max_new_tokens": 3})
        assert len(good["tokens"]) == 3
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# telemetry / metrics schema
# ---------------------------------------------------------------------------

SERVING_GAUGES = (
    "dtt_serving_requests_in_flight",
    "dtt_serving_queue_depth",
    "dtt_serving_kv_pages_used",
    "dtt_serving_kv_pages_total",
    "dtt_serving_ttft_seconds",
    "dtt_serving_tokens_per_s",
    # SERVING_r04 additions (every engine emits these; the resident
    # steps-per-launch gauge additionally needs resident_k > 1).
    "dtt_serving_host_syncs_per_token",
    "dtt_serving_weight_bytes",
    # SERVING_r05 additions (prefix sharing is on by default, so
    # every engine step carries them; the counters render with the
    # same `name value` shape as gauges).
    "dtt_serving_sessions_resident",
    "dtt_serving_prefix_hit_tokens_total",
    "dtt_serving_prefill_tokens_saved_total",
)


def test_metrics_endpoint_serving_gauge_schema(tiny_model, tmp_path):
    """The pinned serving schema on /metrics, additive next to the
    training gauges."""
    import urllib.request

    from distributed_training_tpu.telemetry import (
        MetricsServer, Telemetry, install, uninstall)

    model, params = tiny_model
    tel = Telemetry(events_jsonl=str(tmp_path / "events.jsonl"))
    install(tel)
    try:
        ms = MetricsServer(0, telemetry=tel)
        assert ms.start() is not None
        eng = _engine(model, params)
        eng.submit(Request(id="r0",
                           prompt=np.asarray([1, 2, 3], np.int32),
                           max_new_tokens=4))
        eng.run_until_drained()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{ms.port}/metrics",
            timeout=10).read().decode()
        for gauge in SERVING_GAUGES:
            assert f"\n{gauge} " in "\n" + body, \
                f"{gauge} missing from /metrics"
        # Per-group shared-page family (labeled, so the bare-name
        # pattern above does not cover it).
        assert 'dtt_serving_kv_pages_shared{group="0"}' in body
        assert "dtt_serving_requests_total 1" in body
        # Additive: the training schema is still there.
        assert "dtt_up 1" in body
        ms.stop()
    finally:
        uninstall()
        tel.close()


# ---------------------------------------------------------------------------
# export provenance → weight store
# ---------------------------------------------------------------------------


def _artifact(tmp_path, params, meta):
    from distributed_training_tpu.checkpoint.consolidate import (
        write_artifact)
    path = str(tmp_path / "model.msgpack")
    write_artifact(path, jax.tree.map(np.asarray,
                                      {"params": params}), meta)
    return path


def test_weight_store_provenance_gate(tiny_model, tmp_path, caplog):
    import logging

    from distributed_training_tpu.parallel.planner import load_plan
    from distributed_training_tpu.serving.disagg import (
        ProvenanceError, WeightStore)

    model, params = tiny_model
    plan = load_plan("serving_4dev_cpu_decode")
    good = _artifact(tmp_path, params, {"sharding_plan": {
        "name": plan.name, "fingerprint": plan.fingerprint()}})
    WeightStore(good)  # matching provenance loads silently

    stale = _artifact(tmp_path, params, {"sharding_plan": {
        "name": plan.name, "fingerprint": "deadbeefdeadbeef"}})
    with pytest.raises(ProvenanceError, match="regenerated"):
        WeightStore(stale)

    gone = _artifact(tmp_path, params, {"sharding_plan": {
        "name": "no_such_plan", "fingerprint": "aa"}})
    with pytest.raises(ProvenanceError, match="no longer loads"):
        WeightStore(gone)

    legacy = _artifact(tmp_path, params, {})
    with caplog.at_level(logging.WARNING):
        WeightStore(legacy)
    assert any("no sharding-plan provenance" in r.message
               for r in caplog.records)


def test_export_cli_stamps_plan_provenance(tmp_path):
    """checkpoint/export.py --plan embeds {name, fingerprint}; the
    round trip through the WeightStore then passes the gate."""
    from distributed_training_tpu.checkpoint.export import (
        _plan_provenance)
    from distributed_training_tpu.parallel.planner import load_plan

    plan = load_plan("serving_4dev_cpu_decode")
    prov = _plan_provenance(str(tmp_path / "checkpoints"),
                            "serving_4dev_cpu_decode")
    assert prov == {"name": plan.name,
                    "fingerprint": plan.fingerprint()}
    # Auto-detect: no resolved_config.yaml next to the ckpt dir →
    # legacy (no stamp), never an error.
    assert _plan_provenance(str(tmp_path / "checkpoints"),
                            None) is None
    assert _plan_provenance(str(tmp_path / "checkpoints"),
                            "none") is None


# ---------------------------------------------------------------------------
# disaggregation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_model():
    from distributed_training_tpu.models.transformer import (
        Transformer as TF, TransformerConfig as TC)
    from distributed_training_tpu.parallel.planner import (
        SERVING_MODEL_KWARGS)

    model = TF(TC(**SERVING_MODEL_KWARGS))
    return model, model.init(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def disagg_pipe(serving_model, tmp_path_factory):
    from distributed_training_tpu.parallel.planner import load_plan
    from distributed_training_tpu.serving.disagg import (
        DisaggPipeline, WeightStore)

    model, params = serving_model
    tmp = tmp_path_factory.mktemp("disagg")
    art = _artifact(tmp, params, {})
    store = WeightStore(art, check_provenance=False)
    pre = load_plan("serving_4dev_cpu_prefill")
    dec = load_plan("serving_4dev_cpu_decode")
    devs = jax.devices("cpu")
    return DisaggPipeline(store, pre, dec, devs[:4], devs[4:8]), dec


def test_disagg_pipeline_matches_colocated_engine(serving_model,
                                                  disagg_pipe):
    """Two plans, one weight store, KV handed off between mesh
    slices — greedy tokens identical to the co-located engine."""
    from distributed_training_tpu.serving.disagg import (
        engine_config_for_plan)

    model, params = serving_model
    pipe, dec = disagg_pipe
    prompt = np.asarray([9, 2, 77, 140, 33, 8, 250, 6], np.int32)
    got = pipe.generate(prompt, 10)

    colo = Engine(model, params, engine_config_for_plan(dec))
    assert got == colo.generate(prompt, 10)
    # The handoff crossed two different pool layouts (prefill slice
    # unsharded kv, decode slice dp×tp-sharded) — make that claim
    # real.
    assert pipe.decode_engine.cache.sharding is not None
    assert pipe.decode_engine.dp_groups == dec.mesh["dp"] > 1


def test_batched_continuous_handoff_matches_per_request(disagg_pipe):
    """The continuous-handoff rate path (generate_many: per-step
    batched export/import overlapped with ongoing decode) is pinned
    token-identical to the one-synchronous-transfer-per-request
    path."""
    from distributed_training_tpu.serving.engine import Request

    pipe, _dec = disagg_pipe
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 256, size=int(rng.integers(4, 20)))
               .astype(np.int32) for _ in range(6)]
    reqs = [Request(id=f"h{i}", prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    got = pipe.generate_many(reqs)
    assert set(got) == {r.id for r in reqs}
    for i, p in enumerate(prompts):
        want = pipe.generate(p, 6, req_id=f"solo{i}")
        assert got[f"h{i}"] == want, f"request h{i} diverged"


# ---------------------------------------------------------------------------
# the committed decode plan's reshard-zero pin
# ---------------------------------------------------------------------------


def test_serving_decode_audit_target_registered_and_pinned():
    from distributed_training_tpu.analysis import targets

    t = targets.TARGETS.get("serving_decode_planned")
    assert t is not None, ("serving decode audit target missing — "
                          "conf/plans/serving_8dev_cpu_decode.json "
                          "gone?")
    assert t.kind == "serving"
    assert "SPMD001" in t.pin_zero


def test_serving_decode_program_compiles_reshard_clean():
    """The acceptance pin, re-proved by compile: zero involuntary
    reshards in the decode program under the committed plan."""
    from distributed_training_tpu.analysis import audit, targets

    rec = audit.audit_target(targets.TARGETS["serving_decode_planned"])
    assert rec["spmd_reshard_warnings"] == 0
    assert rec["findings_by_code"].get("SPMD001", 0) == 0


def test_decode_plan_objective_and_kv_feasibility():
    """The decode plan chose a kv-head-sharded layout BECAUSE the
    replicated pool does not fit — the scoring's stated mechanism,
    pinned so a cost-model tweak can't silently flip it."""
    from distributed_training_tpu.parallel.planner import (
        PLAN_TARGETS, load_plan, rank_candidates, score_candidate)

    plan = load_plan("serving_8dev_cpu_decode")
    assert plan.inputs.get("objective") == "decode"
    assert plan.mesh["tp"] > 1
    target = PLAN_TARGETS["serving_8dev_cpu_decode"]
    ranked = rank_candidates(target)
    assert all(c.tp > 1 for c, _s in ranked), \
        "an unsharded-pool candidate became feasible"
    from distributed_training_tpu.parallel.planner import Candidate
    rep = score_candidate(
        target, Candidate(pp=1, dp=8, fsdp=1, sp=1, tp=1,
                          remat="none", batch_per_shard=32))
    assert rep["feasible"] is False and rep["reason"] == "hbm"


# ---------------------------------------------------------------------------
# dp-sharded decode (SERVING_r02): batch-parallel continuous batching
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sharded_engine(serving_model):
    """The committed decode plan's engine: slot table dealt over dp4,
    pool sharded dp×tp, params placed per the plan."""
    from distributed_training_tpu.parallel.planner import load_plan
    from distributed_training_tpu.runtime import MeshSpec, build_mesh
    from distributed_training_tpu.serving.disagg import (
        engine_config_for_plan, place_params)

    model, params = serving_model
    plan = load_plan("serving_8dev_cpu_decode")
    spec = MeshSpec(**{a: plan.mesh.get(a, 1)
                       for a in ("pp", "dp", "fsdp", "sp", "tp")})
    mesh = build_mesh(spec, jax.devices()[:spec.total])
    eng = Engine(model, place_params(params, mesh, plan),
                 engine_config_for_plan(plan), mesh=mesh)
    eng.warmup()
    return eng, plan


def _drain_clean(eng):
    eng.run_until_drained()
    recs = {r["id"]: r for r in eng.completed}
    eng.completed.clear()
    assert eng.cache.pages_used == 0
    return recs


def test_dp_sharded_engine_matches_replicated(serving_model,
                                              sharded_engine):
    """THE tentpole pin: the dp-sharded engine (groups of
    max_batch/dp slots, each against its own pool shard) produces
    token-for-token what the replicated single-group engine produces
    on the same request set — and join/evict stays zero-recompile."""
    import dataclasses

    model, params = serving_model
    eng, plan = sharded_engine
    counts = eng.compile_counts()
    G = eng.dp_groups
    assert G == plan.mesh["dp"] > 1
    assert eng.batch_local * G == eng.cfg.max_batch

    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, 256, size=int(rng.integers(3, 24)))
               .astype(np.int32) for _ in range(12)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=8))
    sharded = _drain_clean(eng)
    assert eng.compile_counts() == counts, \
        "dp-sharded join/evict changed a traced shape"
    # Work actually spread over groups (12 requests, 4 groups).
    assert len({r["group"] for r in sharded.values()}) == G

    # The PR-13-shaped reference: one group holding the WHOLE slot
    # table (same aggregate pool budget), unsharded.
    ref = Engine(model, params, dataclasses.replace(
        eng.cfg, num_pages=G * (eng.cfg.num_pages - 1) + 1))
    for i, p in enumerate(prompts):
        ref.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=8))
    want = _drain_clean(ref)
    assert {k: v["tokens"] for k, v in sharded.items()} == \
        {k: v["tokens"] for k, v in want.items()}


def test_batch_composition_independence_across_groups(
        serving_model, sharded_engine):
    """A sequence decodes the same tokens whichever GROUP it lands
    in and whoever shares the batch — greedy decode must be exact
    across the shard boundary."""
    model, params = serving_model
    eng, _plan = sharded_engine
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, 256, size=int(rng.integers(4, 16)))
               .astype(np.int32) for _ in range(9)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"b{i}", prompt=p, max_new_tokens=6))
    batched = _drain_clean(eng)
    # Solo on the SAME sharded engine: lands in group 0 (empty
    # engine, fewest-active tie to the lowest index) — group
    # assignment differs from the batched run for most requests.
    for i in (2, 5, 8):
        eng.submit(Request(id=f"solo{i}", prompt=prompts[i],
                           max_new_tokens=6))
        solo = _drain_clean(eng)
        assert solo[f"solo{i}"]["tokens"] == \
            batched[f"b{i}"]["tokens"]


def test_per_shard_allocator_leak_freedom_random_join_evict():
    """The PR-13 leak invariant, per dp group: any join/evict order
    keeps every group's ``used + free == usable`` exact, allocations
    never bleed across shards, and a full drain returns every group
    to zero."""
    from distributed_training_tpu.serving.kv_cache import (
        PagedCacheConfig, PagedKVCache)

    G = 4
    cfg = PagedCacheConfig(n_layers=2, n_kv_heads=2, head_dim=16,
                           page_size=8, num_pages=16, max_seq_len=64,
                           dp_groups=G)
    cache = PagedKVCache(cfg)
    rng = np.random.default_rng(23)
    live: dict[int, tuple[int, int]] = {}   # sid -> (group, tokens)
    next_id = 0
    for _ in range(600):
        per_group = [0] * G
        for sid, (g, n) in live.items():
            per_group[g] += -(-n // cfg.page_size) if n else 0
        for g in range(G):
            assert cache.pages_used_in(g) == per_group[g]
            assert cache.pages_used_in(g) + \
                cache.free_pages_in(g) == cfg.usable_pages
        assert cache.pages_used == sum(per_group)
        op = rng.integers(0, 3)
        if op == 0 and len(live) < 12:
            g = int(rng.integers(0, G))
            cache.join(next_id, group=g)
            assert cache.group_of(next_id) == g
            live[next_id] = (g, 0)
            next_id += 1
        elif op == 1 and live:
            sid = int(rng.choice(list(live)))
            g, n = live[sid]
            want = min(n + int(rng.integers(1, 20)),
                       cfg.max_seq_len)
            if cache.ensure(sid, want):
                cache.advance(sid, want - n)
                live[sid] = (g, want)
        elif op == 2 and live:
            sid = int(rng.choice(list(live)))
            cache.free(sid)
            del live[sid]
    for sid in list(live):
        cache.free(sid)
    assert cache.pages_used == 0
    for g in range(G):
        assert cache.free_pages_in(g) == cfg.usable_pages


def test_admission_balances_skewed_arrival_burst(serving_model,
                                                 sharded_engine):
    """A burst arriving all at once must spread over the dp groups
    (fewest-active-slots-first) instead of piling onto shard 0 while
    the others idle."""
    eng, _plan = sharded_engine
    G, B = eng.dp_groups, eng.batch_local
    rng = np.random.default_rng(29)
    n_burst = G * 2
    for i in range(n_burst):
        eng.submit(Request(
            id=f"burst{i}",
            prompt=rng.integers(0, 256, size=6).astype(np.int32),
            max_new_tokens=4))
    # One admission per step: step until the whole burst is in.
    for _ in range(n_burst * 3):
        if eng.in_flight == n_burst:
            break
        eng.step()
    assert eng.in_flight == n_burst
    assert eng.slots_active_by_group() == [n_burst // G] * G, \
        "burst piled onto a subset of dp groups"
    recs = _drain_clean(eng)
    groups = [r["group"] for r in recs.values()]
    assert sorted(set(groups)) == list(range(G))


def test_sharded_engine_emits_group_gauges(serving_model,
                                           tmp_path):
    """The per-dp-group serving gauges: step records carry per-group
    slot/page lists and /metrics exports them as labeled rows,
    additive next to the flat serving schema."""
    import urllib.request

    from distributed_training_tpu.parallel.planner import load_plan
    from distributed_training_tpu.runtime import MeshSpec, build_mesh
    from distributed_training_tpu.serving.disagg import (
        engine_config_for_plan, place_params)
    from distributed_training_tpu.telemetry import (
        MetricsServer, Telemetry, install, uninstall)

    model, params = serving_model
    plan = load_plan("serving_8dev_cpu_decode")
    spec = MeshSpec(**{a: plan.mesh.get(a, 1)
                       for a in ("pp", "dp", "fsdp", "sp", "tp")})
    mesh = build_mesh(spec, jax.devices()[:spec.total])
    tel = Telemetry(events_jsonl=str(tmp_path / "events.jsonl"))
    install(tel)
    try:
        ms = MetricsServer(0, telemetry=tel)
        assert ms.start() is not None
        eng = Engine(model, place_params(params, mesh, plan),
                     engine_config_for_plan(plan), mesh=mesh)
        eng.submit(Request(id="g0",
                           prompt=np.asarray([1, 2, 3], np.int32),
                           max_new_tokens=4))
        eng.run_until_drained()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{ms.port}/metrics",
            timeout=10).read().decode()
        for g in range(eng.dp_groups):
            assert (f'dtt_serving_group_slots_active{{group="{g}"}}'
                    in body)
            assert (f'dtt_serving_group_kv_pages_used{{group="{g}"}}'
                    in body)
        # Flat schema intact next to the labeled rows.
        for gauge in SERVING_GAUGES:
            assert f"\n{gauge} " in "\n" + body
        ms.stop()
    finally:
        uninstall()
        tel.close()


def test_http_streaming_tokens_match_nonstream(tiny_model):
    """`"stream": true` returns chunked transfer-encoding, one JSON
    line per token, and the streamed tokens equal the blocking
    path's token-for-token."""
    import http.client

    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    srv = ServingServer(_engine(model, params), port=0)
    assert srv.start() is not None
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt_ids": [5, 7, 11],
                        "max_new_tokens": 6,
                        "stream": True}).encode(),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Transfer-Encoding") == "chunked"
        lines = []
        while True:
            line = resp.readline()
            if not line:
                break
            lines.append(json.loads(line))
        toks = [ln["token"] for ln in lines if "token" in ln]
        final = lines[-1]
        assert final["done"] is True
        assert final["tokens"] == toks
        assert len(toks) == 6
        conn2 = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=60)
        conn2.request(
            "POST", "/generate",
            json.dumps({"prompt_ids": [5, 7, 11],
                        "max_new_tokens": 6}).encode(),
            {"Content-Type": "application/json"})
        blocking = json.loads(conn2.getresponse().read())
        assert blocking["tokens"] == toks
        # A bad streamed request still 400s BEFORE the stream opens.
        conn3 = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=60)
        conn3.request(
            "POST", "/generate",
            json.dumps({"prompt_ids": [], "stream": True}).encode(),
            {"Content-Type": "application/json"})
        assert conn3.getresponse().status == 400
    finally:
        srv.stop()


def test_stream_abandonment_deregisters_listener(tiny_model):
    """Closing a streaming generator mid-request (the client-went-
    away path) must deregister the engine-side token listener and
    the stream queue immediately — not leave them filling an
    orphaned queue until the sequence drains."""
    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    srv = ServingServer(_engine(model, params), port=0)
    assert srv.start() is not None
    try:
        gen = srv.generate_stream(
            np.asarray([5, 7, 11], np.int32), 12)
        first = next(gen)
        assert "token" in first
        gen.close()  # client disconnect
        assert srv._streams == {}
        assert srv.engine._token_listeners == {}
        # The abandoned request still completes in the engine, and
        # the server keeps serving.
        deadline = time.monotonic() + 30
        while (srv.engine.in_flight or srv._mailbox) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.engine.in_flight == 0
        rec = srv.generate(np.asarray([5, 7, 11], np.int32), 4)
        assert len(rec["tokens"]) == 4
    finally:
        srv.stop()


def test_http_stream_client_disconnect_keeps_serving(tiny_model):
    """A client that drops the connection mid-stream must not take
    down the handler (BrokenPipeError on the chunk/terminator
    writes) — the next request is served normally."""
    import http.client

    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    srv = ServingServer(_engine(model, params), port=0)
    assert srv.start() is not None
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt_ids": [5, 7, 11],
                        "max_new_tokens": 16,
                        "stream": True}).encode(),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert json.loads(resp.readline()).get("token") is not None
        conn.close()  # walk away mid-stream
        conn2 = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=60)
        conn2.request(
            "POST", "/generate",
            json.dumps({"prompt_ids": [5, 7, 11],
                        "max_new_tokens": 6}).encode(),
            {"Content-Type": "application/json"})
        blocking = json.loads(conn2.getresponse().read())
        assert len(blocking["tokens"]) == 6
        # The abandoned stream request may still be decoding
        # (continuous batching ran both concurrently); once it
        # drains, nothing may be left registered.
        deadline = time.monotonic() + 30
        while srv.engine.in_flight and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.engine.in_flight == 0
        assert srv.engine._token_listeners == {}
        assert srv._streams == {}
    finally:
        srv.stop()


def test_preempt_drops_token_listeners(tiny_model):
    """preempt() hands unfinished work back fresh — a listener left
    registered would stream a resubmitted request's early tokens
    twice."""
    model, params = tiny_model
    eng = _engine(model, params, num_pages=96)
    seen: list[int] = []
    eng.submit(Request(id="s0",
                       prompt=np.asarray([1, 2, 3, 4], np.int32),
                       max_new_tokens=8))
    eng.add_token_listener("s0", lambda tok, done: seen.append(tok))
    for _ in range(4):
        eng.step()
    n_before = len(seen)
    assert n_before > 0
    lost = eng.preempt()
    assert eng._token_listeners == {}
    for r in lost:
        eng.submit(r)
    eng.run_until_drained()
    # The re-run emitted nothing to the stale listener.
    assert len(seen) == n_before
    (rec,) = eng.completed
    assert len(rec["tokens"]) == 8


def test_serving_r02_ledger_committed_and_coherent():
    """SERVING_r02.json: the dp-sharded acceptance gates stay
    machine-checked — >= 2x r01's aggregate tokens/s on the same
    storm, zero recompiles, an embedded compared_to block, streamed
    TTFT, and the greedy-vs-full-context parity flag."""
    import os

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    with open(os.path.join(root, "SERVING_r02.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "SERVING_r01.json")) as f:
        r01 = json.load(f)
    steady = doc["steady"]
    assert steady["recompiles_after_warmup"] == 0
    # Concurrency must span dp groups (a faster engine legitimately
    # holds FEWER requests in flight on the same realtime storm, so
    # the r01-era absolute >= 20 gate would punish speed).
    assert steady["max_in_flight"] > steady["slots_per_group"]
    assert steady["dp_groups"] > 1
    cmp_block = doc["compared_to"]
    assert cmp_block["revision"] == "r01"
    assert cmp_block["tokens_per_s"] == \
        r01["steady"]["tokens_per_s"]
    # THE acceptance number: saturated aggregate decode throughput
    # (the realtime storm is arrival-bound — its ~0.8s Poisson span
    # caps any engine near 1.4k tok/s; the note works the math).
    assert doc["saturated"]["tokens_per_s"] >= \
        2 * cmp_block["tokens_per_s"]
    assert cmp_block["speedup"] >= 2
    assert doc["saturated"]["replicated_same_mesh"][
        "tokens_per_s"] > 0
    assert doc["plan"]["mesh"]["dp"] > 1
    assert doc["steady"]["greedy_matches_full_context"] is True
    assert doc["streaming"]["ttft_first_byte_s"] > 0
    pre = doc["preemption"]
    assert pre["tokens_match_steady_storm"] is True
    assert 0 < pre["goodput"] <= 1


# ---------------------------------------------------------------------------
# batched multi-sequence prefill + speculative decode (SERVING_r03)
# ---------------------------------------------------------------------------


def _ragged_prompts():
    """Prompt lengths chosen to hit every chunk-tail shape at
    prefill_chunk=8: shorter than a chunk, exactly one chunk, one
    chunk + tail, and multiple chunks + tail."""
    return [np.asarray([5, 7, 11], np.int32),
            np.asarray(np.arange(8), np.int32),
            np.asarray([5, 7, 11, 13, 17, 19, 23, 29, 31, 37],
                       np.int32),
            np.asarray(([3, 9, 27] * 7)[:20], np.int32)]


def test_batched_prefill_matches_full_context(tiny_model):
    """The tentpole prefill pin: the batched lane program (many
    prompts' chunks per launch, ragged tails included) produces
    token-for-token what the full-context ``model.apply`` reference
    produces, and changes no traced shape."""
    model, params = tiny_model
    prompts = _ragged_prompts()
    eng = _engine(model, params, num_pages=96)
    counts = eng.warmup()
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=10))
    eng.run_until_drained()
    assert eng.compile_counts() == counts, \
        "prefill changed a traced shape"
    batched = {r["id"]: r["tokens"] for r in eng.completed}
    for i, p in enumerate(prompts):
        assert batched[f"r{i}"] == _full_context_greedy(
            model, params, p, 10), f"prompt {i} diverged"


def test_batched_prefill_packs_many_prompts_per_launch(tiny_model):
    """The launch-amortization mechanism itself: once admitted, ONE
    prefill step advances EVERY pending single-chunk prompt."""
    model, params = tiny_model
    eng = _engine(model, params, max_batch=6, num_pages=96)
    eng.warmup()
    prompts = [np.asarray([i + 1, i + 2, i + 3], np.int32)
               for i in range(6)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=4))
    rec = eng.step()
    assert rec["op"] == "prefill"
    # One launch prefilled all six 3-token prompts (and sampled each
    # one's first token in-program).
    assert rec["tokens"] == sum(len(p) for p in prompts)
    assert all(s is None or s.prefill_done for s in eng.slots)
    assert all(len(s.generated) == 1 for s in eng.slots
               if s is not None)


def test_batched_prefill_cross_group_parity(serving_model,
                                            sharded_engine):
    """Batched prefill on the dp-sharded engine: each group packs
    ITS OWN admitted prompts into its lane shard — tokens must match
    the unsharded single-group engine exactly (lanes, groups, and
    chunk tails are invisible to the output)."""
    import dataclasses

    model, params = serving_model
    eng, _plan = sharded_engine
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 256, size=int(rng.integers(3, 24)))
               .astype(np.int32) for _ in range(10)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"pf{i}", prompt=p, max_new_tokens=6))
    sharded = _drain_clean(eng)
    ref = Engine(model, params, dataclasses.replace(
        eng.cfg,
        num_pages=eng.dp_groups * (eng.cfg.num_pages - 1) + 1))
    for i, p in enumerate(prompts):
        ref.submit(Request(id=f"pf{i}", prompt=p, max_new_tokens=6))
    want = _drain_clean(ref)
    assert {k: v["tokens"] for k, v in sharded.items()} == \
        {k: v["tokens"] for k, v in want.items()}


def test_spec_decode_token_identity_and_acceptance(tiny_model):
    """The tentpole decode pin: speculative multi-token decode emits
    EXACTLY the one-token-per-launch greedy stream (acceptance is
    verification, not sampling), and the acceptance accounting adds
    up — emitted tokens across launches equal the decode-emitted
    tokens, with the mean in [1, spec_k]."""
    model, params = tiny_model
    prompts = _ragged_prompts()

    def run(k):
        eng = _engine(model, params, spec_k=k, num_pages=96)
        counts = eng.warmup()
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"r{i}", prompt=p,
                               max_new_tokens=12))
        steps = []
        while not eng.idle:
            steps.append(eng.step())
        assert eng.compile_counts() == counts, \
            f"spec_k={k} decode changed a traced shape"
        return {r["id"]: r["tokens"] for r in eng.completed}, steps

    plain, _ = run(1)
    for k in (3, 5):
        spec, steps = run(k)
        assert spec == plain, f"spec_k={k} changed tokens"
        # The step records are the ledger: a slot-launch is one
        # slot's verification chunk in one decode step.
        decode = [r for r in steps if r["op"] == "decode"]
        st = {"launches": sum(r["slots_stepped"] for r in decode),
              "emitted": sum(r["tokens"] for r in decode)}
        assert st["launches"] > 0
        # Every request's first token comes from prefill; the rest
        # are decode-emitted.
        decode_tokens = sum(len(t) - 1 for t in spec.values())
        assert st["emitted"] == decode_tokens
        mean = st["emitted"] / st["launches"]
        assert 1.0 <= mean <= k
        # Speculation must amortize launches: strictly fewer
        # slot-launches than decode-emitted tokens (acceptance > 1
        # on this repetitive tiny model).
        assert st["launches"] < decode_tokens


def test_spec_decode_respects_budget_and_seq_cap(tiny_model):
    """Chain clamping: a request one token from its budget, and one
    whose prompt + budget exactly fills max_seq_len, must finish
    token-identically under spec_k > 1 (padding lanes, never
    out-of-range writes)."""
    model, params = tiny_model
    prompt = np.asarray([5, 7, 11, 13], np.int32)

    def run(k, n_new, max_seq):
        eng = _engine(model, params, spec_k=k, max_seq_len=max_seq,
                      num_pages=96)
        eng.warmup()
        eng.submit(Request(id="edge", prompt=prompt,
                           max_new_tokens=n_new))
        eng.run_until_drained()
        (rec,) = eng.completed
        assert eng.cache.pages_used == 0
        return rec["tokens"]

    for n_new, max_seq in ((1, 64), (2, 64), (12, 16), (11, 16)):
        assert run(6, n_new, max_seq) == run(1, n_new, max_seq)


def test_spec_requires_greedy():
    with pytest.raises(ValueError, match="greedy"):
        EngineConfig(spec_k=2, temperature=0.7)
    with pytest.raises(ValueError, match="spec_k"):
        EngineConfig(spec_k=0)


@pytest.mark.parametrize("gone", ["prefill_mode", "policy", "paged_impl"])
def test_removed_engine_options_are_unknown_fields(gone):
    """One prefill path, one scheduling order, one attention entry: the
    options that chose among others are no fields any more."""
    with pytest.raises(TypeError, match=gone):
        EngineConfig(**{gone: "x"})


def test_prompt_lookup_draft():
    """The drafting policy: most recent earlier occurrence of the
    trailing n-gram wins; continuations pad with the last token;
    no-match histories draft the last token repeated. Draft quality
    never touches correctness (verification owns the output) — this
    pins the LOOKUP so acceptance behavior is deterministic."""
    from distributed_training_tpu.serving.engine import draft_tokens

    h = np.asarray([1, 2, 3, 9, 1, 2, 3, 7, 1, 2, 3], np.int32)
    # Trailing [1,2,3]: most recent earlier occurrence at index 4 →
    # continuation [7, 1, 2].
    assert draft_tokens(h, 3, 3).tolist() == [7, 1, 2]
    # m longer than the continuation: pad with the last token.
    assert draft_tokens(h, 8, 3).tolist() == [7, 1, 2, 3, 3, 3, 3, 3]
    # No repeated n-gram anywhere: repeat the last token.
    assert draft_tokens(np.asarray([4, 5, 6], np.int32),
                        2, 3).tolist() == [6, 6]
    # Falls back to shorter n-grams when the long one never repeats.
    h2 = np.asarray([8, 1, 9, 2, 9, 3, 9], np.int32)
    assert draft_tokens(h2, 2, 3).tolist() == [3, 9]
    assert draft_tokens(h2, 0, 3).tolist() == []


def test_sharded_engine_emits_prefill_gauges(serving_model,
                                             tmp_path):
    """The per-dp-group PREFILL gauges (SERVING_r03 satellite):
    batched prefill steps carry per-group live-lane counts and an
    aggregate prompt tok/s, exported as labeled /metrics rows
    additive next to the decode set."""
    import urllib.request

    from distributed_training_tpu.parallel.planner import load_plan
    from distributed_training_tpu.runtime import MeshSpec, build_mesh
    from distributed_training_tpu.serving.disagg import (
        engine_config_for_plan, place_params)
    from distributed_training_tpu.telemetry import (
        MetricsServer, Telemetry, install, uninstall)

    model, params = serving_model
    plan = load_plan("serving_8dev_cpu_decode")
    spec = MeshSpec(**{a: plan.mesh.get(a, 1)
                       for a in ("pp", "dp", "fsdp", "sp", "tp")})
    mesh = build_mesh(spec, jax.devices()[:spec.total])
    tel = Telemetry(events_jsonl=str(tmp_path / "events.jsonl"))
    install(tel)
    try:
        ms = MetricsServer(0, telemetry=tel)
        assert ms.start() is not None
        eng = Engine(model, place_params(params, mesh, plan),
                     engine_config_for_plan(plan, spec_k=3),
                     mesh=mesh)
        for i in range(4):
            eng.submit(Request(
                id=f"g{i}",
                prompt=np.asarray([1 + i, 2, 3], np.int32),
                max_new_tokens=6))
        eng.run_until_drained()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{ms.port}/metrics",
            timeout=10).read().decode()
        for g in range(eng.dp_groups):
            assert (f'dtt_serving_group_prefill_slots_active'
                    f'{{group="{g}"}}' in body)
        assert "\ndtt_serving_prefill_tokens_per_s " in "\n" + body
        assert "\ndtt_serving_spec_accepted_mean " in "\n" + body
        # Flat schema intact next to the new rows.
        for gauge in SERVING_GAUGES:
            assert f"\n{gauge} " in "\n" + body
        ms.stop()
    finally:
        uninstall()
        tel.close()


def test_serving_prefill_audit_target_registered_and_pinned():
    from distributed_training_tpu.analysis import targets

    t = targets.TARGETS.get("serving_prefill_planned")
    assert t is not None, ("serving prefill audit target missing — "
                           "conf/plans/serving_4dev_cpu_prefill.json "
                           "gone?")
    assert t.kind == "serving"
    assert t.serving_objective == "prefill"
    assert "SPMD001" in t.pin_zero


def test_serving_prefill_program_compiles_reshard_clean():
    """The r03 acceptance pin, re-proved by compile: zero
    involuntary reshards in the BATCHED prefill program under the
    committed prefill plan."""
    from distributed_training_tpu.analysis import audit, targets

    rec = audit.audit_target(
        targets.TARGETS["serving_prefill_planned"])
    assert rec["spmd_reshard_warnings"] == 0
    assert rec["findings_by_code"].get("SPMD001", 0) == 0


def test_prefill_plan_objective_and_lane_feasibility():
    """The committed prefill plan is resolved FOR the batched lane
    program: slots deal over dp (slots%dp pinned infeasible), and
    the winner's lane table spans the slice."""
    from distributed_training_tpu.parallel.planner import (
        Candidate, PLAN_TARGETS, load_plan, score_candidate)

    plan = load_plan("serving_4dev_cpu_prefill")
    assert plan.inputs.get("objective") == "prefill"
    assert plan.batch_per_shard % plan.mesh.get("dp", 1) == 0
    target = PLAN_TARGETS["serving_4dev_cpu_prefill"]
    # A lane table that cannot deal over dp is infeasible by
    # construction, not merely low-scoring.
    bad = score_candidate(
        target, Candidate(pp=1, dp=4, fsdp=1, sp=1, tp=1,
                          remat="none", batch_per_shard=6))
    assert bad["feasible"] is False and bad["reason"] == "slots%dp"
    good = score_candidate(
        target, Candidate(pp=1, dp=4, fsdp=1, sp=1, tp=1,
                          remat="none", batch_per_shard=8))
    assert good["feasible"] is True
    # The prefill pool rides the feasibility model (the disagg
    # handoff's source KV is real HBM).
    assert good["kv_pool_gib"] > 0


def test_serving_r03_ledger_committed_and_coherent():
    """SERVING_r03.json: the batched-prefill and speculative-decode
    acceptance gates stay machine-checked — spec decode above
    per-token launches same-run with the mean acceptance length
    recorded, zero recompiles, and greedy parity against the
    full-context reference."""
    import os

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    with open(os.path.join(root, "SERVING_r03.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "SERVING_r02.json")) as f:
        r02 = json.load(f)
    steady = doc["steady"]
    assert steady["recompiles_after_warmup"] == 0
    assert set(steady["compile_counts"]) == {"decode",
                                             "prefill_batch"}
    assert steady["greedy_matches_full_context"] is True
    assert steady["spec_k"] > 1
    assert doc["prefill"]["batched"]["prefill_tokens_per_s"] > 0
    # THE decode acceptance number: speculative launches beat
    # per-token launches same-run, acceptance recorded honestly.
    sat = doc["saturated"]
    assert sat["speedup_vs_per_token_same_run"] > 1.0
    assert 1.0 <= sat["spec_accepted_mean"] <= sat["spec_k"]
    assert sat["per_token_same_mesh"]["tokens_per_s"] > 0
    cmp_block = doc["compared_to"]
    assert cmp_block["revision"] == "r02"
    assert cmp_block["tokens_per_s"] == \
        r02["saturated"]["tokens_per_s"]
    pre = doc["preemption"]
    assert pre["tokens_match_steady_storm"] is True
    assert 0 < pre["goodput"] <= 1
    assert doc["streaming"]["ttft_first_byte_s"] > 0
    assert doc["plan"]["mesh"]["dp"] > 1


# ---------------------------------------------------------------------------
# device-resident decode + int8 weight-only serving (SERVING_r04)
# ---------------------------------------------------------------------------


def test_resident_decode_token_identity(tiny_model):
    """The tentpole decode pin: the device-resident K-step loop
    (every K, composed with speculative chunks) emits EXACTLY the
    one-launch-per-step greedy stream, with zero recompiles and the
    host syncing once per burst instead of once per step."""
    model, params = tiny_model
    prompts = _ragged_prompts()

    def run(rk, sk=1):
        eng = _engine(model, params, resident_k=rk, spec_k=sk,
                      num_pages=96)
        counts = eng.warmup()
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"r{i}", prompt=p,
                               max_new_tokens=12))
        steps = []
        while not eng.idle:
            steps.append(eng.step())
        assert eng.compile_counts() == counts, \
            f"resident_k={rk} decode changed a traced shape"
        assert eng.cache.pages_used == 0
        return ({r["id"]: r["tokens"] for r in eng.completed},
                eng.host_syncs, steps)

    plain, base_syncs, _ = run(1)
    for i, p in enumerate(prompts):
        assert plain[f"r{i}"] == _full_context_greedy(
            model, params, p, 12), f"prompt {i} diverged"
    for rk, sk in ((2, 1), (4, 1), (8, 1), (4, 4), (2, 3)):
        got, syncs, steps = run(rk, sk)
        assert got == plain, f"resident_k={rk},spec_k={sk} " \
            "changed tokens"
        bursts = [r for r in steps if r["op"] == "decode"]
        assert bursts
        decode_tokens = sum(len(t) - 1 for t in got.values())
        assert sum(r["tokens"] for r in bursts) == decode_tokens
        for r in bursts:
            assert 1 <= r["resident_steps_per_launch"] <= rk
            assert r["tokens"] / sk <= r["slot_iters"] <= r["tokens"]
        # The whole point: strictly fewer host syncs than the
        # per-step engine needed for the same stream.
        assert syncs < base_syncs


def _greedy_streams(model, params, first, n, width):
    """Each row's greedy continuation of its one token ``first[b]``,
    ``n`` tokens, the whole context re-run every token at one padded
    ``width`` (causal: a position's logits see nothing after it)."""
    ids = np.zeros((len(first), width), np.int32)
    ids[:, 0] = first
    apply = jax.jit(model.apply)
    for t in range(n):
        logits, _aux = apply(params, jnp.asarray(ids))
        ids[:, t + 1] = np.asarray(jnp.argmax(logits[:, t], axis=-1))
    return ids


def _appended(hist, kv, left, budget, active, streams, eos):
    """The resident loop's append as NumPy: each packed slot emits its
    stream's next ``min(budget, left)`` tokens, cut after the first
    ``eos``, written at ``kv + 1`` on; nothing else of the row moves.
    Returns ``(emitted per slot, history, kv_len, left)``."""
    hist, kv, left = hist.copy(), kv.copy(), left.copy()
    emitted = []
    for b in range(len(kv)):
        m = min(budget[b], left[b]) if active[b] else 0
        toks = list(streams[b, kv[b] + 1:kv[b] + 1 + m])
        stop = eos >= 0 and eos in toks
        if stop:
            toks = toks[:toks.index(eos) + 1]
        hist[b, kv[b] + 1:kv[b] + 1 + len(toks)] = toks
        left[b] = 0 if stop else left[b] - len(toks)
        kv[b] += len(toks)
        emitted.append(toks)
    return emitted, hist, kv, left


_APPEND_CASES = ("eos_mid_burst", "budget_ends_burst", "not_packed",
                 "reaches_max_seq_len")


@pytest.mark.parametrize("C", (1, 4))
@pytest.mark.parametrize("case", _APPEND_CASES)
def test_resident_append_writes_only_accepted_tokens(tiny_model, case,
                                                     C):
    """``_resident_program``'s history append, driven directly: two
    bursts on one carried slot table (the first writes each slot's
    prompt and its KV, the second is the case) leave every row as a
    NumPy model of the append says: its tokens up to ``kv_len``, then
    the tokens emitted, each the full-context greedy one, and every
    position past the new ``kv_len`` as it came in. Slot 0 is the
    case: the stop token three tokens into a burst, a budget that
    ends its burst before the loop's ``K`` does, a slot not packed,
    and a row written to ``max_seq_len - 1`` (the scatter's columns
    past the accepted ones then all fall out of range and are
    dropped). Slot 1's budget is cut by what its request has left."""
    model, params = tiny_model
    B, Lmax, ps = 4, 64, 8
    streams = _greedy_streams(model, params, [3, 50, 100, 200],
                              Lmax - 1, Lmax)
    prompt = np.asarray([6, 9, 4, 10])
    eos = -1
    if case == "eos_mid_burst":
        # a token of slot 0's stream that it has not emitted before
        q = max(i for i in range(3, 24)
                if streams[0, i] not in streams[0, :i])
        eos, prompt[0] = int(streams[0, q]), q - 3
    eng = _engine(model, params, max_batch=B, page_size=ps,
                  max_seq_len=Lmax, resident_k=Lmax, spec_k=C,
                  eos_id=eos)
    rng = np.random.default_rng(0)
    hist = rng.integers(0, 256, (B, Lmax)).astype(np.int32)
    hist[:, 0] = streams[:, 0]
    kv = np.zeros(B, np.int32)
    left = np.full(B, 100, np.int32)
    P = Lmax // ps
    rows = (1 + np.arange(B * P, dtype=np.int32)).reshape(1, B, P)
    pools = (eng.cache.k_pages, eng.cache.v_pages)
    budget2 = np.asarray([8, 8, 5, 8])
    active2 = np.ones(B, bool)
    if case == "budget_ends_burst":
        budget2[0] = 5
    elif case == "not_packed":
        active2[0] = False
    elif case == "reaches_max_seq_len":
        budget2[0] = Lmax - 1 - prompt[0]
    for budget, active in ((prompt, np.ones(B, bool)),
                           (budget2, active2)):
        out, n_em, _steps, _counts, *table, k, v = eng._decode_fn(
            eng.params, *pools, hist[None], kv[None], left[None],
            rows, budget[None].astype(np.int32), active[None])
        pools = (k, v)
        emitted, want, want_kv, want_left = _appended(
            hist, kv, left, budget, active, streams, eos)
        hist, kv, left = (np.array(a)[0] for a in table)
        np.testing.assert_array_equal(np.asarray(n_em)[0],
                                      [len(t) for t in emitted])
        for b, toks in enumerate(emitted):
            assert list(np.asarray(out)[0, b, :len(toks)]) == toks, b
        np.testing.assert_array_equal(hist, want)
        np.testing.assert_array_equal(kv, want_kv)
        np.testing.assert_array_equal(left, want_left)
        left[1] = 3
    if case == "eos_mid_burst":
        assert emitted[0][-1] == eos and len(emitted[0]) == 3
    elif case == "budget_ends_burst":
        assert len(emitted[0]) == 5
    elif case == "not_packed":
        assert emitted[0] == []
    else:
        assert kv[0] == Lmax - 1


def test_resident_decode_eos_stops_mid_burst(tiny_model):
    """Per-slot stop detection INSIDE the loop: when the stop token
    lands at step j < K the slot's burst ends there — the emitted
    stream truncates at the first EOS (inclusive) and matches the
    one-step engine configured identically."""
    model, params = tiny_model
    prompt = np.asarray([5, 7, 11, 13, 17], np.int32)

    def run(rk, eos):
        eng = _engine(model, params, resident_k=rk, eos_id=eos,
                      num_pages=96)
        eng.warmup()
        eng.submit(Request(id="e", prompt=prompt, max_new_tokens=12))
        eng.run_until_drained()
        (rec,) = eng.completed
        assert eng.cache.pages_used == 0
        return rec["tokens"]

    free = run(1, -1)
    assert len(free) == 12
    # Stop on a token the greedy stream actually emits, away from
    # burst boundaries (position 5 with K=4 is step 1 of burst 2).
    eos = free[5]
    want = free[:free.index(eos) + 1]
    got = run(4, eos)
    assert got == want, "resident EOS truncation diverged"
    assert run(1, eos) == want
    assert got[-1] == eos and len(got) < 12


def test_resident_decode_tight_pool_still_progresses(tiny_model):
    """All-slots-stall fallback: when the pool is too tight to cover
    a full K-step burst, the burst budget degrades to the pages a
    slot CAN cover (token_capacity) instead of stalling — the storm
    drains token-identically, just with more host syncs."""
    model, params = tiny_model
    prompts = [np.asarray([3 + i, 5, 7, 9], np.int32)
               for i in range(2)]

    def run(rk, pages):
        eng = _engine(model, params, max_batch=2, page_size=4,
                      num_pages=pages, max_seq_len=32,
                      prefill_chunk=4, resident_k=rk)
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"t{i}", prompt=p,
                               max_new_tokens=16))
        eng.run_until_drained(max_steps=300)
        assert eng.cache.pages_used == 0
        return {r["id"]: r["tokens"] for r in eng.completed}

    # 9 usable pages of 4 tokens for two sequences of 4+16 = 5 pages
    # each: neither can hold its whole horizon at once.
    want = run(1, 10)
    assert run(8, 10) == want
    # And with a roomy pool the same streams come out (sanity).
    assert run(8, 64) == want


def test_resident_preempt_mid_storm_resubmit_parity(tiny_model):
    """Bursts are atomic host-side: cache/slot state advances only
    after the burst's single fetch, so preempting between steps and
    resubmitting replays token-identically under resident_k > 1."""
    model, params = tiny_model
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, 256, size=8).astype(np.int32)
               for _ in range(5)]

    def submit_all(eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"r{i}", prompt=p,
                               max_new_tokens=8))

    ref = _engine(model, params, resident_k=4, num_pages=96)
    submit_all(ref)
    ref.run_until_drained()
    want = {r["id"]: r["tokens"] for r in ref.completed}

    eng = _engine(model, params, resident_k=4, num_pages=96)
    submit_all(eng)
    for _ in range(4):  # a few prefill + resident-burst steps in
        eng.step()
    lost = eng.preempt()
    assert eng.cache.pages_used == 0
    for r in lost:
        eng.submit(r)
    eng.run_until_drained()
    assert {r["id"]: r["tokens"] for r in eng.completed} == want


def test_resident_requires_greedy():
    with pytest.raises(ValueError, match="resident_k"):
        EngineConfig(resident_k=0)
    with pytest.raises(ValueError, match="greedy"):
        EngineConfig(resident_k=2, temperature=0.5)


def test_ngram_index_matches_rescan_draft():
    """The incremental per-slot n-gram index drafts EXACTLY what the
    O(L)-rescan draft_tokens drafts, under randomized histories and
    incremental extension — the acceptance dynamics of r03 are
    pinned, not approximately preserved."""
    from distributed_training_tpu.serving.engine import (
        NgramIndex, draft_tokens)

    rng = np.random.default_rng(23)
    for trial in range(20):
        n = int(rng.integers(1, 4))
        hist = list(rng.integers(0, 5, size=int(rng.integers(1, 9))))
        idx = NgramIndex(n)
        for t in hist:
            idx.append(int(t))
        for _ in range(30):
            t = int(rng.integers(0, 5))  # tiny vocab → many repeats
            hist.append(t)
            idx.append(t)
            m = int(rng.integers(0, 7))
            h = np.asarray(hist, np.int32)
            assert idx.draft(m).tolist() == \
                draft_tokens(h, m, n).tolist(), (trial, n, hist, m)


def test_resident_sharded_engine_matches_replicated(serving_model):
    """The SPMD pin: the resident while_loop under the committed
    dp×tp decode plan (manual-dp shard_map, per-group trip counts
    free to differ) decodes token-for-token what the unsharded
    engine decodes, with zero post-warmup recompiles."""
    import dataclasses

    from distributed_training_tpu.parallel.planner import load_plan
    from distributed_training_tpu.runtime import MeshSpec, build_mesh
    from distributed_training_tpu.serving.disagg import (
        engine_config_for_plan, place_params)

    model, params = serving_model
    plan = load_plan("serving_8dev_cpu_decode")
    spec = MeshSpec(**{a: plan.mesh.get(a, 1)
                       for a in ("pp", "dp", "fsdp", "sp", "tp")})
    mesh = build_mesh(spec, jax.devices()[:spec.total])
    eng = Engine(model, place_params(params, mesh, plan),
                 engine_config_for_plan(plan, spec_k=2,
                                        resident_k=4),
                 mesh=mesh)
    counts = eng.warmup()
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, 256, size=int(rng.integers(3, 20)))
               .astype(np.int32) for _ in range(8)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"s{i}", prompt=p, max_new_tokens=6))
    bursts = 0
    while not eng.idle:
        bursts += "resident_steps_per_launch" in eng.step()
    sharded = _drain_clean(eng)
    assert eng.compile_counts() == counts, \
        "sharded resident decode changed a traced shape"
    assert bursts > 0
    ref = Engine(model, params, dataclasses.replace(
        eng.cfg,
        num_pages=eng.dp_groups * (eng.cfg.num_pages - 1) + 1))
    for i, p in enumerate(prompts):
        ref.submit(Request(id=f"s{i}", prompt=p, max_new_tokens=6))
    want = _drain_clean(ref)
    assert {k: v["tokens"] for k, v in sharded.items()} == \
        {k: v["tokens"] for k, v in want.items()}


def test_int8_weight_only_parity(tiny_model):
    """Int8 weight-only serving: per-channel scales bound the
    dequant error tightly enough that the greedy stream is IDENTICAL
    to fp32 on this model, and the logits the dequantized weights
    produce stay within quantization tolerance of fp32 logits."""
    from distributed_training_tpu.serving.disagg import (
        _QUANT_AXES, quantize_params_int8, quantized_weight_bytes)

    model, params = tiny_model
    qparams = quantize_params_int8(params)
    sizes = quantized_weight_bytes(qparams)
    assert sizes["int8"] < 0.5 * sizes["fp32"]
    prompts = _ragged_prompts()

    def run(p, rk, sk):
        eng = _engine(model, p, resident_k=rk, spec_k=sk,
                      num_pages=96)
        eng.warmup()
        for i, pr in enumerate(prompts):
            eng.submit(Request(id=f"q{i}", prompt=pr,
                               max_new_tokens=10))
        eng.run_until_drained()
        return {r["id"]: r["tokens"] for r in eng.completed}, eng

    fp, efp = run(params, 1, 1)
    q, eq = run(qparams, 4, 4)
    assert q == fp, "int8 argmax parity broken"
    # The engine's weight-residency gauge sees the shrink.
    assert eq.weight_bytes < efp.weight_bytes
    # Logits tolerance: dequantized weights through the SAME forward
    # stay within per-channel quantization error of fp32.
    deq = jax.tree.map(
        lambda lf: (np.asarray(lf["qw"], np.float32) * lf["scale"]
                    if isinstance(lf, dict) and "qw" in lf else lf),
        qparams, is_leaf=lambda lf: isinstance(lf, dict)
        and "qw" in lf)
    ids = jnp.asarray([prompts[2].tolist()], jnp.int32)
    lf, _ = model.apply(params, ids)
    lq, _ = model.apply(deq, ids)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lf),
                               atol=0.15)
    assert len(_QUANT_AXES) == 6  # attn qkv/o + mlp in/out


def test_int8_weight_store_stamp_and_refusals(tiny_model, tmp_path):
    """Provenance: export stamps ``quantization: int8`` and the
    WeightStore surfaces it; an unknown stamp refuses to load
    (dequant-at-compute must know the scheme, not guess it)."""
    from distributed_training_tpu.serving.disagg import (
        WeightStore, quantize_params_int8)

    model, params = tiny_model
    qparams = quantize_params_int8(params)
    path = _artifact(tmp_path, qparams, {"quantization": "int8"})
    store = WeightStore(path)
    assert store.quantization == "int8"
    leaf = store.params["attn"]["wq"] if "attn" in store.params \
        else jax.tree.leaves(
            store.params,
            is_leaf=lambda x: isinstance(x, dict) and "qw" in x)[0]
    assert isinstance(leaf, dict) and leaf["qw"].dtype == np.int8
    bad = _artifact(tmp_path, params, {"quantization": "int4"})
    with pytest.raises(ValueError, match="quantization"):
        WeightStore(bad)


def test_int8_decode_plan_objective_and_hbm_credit():
    """The committed int8 decode plan: resolved with quant='int8',
    and the quantization credit is WHY its layout exists — the same
    HBM budget that forces fp32 to shard weights over tp admits the
    int8 store at dp-only (zero decode collectives)."""
    from distributed_training_tpu.parallel.planner import (
        PLAN_TARGETS, load_plan, score_candidate)

    plan = load_plan("serving_8dev_cpu_decode_int8")
    assert plan.inputs.get("quant") == "int8"
    assert plan.inputs.get("objective") == "decode"
    assert plan.mesh.get("dp", 1) == 8
    fp32 = load_plan("serving_8dev_cpu_decode")
    assert fp32.inputs.get("quant", "none") == "none"
    # Re-scoring the int8 winner's layout under the fp32 target
    # must be HBM-infeasible: the credit is load-bearing.
    target = PLAN_TARGETS["serving_8dev_cpu_decode"]
    from distributed_training_tpu.parallel.planner import Candidate
    cand = Candidate(
        pp=1, dp=8, fsdp=1, sp=1, tp=1, remat="none",
        batch_per_shard=plan.batch_per_shard)
    assert score_candidate(target, cand)["feasible"] is False
    itarget = PLAN_TARGETS["serving_8dev_cpu_decode_int8"]
    assert score_candidate(itarget, cand)["feasible"] is True
    with pytest.raises(ValueError, match="quant"):
        import dataclasses
        dataclasses.replace(itarget, quant="int4")


def test_serving_resident_audit_target_registered_and_pinned():
    from distributed_training_tpu.analysis import targets

    t = targets.TARGETS.get("serving_resident_planned")
    assert t is not None, ("serving resident audit target missing — "
                           "conf/plans/serving_8dev_cpu_decode.json "
                           "gone?")
    assert t.kind == "serving"
    assert t.serving_objective == "resident"
    assert "SPMD001" in t.pin_zero


def test_resident_metrics_gauges(tiny_model, tmp_path):
    """The r04 gauge additions on /metrics, additive next to the
    pinned schema: host syncs per token (→ 1/K), resident steps per
    launch, and the weight-store residency bytes."""
    import urllib.request

    from distributed_training_tpu.telemetry import (
        MetricsServer, Telemetry, install, uninstall)

    model, params = tiny_model
    tel = Telemetry(events_jsonl=str(tmp_path / "events.jsonl"))
    install(tel)
    try:
        ms = MetricsServer(0, telemetry=tel)
        assert ms.start() is not None
        eng = _engine(model, params, resident_k=4, num_pages=96)
        eng.submit(Request(id="m0",
                           prompt=np.asarray([1, 2, 3], np.int32),
                           max_new_tokens=8))
        eng.run_until_drained()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{ms.port}/metrics",
            timeout=10).read().decode()
        for gauge in SERVING_GAUGES + (
                "dtt_serving_resident_steps_per_launch",):
            assert f"\n{gauge} " in "\n" + body, \
                f"{gauge} missing from /metrics"
        ms.stop()
    finally:
        uninstall()
        tel.close()


@pytest.mark.parametrize("over, programs", [
    (dict(resident_k=4, spec_k=2),
     ["serving_resident_decode", "serving_prefill_batch",
      "serving_seed", "serving_cow"]),
    (dict(spec_k=2),
     ["serving_spec_decode", "serving_prefill_batch", "serving_cow"]),
    (dict(prefix_sharing=False),
     ["serving_decode", "serving_prefill_batch"]),
], ids=["resident", "spec", "per_token"])
def test_engine_reports_paged_form_per_program(tiny_model, tmp_path,
                                               over, programs):
    """``Engine.paged_forms`` and the ``serving_warmup`` record name
    the form every compiled program took, under the programs' trace
    names: what the rule gives for the shapes the program hands
    ``paged_attention_chunk``, ``None`` where a program reads no pool
    through it. ``compile_counts`` keeps its keys."""
    from distributed_training_tpu.ops.paged_attention import (
        chunk_form)
    from distributed_training_tpu.telemetry import (
        Telemetry, install, uninstall)

    model, params = tiny_model
    c = model.cfg
    records: list = []
    tel = install(Telemetry(events_jsonl=str(tmp_path / "ev.jsonl")))
    tel.add_observer(records.append)
    try:
        eng = _engine(model, params, **over)
        assert eng.paged_forms() == {}      # nothing traced yet
        counts = eng.warmup()
    finally:
        uninstall()
        tel.close()
    forms = eng.paged_forms()
    assert list(forms) == programs
    assert set(counts) == {p.removeprefix("serving_").replace(
        "resident_decode", "decode").replace("spec_decode", "decode")
        for p in programs}

    def rule(B, S):
        ec = eng.cfg
        return chunk_form(
            (B, S, c.n_heads, c.head_dim),
            (c.n_kv_heads, ec.num_pages, ec.page_size, c.head_dim),
            (B, ec.max_seq_len // ec.page_size), 4)

    want = {"serving_resident_decode": rule(4, eng.cfg.spec_k),
            "serving_spec_decode": rule(4, eng.cfg.spec_k),
            "serving_decode": rule(4, 1),
            "serving_prefill_batch": rule(eng.prefill_local,
                                          eng.cfg.prefill_chunk),
            "serving_seed": None, "serving_cow": None}
    assert forms == {p: want[p] for p in programs}
    warm = [r for r in records if r["kind"] == "serving_warmup"]
    assert len(warm) == 1
    assert [(p["program"], p["paged_form"])
            for p in warm[0]["programs"]] == list(forms.items())
    # What says that the stored layout engaged: the pools' shapes and
    # bytes, and each program's temporaries beside them. Two kv heads
    # of 16 are a quarter of one 128-lane tile.
    ec = eng.cfg
    assert warm[0]["pool_shapes"] == [
        [1, c.n_layers, ec.num_pages, ec.page_size, 128]] * 2
    assert warm[0]["pool_bytes"] == (
        ec.num_pages * ec.page_size * c.n_layers * 2 * 2 * 16 * 4)
    assert warm[0]["pool_bytes_tiled"] == 4 * warm[0]["pool_bytes"]
    assert all(isinstance(p["temp_bytes"], int)
               for p in warm[0]["programs"])
    # (XLA's CPU copy-on-write program does hold a copy.)
    assert all(p["temp_bytes"] < warm[0]["pool_bytes_tiled"]
               for p in warm[0]["programs"] if p["paged_form"])


@functools.lru_cache(maxsize=None)
def _wide_head_model():
    """Two kv heads of 64, a 128-lane tile: with pages of 16 a page of
    a layer is 8 KB, and ``chunk_form`` takes the ragged form for a
    decode program's few queries (a page of the other tiny models is
    under 1 KB, where a page's DMAs outweigh its bytes)."""
    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=2,
        n_kv_heads=2, max_seq_len=128, dtype="float32",
        param_dtype="float32", pos_encoding="rope",
        tie_embeddings=False)
    model = Transformer(cfg)
    return model, model.init(jax.random.PRNGKey(0))


_RAGGED_ENGINE = dict(page_size=16, num_pages=64, max_seq_len=64)


@pytest.mark.parametrize("cadence", ["plain", "spec4", "resident8"])
def test_engine_in_the_ragged_form_emits_what_the_gather_form_emits(
        monkeypatch, cadence):
    """A tiny dense engine whose decode program the rule gives the
    ragged form (the kernel ``dtt_paged_decode``, interpreted here)
    streams token for token what the same engine streams with
    ``chunk_form`` forced to ``"gather"``, on every decode cadence;
    ``Engine.paged_forms()`` names the form, and the decode step
    records count the pages the kernel walked beside the pages the
    gather form copies."""
    from distributed_training_tpu.ops import paged_attention as pa

    model, params = _wide_head_model()
    over = {**_RAGGED_ENGINE, **_LAYOUT_CADENCES[cadence]}
    decode = ("serving_resident_decode" if cadence == "resident8" else
              "serving_spec_decode" if cadence == "spec4" else
              "serving_decode")
    prompts = _ragged_prompts()

    def run():
        eng = _engine(model, params, **over)
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=12))
        records = []
        while not eng.idle:
            records.append(eng.step())
        return eng, records, {r["id"]: r["tokens"]
                              for r in eng.completed}

    eng, records, done = run()
    # (A prompt chunk of 8 is few queries too: the rule gives it the
    # same form. A real chunk's hundreds of rows are never offered it.)
    assert eng.paged_forms()[decode] == "ragged"
    steps = [r for r in records if r["op"] == "decode"]
    # Every slot's whole table row a layer an iteration, against the
    # pages that hold what a live slot's query sees: at most 32
    # positions here, two pages of a row's four.
    P, L, B = 64 // 16, model.cfg.n_layers, eng.cfg.max_batch
    assert steps and all(
        r["kv_pages_tabled"] == r["iters"] * B * L * P
        and L * r["slot_iters"] <= r["kv_pages_walked"]
        <= 2 * L * r["slot_iters"] for r in steps)
    monkeypatch.setattr(pa, "chunk_form", lambda *a, **kw: "gather")
    forced, forced_records, want = run()
    assert forced.paged_forms()[decode] == "gather"
    assert not any("kv_pages_walked" in r for r in forced_records)
    assert done == want and all(len(t) == 12 for t in done.values())


def test_a_lone_requests_walk_is_its_positions_pages():
    """``kv_pages_walked`` of the one-token cadence, one request alone:
    a decode launch at position ``p`` walks ``p // page_size + 1``
    pages a layer, and tables every slot's whole row."""
    model, params = _wide_head_model()
    eng = _engine(model, params, **_RAGGED_ENGINE)
    eng.submit(Request(id="r", prompt=np.arange(13, dtype=np.int32),
                       max_new_tokens=8))
    records = []
    while not eng.idle:
        records.append(eng.step())
    steps = [r for r in records if r["op"] == "decode"]
    # The first token comes with the prompt; seven launches at
    # positions 13..19, the page boundary at 16.
    assert [r["kv_pages_walked"] for r in steps] == [
        2 * (p // 16 + 1) for p in range(13, 20)]
    assert {r["kv_pages_tabled"] for r in steps} == {4 * 2 * 4}


def test_serving_r04_ledger_committed_and_coherent():
    """SERVING_r04.json: the resident-decode and int8 acceptance
    gates stay machine-checked — >= 1.5x the r03 saturated tok/s in
    the same-run comparison, host syncs bounded by tokens/K +
    completions, zero recompiles, greedy parity, and int8 riding the
    same run with argmax parity asserted."""
    import os

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    with open(os.path.join(root, "SERVING_r04.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "SERVING_r03.json")) as f:
        r03 = json.load(f)
    steady = doc["steady"]
    assert steady["recompiles_after_warmup"] == 0
    assert steady["greedy_matches_full_context"] is True
    assert steady["resident_k"] > 1
    sat = doc["saturated"]
    assert sat["speedup_vs_per_step_same_run"] > 1.0
    assert sat["tokens_per_s"] >= 1.5 * \
        r03["saturated"]["tokens_per_s"]
    # Host syncs: once per burst, so bounded by tokens/K plus one
    # fetch per completion-truncated burst.
    hs = sat["host_syncs"]
    assert hs <= sat["decode_tokens"] / sat["resident_k"] + \
        sat["completions"]
    assert sat["per_step_same_mesh"]["tokens_per_s"] > 0
    cmp_block = doc["compared_to"]
    assert cmp_block["revision"] == "r03"
    assert cmp_block["tokens_per_s"] == \
        r03["saturated"]["tokens_per_s"]
    q = doc["int8"]
    assert q["argmax_parity"] is True  # vs dequantized reference
    assert q["stream_match_fraction_vs_fp32"] >= 0.9
    assert q["weight_bytes"] < 0.5 * q["weight_bytes_fp32"]
    assert q["tokens_per_s"] > 0
    assert q["plan"]["mesh"] == {"dp": 8}
    pre = doc["preemption"]
    assert pre["tokens_match_steady_storm"] is True
    assert 0 < pre["goodput"] <= 1
    assert doc["plan"]["mesh"]["dp"] > 1


def test_serving_ledger_committed_and_coherent():
    """SERVING_r01.json: the acceptance criteria stay machine-checked
    (>= 20 concurrent, zero recompiles, a goodput figure for the
    supervised preemption, token-transparent restart)."""
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SERVING_r01.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["steady"]["max_in_flight"] >= 20
    assert doc["steady"]["recompiles_after_warmup"] == 0
    assert doc["steady"]["tokens_per_s"] > 0
    for p in ("p50", "p99"):
        assert doc["steady"]["ttft_s"][p] > 0
        assert doc["steady"]["per_token_latency_s"][p] > 0
    pre = doc["preemption"]
    assert pre["restarts"] >= 1
    assert pre["outcomes"][0] == "preempted"
    assert pre["outcomes"][-1] == "completed"
    assert 0 < pre["goodput"] <= 1
    assert pre["tokens_match_steady_storm"] is True
    assert doc["plan"]["name"] == "serving_8dev_cpu_decode"


# ---------------------------------------------------------------------------
# prefix sharing: refcounted COW pages, prefix index, sessions (r05)
# ---------------------------------------------------------------------------


def test_refcount_invariants_random_join_fork_retain_evict_free():
    """The PR-13 leak invariant extended to REFCOUNTS: any order of
    join / grow / fork (attach) / retain (rename) / free keeps every
    group's distinct-allocated + free == usable exact, a shared page
    survives until its LAST owner releases it, allocations never
    bleed across shards, and a full drain returns every group to
    zero — no leak, no double-free."""
    from distributed_training_tpu.serving.kv_cache import (
        PagedCacheConfig, PagedKVCache)

    G = 3
    cfg = PagedCacheConfig(n_layers=2, n_kv_heads=2, head_dim=16,
                           page_size=8, num_pages=24, max_seq_len=96,
                           dp_groups=G)
    cache = PagedKVCache(cfg)
    rng = np.random.default_rng(31)
    live: dict = {}   # key -> (group, n_tokens)
    next_id = 0
    for _ in range(800):
        # Invariant sweep: what the cache thinks is allocated per
        # group must equal the union of live tables (forked pages
        # counted ONCE), and the free list must cover the rest.
        for g in range(G):
            union = set()
            for key, (kg, _n) in live.items():
                if kg == g:
                    union.update(cache._tables[key])
            assert cache.pages_used_in(g) == len(union)
            assert cache.pages_used_in(g) + \
                cache.free_pages_in(g) == cfg.usable_pages
            # No cross-shard bleed: refcounted pages in g are
            # exactly the allocated ones.
            assert set(cache._refs[g]) == union
        op = int(rng.integers(0, 5))
        if op == 0 and len(live) < 10:
            g = int(rng.integers(0, G))
            cache.join(next_id, group=g)
            live[next_id] = (g, 0)
            next_id += 1
        elif op == 1 and live:
            key = list(live)[int(rng.integers(0, len(live)))]
            g, n = live[key]
            want = min(n + int(rng.integers(1, 20)),
                       cfg.max_seq_len)
            if cache.ensure(key, want):
                cache.advance(key, want - n)
                live[key] = (g, want)
        elif op == 2 and live:
            # Fork: attach a committed page-aligned prefix of a live
            # sequence to a fresh one (refcounts go up, no pages
            # move).
            donors = [k for k, (_g, n) in live.items()
                      if n >= cfg.page_size]
            if donors:
                donor = donors[int(rng.integers(0, len(donors)))]
                g, n = live[donor]
                j = int(rng.integers(1, n // cfg.page_size + 1))
                cache.join(next_id, group=g)
                cache.attach(next_id,
                             tuple(cache._tables[donor][:j]),
                             j * cfg.page_size)
                live[next_id] = (g, j * cfg.page_size)
                next_id += 1
        elif op == 3 and live:
            # Retain: park a sequence under a session-style key —
            # pages survive the identity change untouched.
            key = list(live)[int(rng.integers(0, len(live)))]
            if not (isinstance(key, tuple) and key[0] == "sess"):
                cache.rename(key, ("sess", key))
                live[("sess", key)] = live.pop(key)
        elif op == 4 and live:
            key = list(live)[int(rng.integers(0, len(live)))]
            cache.free(key)
            del live[key]
    for key in list(live):
        cache.free(key)
    assert cache.pages_used == 0
    for g in range(G):
        assert cache.free_pages_in(g) == cfg.usable_pages
        assert not cache._refs[g]
        assert cache.shared_pages_in(g) == 0


def test_prefix_index_is_dp_group_local():
    """No cross-group sharing: a prefix registered in group 0 never
    matches admission into group 1 (each dp shard's pool is its own
    physical memory — a cross-group page id would read another
    shard's bytes)."""
    from distributed_training_tpu.serving.kv_cache import (
        PagedCacheConfig, PagedKVCache)

    cfg = PagedCacheConfig(n_layers=2, n_kv_heads=2, head_dim=16,
                           page_size=8, num_pages=16, max_seq_len=64,
                           dp_groups=2)
    cache = PagedKVCache(cfg)
    toks = np.arange(16, dtype=np.int32)
    cache.join("a", group=0)
    assert cache.ensure("a", 16)
    cache.advance("a", 16)
    cache.register_prefix("a", toks)
    pages, m = cache.match_prefix(0, toks)
    assert m == 2 and len(pages) == 2
    assert cache.match_prefix(1, toks) == ((), 0)
    # Sub-page prefixes are never indexed either (page-alignment
    # rule): 7 of the same leading tokens match nothing.
    assert cache.match_prefix(0, toks[:7]) == ((), 0)
    cache.free("a")
    # Freeing the last owner invalidates the index entries.
    assert cache.match_prefix(0, toks) == ((), 0)
    assert cache.pages_used == 0


@pytest.mark.parametrize("shape", ["tiny_gqa16", "odd64"])
def test_cow_fork_token_parity_diverging_mid_page(shape):
    """Two requests share a prompt header and diverge MID-PAGE: the
    follower attaches the shared full pages, prefills only its tail,
    and both streams are token-identical to fully independent
    prefill (the full-context reference). The page-aligned twin then
    pins the actual copy-on-write: a full-prefix match admits with
    zero prefill tokens and forks the shared boundary page on its
    first decode write. Also where a token's row of the pool ends in
    a half-empty tile (``odd64``): a page copy moves rows as they are
    stored."""
    model, params, _dense = _layout_model(shape)
    eng = _engine(model, params)
    eng.warmup()
    rng = np.random.default_rng(47)
    common = rng.integers(0, 256, size=12).astype(np.int32)
    pa = np.concatenate(
        [common, rng.integers(0, 256, size=4).astype(np.int32)])
    pb = np.concatenate(
        [common, rng.integers(0, 256, size=4).astype(np.int32)])
    eng.submit(Request(id="a", prompt=pa, max_new_tokens=6))
    for _ in range(3):   # prefill a fully (registers its pages)
        eng.step()
    pt0 = eng.prefill_tokens_computed
    eng.submit(Request(id="b", prompt=pb, max_new_tokens=6))
    eng.run_until_drained()
    done = {r["id"]: r["tokens"] for r in eng.completed}
    assert done["a"] == _full_context_greedy(model, params, pa, 6)
    assert done["b"] == _full_context_greedy(model, params, pb, 6)
    # b shared common's one full page (8 of 12 tokens) and computed
    # only the 8 uncovered ones.
    assert eng.prefix_stats["hit_tokens"] >= 8
    assert eng.prefill_tokens_computed - pt0 == len(pb) - 8
    # Page-aligned twin: full match, zero prefill, COW on write.
    p16 = rng.integers(0, 256, size=16).astype(np.int32)
    eng.submit(Request(id="x", prompt=p16, max_new_tokens=10))
    for _ in range(4):
        eng.step()
    pt0 = eng.prefill_tokens_computed
    eng.submit(Request(id="y", prompt=p16.copy(),
                       max_new_tokens=4))
    eng.run_until_drained()
    done = {r["id"]: r["tokens"] for r in eng.completed}
    assert eng.prefill_tokens_computed == pt0
    assert eng.prefix_stats["cow_pages"] >= 1
    assert done["y"] == _full_context_greedy(model, params, p16, 4)
    assert done["x"] == _full_context_greedy(model, params, p16, 10)
    # Sharing is bookkeeping only: everything drains back to zero.
    assert eng.cache.pages_used == 0


def test_session_reattach_zero_prefill_parity(tiny_model):
    """Chat sessions: the first turn retains its pages under the
    session key; an EXACT follow-up (prompt == retained history)
    re-attaches with ZERO prefill launches, an extended follow-up
    prefills only the unseen suffix — both token-identical to the
    full-context reference."""
    model, params = tiny_model
    eng = _engine(model, params)
    eng.warmup()
    rng = np.random.default_rng(53)
    p1 = rng.integers(0, 256, size=12).astype(np.int32)
    eng.submit(Request(id="t1", prompt=p1, max_new_tokens=4,
                       session="s"))
    eng.run_until_drained()
    t1 = next(r for r in eng.completed if r["id"] == "t1")["tokens"]
    assert len(eng.sessions) == 1
    assert eng.cache.pages_used > 0   # retained, not freed
    hist = np.concatenate([p1, np.asarray(t1, np.int32)])
    pl0, pt0 = eng.prefill_launches, eng.prefill_tokens_computed
    eng.submit(Request(id="t2", prompt=hist, max_new_tokens=4,
                       session="s"))
    eng.run_until_drained()
    t2 = next(r for r in eng.completed if r["id"] == "t2")["tokens"]
    assert eng.prefill_launches == pl0, \
        "exact resume must not launch a prefill program"
    assert eng.prefill_tokens_computed == pt0
    assert t2 == _full_context_greedy(model, params, hist, 4)
    # Extended turn: history + new user tokens → prefill only those.
    hist2 = np.concatenate(
        [hist, np.asarray(t2, np.int32),
         rng.integers(0, 256, size=3).astype(np.int32)])
    eng.submit(Request(id="t3", prompt=hist2, max_new_tokens=4,
                       session="s"))
    eng.run_until_drained()
    t3 = next(r for r in eng.completed if r["id"] == "t3")["tokens"]
    assert t3 == _full_context_greedy(model, params, hist2, 4)
    assert eng.prefix_stats["session_resumes"] == 2
    assert len(eng.sessions) == 1
    # A mismatched prompt DROPS the stale session and prefills from
    # scratch (no silent wrong-context reuse).
    other = rng.integers(0, 256, size=6).astype(np.int32)
    eng.submit(Request(id="t4", prompt=other, max_new_tokens=2,
                       session="s"))
    eng.run_until_drained()
    t4 = next(r for r in eng.completed if r["id"] == "t4")["tokens"]
    assert t4 == _full_context_greedy(model, params, other, 2)
    eng._drop_session("s")
    assert eng.cache.pages_used == 0


def test_subpage_prefix_never_shares(tiny_model):
    """Page-alignment edge: prompts shorter than one page are never
    indexed, so an identical sub-page prompt admits with zero hits
    (sharing granularity is the page, by design)."""
    model, params = tiny_model
    eng = _engine(model, params)
    eng.warmup()
    rng = np.random.default_rng(59)
    p6 = rng.integers(0, 256, size=6).astype(np.int32)
    eng.submit(Request(id="m1", prompt=p6, max_new_tokens=3,
                       session="keep"))
    eng.run_until_drained()
    eng.submit(Request(id="m2", prompt=p6.copy(),
                       max_new_tokens=3))
    eng.run_until_drained()
    assert eng.prefix_stats["hit_tokens"] == 0
    m1 = next(r for r in eng.completed if r["id"] == "m1")["tokens"]
    m2 = next(r for r in eng.completed if r["id"] == "m2")["tokens"]
    assert m1 == m2 == _full_context_greedy(model, params, p6, 3)


def test_preempt_keeps_sessions_skippable_and_free_list_clean(
        tiny_model):
    """Eviction policy: preempt() drops in-flight work but RETAINED
    sessions survive (their pages are refcount-held, not slot-held),
    the free list stays exact, and the next incarnation both replays
    the lost requests token-identically and zero-prefill-resumes the
    session."""
    model, params = tiny_model
    eng = _engine(model, params)
    eng.warmup()
    rng = np.random.default_rng(61)
    p1 = rng.integers(0, 256, size=12).astype(np.int32)
    eng.submit(Request(id="t1", prompt=p1, max_new_tokens=4,
                       session="s"))
    eng.run_until_drained()
    t1 = next(r for r in eng.completed if r["id"] == "t1")["tokens"]
    held = eng.cache.pages_used
    assert held > 0
    prompts = {f"r{i}": rng.integers(0, 256, size=10).astype(
        np.int32) for i in range(3)}
    for rid, p in prompts.items():
        eng.submit(Request(id=rid, prompt=p, max_new_tokens=5))
    eng.step()
    eng.step()
    lost = eng.preempt()
    assert {r.id for r in lost} == set(prompts)
    # Sessions survive preemption; in-flight pages all released.
    assert len(eng.sessions) == 1
    assert eng.cache.pages_used == held
    g = eng.sessions["s"]["group"]
    assert eng.cache.pages_used_in(g) + eng.cache.free_pages_in(g) \
        == eng.cache.cfg.usable_pages
    for r in lost:
        eng.submit(r)
    eng.run_until_drained()
    for rid, p in prompts.items():
        got = next(r for r in eng.completed
                   if r["id"] == rid)["tokens"]
        assert got == _full_context_greedy(model, params, p, 5)
    # The retained session still resumes with zero prefill.
    hist = np.concatenate([p1, np.asarray(t1, np.int32)])
    pl0 = eng.prefill_launches
    eng.submit(Request(id="t2", prompt=hist, max_new_tokens=2,
                       session="s"))
    eng.run_until_drained()
    assert eng.prefill_launches == pl0
    t2 = next(r for r in eng.completed if r["id"] == "t2")["tokens"]
    assert t2 == _full_context_greedy(model, params, hist, 2)
    eng._drop_session("s")
    assert eng.cache.pages_used == 0


def test_int8_plan_spends_hbm_credit_on_kv_pool():
    """ROADMAP item 4 remainder: the committed int8 plan's provenance
    prices the residual HBM credit as KV pages (kv_pool_tokens >
    the minimal slots×seq_len pool) and the engine geometry actually
    spends it — a BIGGER per-group pool than the fp32 plan's minimal
    sizing, same program shapes otherwise."""
    from distributed_training_tpu.parallel.planner import load_plan
    from distributed_training_tpu.serving.disagg import (
        engine_config_for_plan)

    plan = load_plan("serving_8dev_cpu_decode_int8")
    score = plan.provenance["score"]
    assert score["kv_pool_tokens"] >= \
        plan.batch_per_shard * plan.seq_len
    assert score["kv_pool_tokens"] == score["kv_capacity_tokens"]
    assert score["kv_pool_gib_delta"] > 0
    cfg_q = engine_config_for_plan(plan)
    dp = plan.mesh.get("dp", 1)
    minimal = (plan.batch_per_shard // dp) \
        * -(-plan.seq_len // cfg_q.page_size) + 1
    assert cfg_q.num_pages > minimal
    # Plans without the provenance field keep the minimal pool —
    # pre-r05 plan files stay valid.
    base = load_plan("serving_8dev_cpu_decode")
    cfg_b = engine_config_for_plan(base)
    dp_b = base.mesh.get("dp", 1)
    assert cfg_b.num_pages == (base.batch_per_shard // dp_b) \
        * -(-base.seq_len // cfg_b.page_size) + 1


def test_serving_r05_ledger_committed_and_coherent():
    """SERVING_r05.json: the prefix-sharing acceptance gates stay
    machine-checked — ≥4x fewer prefill tokens computed than the
    sharing-disabled same-run engine, byte-identical streams, zero
    recompiles, a zero-prefill-launch session re-attach, and the
    saturated-decode non-regression vs the committed r04 entry."""
    import os

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    with open(os.path.join(root, "SERVING_r05.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "SERVING_r04.json")) as f:
        r04 = json.load(f)
    assert doc["revision"] == "r05"
    steady = doc["steady"]
    assert steady["recompiles_after_warmup"] == 0
    assert steady["greedy_matches_full_context"] is True
    pre = doc["prefix"]
    assert pre["recompiles_after_warmup"] == 0
    assert pre["tokens_match_sharing_disabled"] is True
    assert pre["greedy_matches_full_context"] is True
    cmp_pre = pre["compared_to"]
    assert cmp_pre["reduction_x"] >= 4.0
    assert cmp_pre["prefill_tokens_computed"] >= \
        4 * pre["prefill_tokens_computed"]
    followers = pre["tenants"] - pre["primer_waves"]
    assert pre["prefix_hit_tokens"] >= \
        followers * pre["common_prefix_tokens"]
    assert pre["prefill_tokens_saved"] >= \
        followers * pre["common_prefix_tokens"]
    fork = pre["zero_prefill_fork"]
    assert fork["prefill_tokens_computed"] == 0
    assert fork["cow_pages"] >= 1
    assert fork["tokens_match_retained_twin"] is True
    ses = doc["session"]
    assert ses["zero_prefill_resume"] is True
    assert ses["resume_exact"]["prefill_launches"] == 0
    assert ses["resume_exact"]["prefill_tokens_computed"] == 0
    assert ses["resume_extended"]["prefill_tokens_computed"] <= \
        ses["resume_extended"]["prompt_tokens"] \
        - ses["resume_exact"]["prompt_tokens"] \
        - ses["resume_exact"]["new_tokens"] + 1
    assert ses["session_resumes"] >= 2
    assert ses["tokens_match_full_context"] is True
    cmp_block = doc["compared_to"]
    assert cmp_block["revision"] == "r04"
    assert cmp_block["tokens_per_s"] == \
        r04["saturated"]["tokens_per_s"]
    assert doc["saturated"]["tokens_per_s"] >= \
        0.75 * r04["saturated"]["tokens_per_s"]
    # The r04 lanes all still ride the r05 entry.
    assert doc["int8"]["argmax_parity"] is True
    assert doc["preemption"]["tokens_match_steady_storm"] is True


# ---------------------------------------------------------------------------
# SERVING_r06: request-lifecycle tracing + per-tenant observability
# ---------------------------------------------------------------------------


def _trace_collector(tmp_path):
    """Installed Telemetry + a live list of serving_trace records."""
    from distributed_training_tpu.telemetry import Telemetry, install

    recs = []
    tel = Telemetry(events_jsonl=str(tmp_path / "events.jsonl"))
    tel.add_observer(lambda r: recs.append(r)
                     if r.get("kind") == "serving_trace" else None)
    install(tel)
    return tel, recs


def test_trace_lifecycle_preempt_resubmit_finish(tiny_model,
                                                 tmp_path):
    """The full span story of one request that gets evicted mid-
    decode and retried: trace 1 closes ``outcome=preempted`` with its
    discarded tokens BEFORE the state is freed; the resubmit (same
    Request, ORIGINAL arrival) opens trace 2, whose admitted span's
    relative time covers the lost first pass, ending ``finished``.
    The record's payload keys are the pinned TRACE_KEYS schema."""
    from distributed_training_tpu.telemetry import uninstall
    from distributed_training_tpu.telemetry.serving_trace import (
        SPAN_EVENTS, TRACE_KEYS)

    model, params = tiny_model
    tel, recs = _trace_collector(tmp_path)
    try:
        eng = _engine(model, params)
        eng.submit(Request(id="tr-1",
                           prompt=np.asarray([5, 6, 7, 8], np.int32),
                           max_new_tokens=6,
                           arrival=time.monotonic()))
        for _ in range(3):  # prefill + a couple of decode steps
            eng.step()
        lost = eng.preempt()
        assert [r.id for r in lost] == ["tr-1"]
        assert len(recs) == 1
        pre = recs[0]
        assert pre["outcome"] == "preempted"
        assert pre["tokens_discarded"] == pre["new_tokens"] >= 1
        assert pre["spans"][-1]["ev"] == "preempted"
        assert pre["spans"][-1]["tokens_discarded"] == \
            pre["tokens_discarded"]

        eng.submit(lost[0])  # original arrival rides along
        eng.run_until_drained()
        assert len(recs) == 2
        fin = recs[1]
        assert fin["outcome"] == "finished"
        assert fin["id"] == "tr-1" and fin["tenant"] == "default"
        assert fin["prompt_tokens"] == 4 and fin["new_tokens"] == 6
        evs = [s["ev"] for s in fin["spans"]]
        assert evs[:3] == ["queued", "submitted", "admitted"]
        assert evs[-1] == "finished"
        assert "prefill" in evs and "decode" in evs
        assert set(evs) <= set(SPAN_EVENTS)
        # Span times are arrival-relative and monotone; the retry's
        # admission happened AFTER the first pass was discarded.
        ts = [s["t"] for s in fin["spans"][1:]]
        assert ts == sorted(ts) and min(ts) >= 0.0
        # (its ``submitted`` stamp is the RE-submission's: the queue
        # share of the wait is the retry's own, the total the whole.)
        assert fin["spans"][2]["t"] >= fin["spans"][1]["t"] \
            >= pre["spans"][-1]["t"]
        assert fin["ttft_s"] >= 0 and fin["e2e_s"] >= fin["ttft_s"]
        assert fin["queue_wait_s"] >= 0
        # Schema pin: envelope (kind, t) + exactly TRACE_KEYS.
        for rec in recs:
            assert set(rec) - {"kind", "t"} == set(TRACE_KEYS)
    finally:
        uninstall()
        tel.close()


# One engine override a cadence: every ``_run_decode_*`` path of
# ``Engine``, the one-token path greedy and sampled (a categorical draw
# over one candidate is the argmax, so its tokens are the greedy ones).
_CADENCES = {"plain": {}, "spec4": {"spec_k": 4},
             "resident8": {"resident_k": 8},
             "sampled_top1": {"temperature": 0.7, "top_k": 1}}
_cadence = pytest.mark.parametrize("cadence", sorted(_CADENCES))


@_cadence
def test_tracing_adds_no_recompiles_and_no_host_syncs(tiny_model,
                                                      tmp_path,
                                                      cadence):
    """The DTT010 story as a measured equality, in every cadence: the
    identical backlog drained with tracing ON (Telemetry installed)
    and OFF must report the SAME host-sync count and the SAME compile
    counts — span capture, the ``serving.*`` phases and the step
    record's counters are host-side bookkeeping, never a device sync —
    and the token streams stay byte-identical."""
    from distributed_training_tpu.telemetry import uninstall

    model, params = tiny_model
    rng = np.random.default_rng(7)
    backlog = [(f"b-{i}",
                rng.integers(0, 256, size=int(rng.integers(3, 9)))
                .astype(np.int32)) for i in range(5)]

    def drain(traced):
        eng = _engine(model, params, **_CADENCES[cadence])
        warm = eng.warmup()
        h0 = eng.host_syncs
        for rid, prompt in backlog:
            eng.submit(Request(id=rid, prompt=prompt,
                               max_new_tokens=5,
                               arrival=time.monotonic()))
        eng.run_until_drained()
        assert eng.compile_counts() == warm, \
            f"recompiled (traced={traced})"
        return (eng.host_syncs - h0,
                {r["id"]: r["tokens"] for r in eng.completed})

    syncs_off, toks_off = drain(traced=False)
    tel, recs = _trace_collector(tmp_path)
    try:
        syncs_on, toks_on = drain(traced=True)
    finally:
        uninstall()
        tel.close()
    assert toks_on == toks_off
    assert syncs_on == syncs_off, \
        "tracing changed the host-sync count"
    assert len(recs) == len(backlog)


@_cadence
def test_step_records_carry_phases_and_counts(tiny_model, cadence):
    """Every launching step's record splits ``dur_s`` into the five
    ``serving.*`` parts (never nested, so they sum to at most it) and
    counts where the work happens: a decode step the slots it packed
    and the loop iterations they were live in, a prefill step the
    first tokens it handed to completed prompts."""
    model, params = tiny_model
    over = _CADENCES[cadence]
    eng = _engine(model, params, num_pages=96, **over)
    eng.warmup()
    prompts = _ragged_prompts()
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"p{i}", prompt=p, max_new_tokens=9))
    recs = []
    while not eng.idle:
        recs.append(eng.step())
    assert all(r["op"] != "idle" for r in recs)
    for r, after in zip(recs, recs[1:] + [None]):
        ph = r["phase_s"]
        assert tuple(ph) == ("admit", "pack", "launch", "fetch",
                             "emit")
        assert all(v >= 0.0 for v in ph.values())
        assert sum(ph.values()) <= r["dur_s"] + 1e-5
        # A record's stretch runs from the retire before to its own:
        # it holds the dispatch of its own launch where nothing was in
        # flight then, and of the launch after where that ran ahead.
        dispatched = r["ran_ahead"] == 0 or (
            after is not None and after["ran_ahead"] == 1)
        assert (ph["launch"] > 0.0) == dispatched
    assert {r["ran_ahead"] for r in recs} == (
        {0, 1} if cadence == "resident8" else {0})
    decode = [r for r in recs if r["op"] == "decode"]
    prefill = [r for r in recs if r["op"] == "prefill"]
    assert decode and prefill
    spec_k = over.get("spec_k", 1)
    for r in decode:
        assert 1 <= r["slots_stepped"] <= eng.cfg.max_batch
        # A live iteration emits between one token and spec_k.
        assert r["tokens"] / spec_k <= r["slot_iters"] <= r["tokens"]
        assert r["slot_iters"] >= r["slots_stepped"]
        assert "first_tokens" not in r
    for r in prefill:
        assert "slot_iters" not in r and "slots_stepped" not in r
    # No shared prefix here, so every prompt completes in a prefill
    # step, and every other token is some decode step's.
    assert sum(r["first_tokens"] for r in prefill) == len(prompts)
    assert (sum(r["tokens"] for r in decode) + len(prompts)
            == sum(len(c["tokens"]) for c in eng.completed)
            == 9 * len(prompts))
    assert sum(r["host_syncs"] for r in recs) == eng.host_syncs


@_cadence
def test_engine_programs_have_distinct_stable_names(tiny_model,
                                                    cadence):
    """jax names a jitted ``functools.partial`` ``jit__unknown``; the
    engine names every program it builds, so the trace's module line,
    the compile cache's lists and compiler errors tell them apart."""
    import re

    model, params = tiny_model
    eng = _engine(model, params, **_CADENCES[cadence])
    names = [re.search(r"module @(\S+)",
                       fn.lower(*args).as_text()).group(1)
             for fn, args in ((f, (*eng._state_of(f), *a))
                              for f, a in eng._warmup_calls())]
    assert len(names) == len(eng.compile_counts()) >= 3
    assert len(set(names)) == len(names), names
    assert all(n.startswith("jit_serving_") for n in names), names
    decode = {"plain": "jit_serving_decode",
              "sampled_top1": "jit_serving_decode",
              "spec4": "jit_serving_spec_decode",
              "resident8": "jit_serving_resident_decode"}[cadence]
    assert names[0] == decode and names[-1] == "jit_serving_cow"


@_cadence
def test_stop_token_ends_a_request_in_every_cadence(tiny_model,
                                                    cadence):
    """The stop token is emitted once and nothing after it, to the
    record and to the stream alike, and the request's pages are freed:
    mid-stream, and when it is the FIRST token out of prefill (the
    request then never decodes). Every cadence ends a request through
    the one ``Engine._emit``."""
    model, params = tiny_model
    prompts = _ragged_prompts()

    def run(eos):
        eng = _engine(model, params, num_pages=96, eos_id=eos,
                      **_CADENCES[cadence])
        streams = {f"s{i}": [] for i in range(len(prompts))}
        for i, p in enumerate(prompts):
            eng.submit(Request(id=f"s{i}", prompt=p,
                               max_new_tokens=12))
            eng.add_token_listener(
                f"s{i}", lambda tok, done, out=streams[f"s{i}"]:
                out.append((tok, done)))
        eng.run_until_drained()
        assert eng.cache.pages_used == 0
        done = {r["id"]: r["tokens"] for r in eng.completed}
        for rid, toks in done.items():
            assert [t for t, _d in streams[rid]] == toks
            assert [d for _t, d in streams[rid]] == \
                [False] * (len(toks) - 1) + [True]
        return [done[f"s{i}"] for i in range(len(prompts))]

    free = run(-1)
    assert all(len(t) == 12 for t in free)
    for eos in (free[0][5], free[1][0]):
        want = [t[:t.index(eos) + 1] if eos in t else t for t in free]
        assert run(eos) == want, eos
        assert any(len(t) < 12 and t[-1] == eos for t in want)
    assert run(free[1][0])[1] == [free[1][0]]


def _run_ahead_prompts():
    rng = np.random.default_rng(31)
    return [rng.integers(0, 256, size=n).astype(np.int32)
            for n in (3, 8, 13, 21, 6)]


def _submit(eng, prompts, new, streams=None, ids=None):
    for i, p in enumerate(prompts):
        rid = ids[i] if ids else f"q{i}"
        eng.submit(Request(id=rid, prompt=p, max_new_tokens=new[i]))
        if streams is not None:
            eng.add_token_listener(
                rid, lambda tok, done, out=streams.setdefault(rid, []):
                out.append(tok))


def _step_until_flying(eng, least=3):
    """A few steps in, stopped where a launch is dispatched and not
    yet retired (the state every outside caller must settle)."""
    for _ in range(least):
        eng.step()
    while eng._flying is None:
        eng.step()
    assert not eng.idle and eng.in_flight


def _same_params(params):
    return jax.tree.map(lambda x: jnp.array(np.asarray(x)), params)


# What happens while a launch is in flight, by case: the engine's
# overrides, the tokens asked a request, and what the test does to the
# run-ahead engine mid-storm (None: nothing, it just drains).
_RUN_AHEAD_CASES = {
    "stop_token_mid_burst": (dict(resident_k=4), [12] * 5, None),
    "spec_k_4": (dict(resident_k=4, spec_k=4), [12] * 5, None),
    "budgets_end_mid_burst": (dict(resident_k=4), [1, 6, 7, 9, 11],
                              None),
    # Two slots, 15 usable pages of 4: the longest pair wants 10 + 8.
    "pool_forces_short_budgets": (
        dict(resident_k=8, max_batch=2, page_size=4, num_pages=16,
             max_seq_len=40), [16] * 5, "watch_pool"),
    "chunked_prompt_joins_mid_flight": (
        dict(resident_k=4, prefill_chunk=4), [9] * 5, "late_prompt"),
    "preempt": (dict(resident_k=4), [12] * 5, "preempt"),
    "drain": (dict(resident_k=4), [12] * 5, "drain"),
    "swap_weights": (dict(resident_k=4), [12] * 5, "swap"),
    "export_in_flight": (dict(resident_k=4), [12] * 5, "export"),
}


@pytest.mark.parametrize("case", sorted(_RUN_AHEAD_CASES))
def test_run_ahead_is_the_one_token_cadence_token_for_token(
        tiny_model, case):
    """The engine that dispatches launch n+1 before it fetches launch
    n (the resident cadence: slot state on the device, the host packing
    from a projection) emits what the engine that retires every launch
    at once emits, token for token and each token once: with slots that
    meet the stop token mid-burst, at ``spec_k`` 4, with requests that
    end mid-burst, on a pool too small for the budgets, with a prompt
    of several chunks admitted while a burst is in flight, and with
    ``preempt``, ``drain``, ``swap_weights`` and ``export_in_flight``
    called while a launch is in flight."""
    model, params = tiny_model
    over, new, act = _RUN_AHEAD_CASES[case]
    prompts = _run_ahead_prompts()
    late = np.asarray(([7, 3, 9, 1] * 4)[:14], np.int32)
    plain = {k: v for k, v in over.items()
             if k not in ("resident_k", "spec_k")}
    eos = -1
    if case == "stop_token_mid_burst":
        free = _engine(model, params, num_pages=96, **plain)
        _submit(free, prompts, new)
        free.run_until_drained()
        # Position 5 with K=4 is the second token of a second burst.
        eos = next(r for r in free.completed
                   if r["id"] == "q2")["tokens"][5]

    def drained(eng, streams):
        eng.run_until_drained(max_steps=2000)
        assert eng.cache.pages_used == 0 and eng._flying is None
        done = {r["id"]: r["tokens"] for r in eng.completed}
        for rid, toks in streams.items():
            assert toks == done[rid], f"{rid} streamed twice or not"
        return done

    ref = _engine(model, params, eos_id=eos,
                  **{"num_pages": 96, **plain})
    ref_streams: dict = {}
    _submit(ref, prompts, new, ref_streams)
    if act == "late_prompt":
        ref.submit(Request(id="late", prompt=late, max_new_tokens=9))
    want = drained(ref, ref_streams)
    if eos >= 0:
        assert any(t[-1] == eos and len(t) < 12 for t in want.values())

    eng = _engine(model, params, eos_id=eos,
                  **{"num_pages": 96, **over})
    streams: dict = {}
    _submit(eng, prompts, new, streams)
    if act is None:
        assert drained(eng, streams) == want
        return
    if act == "watch_pool":
        short = 0
        while not eng.idle:
            rec = eng.step()
            short += rec["op"] == "decode" and any(
                sp["ev"] == "decode" and sp["budget"] < min(
                    8, 16 - len(s.generated) + sp["emitted"])
                for s in eng.slots if s is not None
                for sp in s.trace[-1:])
        assert short, "the pool never cut a budget"
        assert drained(eng, streams) == want
        return
    _step_until_flying(eng)
    if act == "late_prompt":
        eng.submit(Request(id="late", prompt=late, max_new_tokens=9))
        chunks = 0
        while not eng.idle:
            rec = eng.step()
            chunks += rec["op"] == "prefill" and rec["ran_ahead"]
        assert chunks >= 4      # its chunks were dispatched ahead
        assert drained(eng, streams) == want
    elif act == "preempt":
        lost = eng.preempt()
        assert eng.cache.pages_used == 0 and eng._flying is None
        assert not eng._token_listeners
        for r in lost:
            eng.submit(r)
        assert drained(eng, {}) == want
    elif act == "drain":
        held = eng.in_flight
        report = eng.drain()
        assert eng._flying is None and eng.in_flight == 0
        assert len(report["finished"]) >= held
        eng.draining = False
        assert drained(eng, streams) == want
    elif act == "swap":
        before = sum(len(s.generated) for s in eng.slots if s)
        eng.swap_weights(_same_params(params), "v1")
        # What the launch in flight emitted was emitted, and tagged,
        # before the install.
        assert eng._flying is None
        settled = [s for s in eng.slots if s is not None]
        assert sum(len(s.generated) for s in settled) > before
        assert all(v == "v0" for s in settled for v, _n in s.versions)
        assert drained(eng, streams) == want
        assert {v for r in eng.completed
                for v, _n in r["weights_versions"]} == {"v0", "v1"}
    elif act == "export":
        state = eng.export_emission_state()
        export = eng.export_in_flight()
        assert eng._flying is None and eng.cache.pages_used == 0
        assert export["adoptable"]
        queued = list(eng.queue)
        heir = _engine(model, params, num_pages=96, **over)
        heir.import_emission_state(state)
        heir.adopt_batch(export["adoptable"])
        for r in export["requests"] + queued:
            heir.submit(r)
        done = drained(heir, {})
        done.update({r["id"]: r["tokens"] for r in eng.completed})
        assert done == want
        for rid, toks in streams.items():
            assert toks == want[rid], f"{rid} streamed twice or not"


def test_run_ahead_dispatches_before_it_fetches(tiny_model):
    """The order itself, on the host: launch n+1 is dispatched before
    launch n is fetched (so the device never waits for the host's
    emit), every launch but the first of a filled pipeline says so in
    ``ran_ahead``, ``Engine.idle`` is false while a launch is in
    flight, and pages claimed for tokens that did not come (``spec_k``
    4 accepts less than its budget on random weights) are back in the
    free list after the retire."""
    model, params = tiny_model
    eng = _engine(model, params, resident_k=4, spec_k=4, page_size=4,
                  num_pages=96)
    eng.warmup()
    order: list = []
    fetch = eng._fetch_host

    def fetching(*arrays):
        order.append("fetch")
        return fetch(*arrays)

    def dispatching(fn):
        def call(*args):
            order.append("dispatch")
            return fn(*args)
        call.__wrapped__ = fn.__wrapped__
        return call

    eng._fetch_host = fetching
    eng._decode_fn = dispatching(eng._decode_fn)
    eng._prefill_batch_fn = dispatching(eng._prefill_batch_fn)
    _submit(eng, _run_ahead_prompts()[:4], [24] * 4)
    recs, trimmed = [], 0
    ps = eng.cfg.page_size
    while not eng.idle:
        pages = {s.req.id: eng.cache.pages_of(s.req.id)
                 for s in eng.slots if s is not None}
        recs.append(eng.step())
        assert recs[-1]["op"] != "idle"
        if eng._flying is not None:
            assert not eng.idle
        for s in eng.slots:
            if s is not None and s.prefill_done:
                # No page beyond what its launch in flight may write.
                held = eng.cache.pages_of(s.req.id)
                assert held == -(-s.kv_ahead // ps)
                trimmed += held < pages.get(s.req.id, 0)
    assert trimmed, "no burst gave pages back"
    assert eng.cache.pages_used == 0
    assert eng.cache.free_pages_in(0) == eng.cache.cfg.usable_pages
    # Two dispatches fill the pipeline; from then on every fetch has
    # the next launch queued behind the one it waits for.
    assert order[:3] == ["dispatch", "dispatch", "fetch"]
    ahead = 0
    for what in order:
        ahead += 1 if what == "dispatch" else -1
        assert 0 <= ahead <= 2
    assert [r["ran_ahead"] for r in recs[:3]] == [0, 1, 1]
    assert sum(r["ran_ahead"] for r in recs) >= len(recs) - 2
    assert sum(r["host_syncs"] for r in recs) == eng.host_syncs \
        == order.count("fetch")


def test_server_request_trace_splits_mailbox_from_queue(tiny_model,
                                                        tmp_path):
    """A request through ``ServingServer`` waits twice before it has a
    slot: in the server's mailbox until the engine thread hands it to
    ``Engine.submit``, then in the engine's queue. Its trace shows
    both: ``queued`` (arrival), ``submitted``, ``admitted``."""
    from distributed_training_tpu.serving.server import ServingServer
    from distributed_training_tpu.telemetry import uninstall

    model, params = tiny_model
    tel, recs = _trace_collector(tmp_path)
    eng = _engine(model, params)
    eng.warmup()
    srv = ServingServer(eng, port=0)
    assert srv.start() is not None
    try:
        out = srv.generate(np.asarray([3, 1, 4, 1, 5], np.int32), 4)
        assert len(out["tokens"]) == 4
    finally:
        srv.stop()
        uninstall()
        tel.close()
    [trace] = recs
    spans = trace["spans"]
    assert [s["ev"] for s in spans[:3]] == ["queued", "submitted",
                                            "admitted"]
    ts = [s["t"] for s in spans]
    assert ts == sorted(ts) and ts[0] == 0.0
    assert trace["queue_wait_s"] == pytest.approx(spans[2]["t"],
                                                  abs=1e-5)


def test_anomaly_detector_adds_no_host_syncs(tiny_model, tmp_path):
    """The ISSUE's zero-new-device-syncs acceptance, measured: the
    identical saturated backlog drained with an AnomalyDetector
    observer attached vs plain tracing must report the SAME host-sync
    count and byte-identical token streams — the detector folds
    already-emitted records on the host, it never touches the
    device."""
    from distributed_training_tpu.telemetry import (AnomalyDetector,
                                                    uninstall)

    model, params = tiny_model
    rng = np.random.default_rng(11)
    backlog = [(f"ad-{i}",
                rng.integers(0, 256, size=int(rng.integers(3, 9)))
                .astype(np.int32)) for i in range(6)]

    def drain(with_detector):
        tel, _ = _trace_collector(tmp_path)
        det = None
        if with_detector:
            det = AnomalyDetector(telemetry=tel,
                                  run_dir=str(tmp_path), window=16,
                                  min_samples=2, threshold=8.0)
            tel.add_observer(det.observe)
        try:
            eng = _engine(model, params)
            eng.warmup()
            h0 = eng.host_syncs
            for rid, prompt in backlog:
                eng.submit(Request(id=rid, prompt=prompt,
                                   max_new_tokens=5,
                                   arrival=time.monotonic()))
            eng.run_until_drained()
            return (eng.host_syncs - h0,
                    {r["id"]: r["tokens"] for r in eng.completed},
                    det)
        finally:
            uninstall()
            tel.close()

    syncs_off, toks_off, _ = drain(False)
    syncs_on, toks_on, det = drain(True)
    assert toks_on == toks_off
    assert syncs_on == syncs_off, \
        "anomaly detection changed the host-sync count"
    # Not vacuous: the detector really folded the serving stream.
    fp = det.state_fingerprint()
    assert fp["windows"]["serving_queue_depth"]
    assert fp["windows"]["serving_ttft"]


def test_debug_requests_endpoint(tiny_model):
    """GET /debug/requests snapshots the in-flight engine state
    (id, tenant, slot geometry, progress, pages held) without
    touching the device — polled live while a request decodes."""
    import threading
    import urllib.request

    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    srv = ServingServer(_engine(model, params), port=0)
    assert srv.start() is not None
    try:
        def post():
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate",
                data=json.dumps({"prompt_ids": [1, 2, 3, 4],
                                 "max_new_tokens": 48,
                                 "tenant": "acme"}).encode(),
                headers={"Content-Type": "application/json"})
            return json.loads(
                urllib.request.urlopen(req, timeout=120).read())

        th = threading.Thread(target=post)
        th.start()
        seen = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/requests",
                timeout=10).read())
            assert set(body) == {"in_flight", "queue_depth",
                                 "requests", "weights", "draining"}
            assert body["weights"]["version"] == "v0"
            assert body["draining"] is False
            if body["requests"]:
                seen = body
                break
        th.join(timeout=120)
        assert seen is not None, \
            "never observed the request in /debug/requests"
        [row] = seen["requests"]
        assert row["id"] == "http-0" or row["id"].startswith("http-")
        assert row["tenant"] == "acme"
        assert row["session"] is None
        assert row["prompt_tokens"] == 4
        assert 0 <= row["generated"] <= 48
        assert row["pages_held"] >= 1
        assert isinstance(row["group"], int)
        assert isinstance(row["slot"], int)
        assert [s["ev"] for s in row["spans"][:3]] == [
            "queued", "submitted", "admitted"]
        assert seen["in_flight"] == 1
    finally:
        srv.stop()


def test_metrics_on_serving_port_with_tenant_histograms(tiny_model,
                                                        tmp_path):
    """Satellite (b) + the tenant-label thread: with NO standalone
    metrics port, the serving port itself answers GET /metrics via
    the shared renderer, and a request's JSON-body tenant shows up
    as the {tenant=...} label on every latency histogram family.
    The pinned last-value ttft gauge stays next to them."""
    import urllib.request

    from distributed_training_tpu.telemetry import uninstall

    model, params = tiny_model
    tel, _recs = _trace_collector(tmp_path)
    try:
        from distributed_training_tpu.serving.server import (
            ServingServer)
        srv = ServingServer(_engine(model, params), port=0)
        assert srv.start() is not None
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate",
                data=json.dumps({"prompt_ids": [9, 8, 7],
                                 "max_new_tokens": 4,
                                 "tenant": "acme"}).encode(),
                headers={"Content-Type": "application/json"})
            out = json.loads(
                urllib.request.urlopen(req, timeout=120).read())
            assert len(out["tokens"]) == 4
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=10).read().decode()
            for fam in ("dtt_serving_time_to_first_token_seconds",
                        "dtt_serving_e2e_seconds",
                        "dtt_serving_queue_wait_seconds",
                        "dtt_serving_tokens_per_request"):
                assert f'{fam}_bucket{{tenant="acme",le="+Inf"}} 1' \
                    in body, f"{fam} missing its acme +Inf bucket"
                assert f'{fam}_count{{tenant="acme"}} 1' in body
                assert f'{fam}_sum{{tenant="acme"}}' in body
                assert f"# TYPE {fam} histogram" in body
            # tokens_per_request: 4 new tokens -> the le="4" bucket.
            assert ('dtt_serving_tokens_per_request_bucket'
                    '{tenant="acme",le="4"} 1') in body
            # The last-value gauge survives next to the histograms.
            assert "\ndtt_serving_ttft_seconds " in body
            assert "dtt_serving_requests_total 1" in body
        finally:
            srv.stop()
    finally:
        uninstall()
        tel.close()


def test_serving_r06_ledger_committed_and_coherent():
    """SERVING_r06.json: the observability acceptance gates stay
    machine-checked — tracing-on re-run with zero recompiles and an
    UNCHANGED host-sync count vs the untraced same-run drain, and a
    per-tenant SLO block (p50/p95/p99 TTFT + attainment) for the
    mixed chat/docs/bursty scenario scored against the committed
    conf deadlines."""
    import os

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    with open(os.path.join(root, "SERVING_r06.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "SERVING_r05.json")) as f:
        r05 = json.load(f)
    assert doc["revision"] == "r06"
    tr = doc["tracing"]
    assert tr["recompiles_after_warmup"] == 0
    assert tr["host_syncs_unchanged"] is True
    assert tr["saturated_host_syncs_traced"] == \
        tr["saturated_host_syncs_untraced"]
    slo = doc["slo"]
    assert slo["ttft_deadline_s"] == 0.25
    assert slo["per_token_deadline_s"] == 0.05
    rep = slo["report"]
    assert set(rep["tenants"]) == {"chat", "docs", "bursty"}
    for trep in rep["tenants"].values():
        q = trep["ttft_s"]
        assert q["p50"] is not None
        assert q["p50"] <= q["p95"] <= q["p99"]
        assert 0.0 <= trep["slo"]["attained"] <= 1.0
    assert rep["overall"]["preemptions"] >= 1
    assert 0.0 <= rep["overall"]["slo"]["attained"] <= 1.0
    # The retry cost of the mid-storm preempt is accounted.
    assert rep["overall"]["tokens_discarded"] >= 1
    cmp_block = doc["compared_to"]
    assert cmp_block["revision"] == "r05"
    assert cmp_block["tokens_per_s"] == \
        r05["saturated"]["tokens_per_s"]
    # The r05 lanes all still ride the r06 entry.
    assert doc["steady"]["recompiles_after_warmup"] == 0
    assert doc["prefix"]["compared_to"]["reduction_x"] >= 4.0
    assert doc["session"]["zero_prefill_resume"] is True
    assert doc["preemption"]["tokens_match_steady_storm"] is True


# ---------------------------------------------------------------------------
# SERVING_r07: serving resilience — hot-swap, drain, crash supervision
# ---------------------------------------------------------------------------


def _greedy_reference(model, params, prompts, n):
    """Fault-free greedy streams, one engine, full drain."""
    eng = _engine(model, params)
    out: dict[str, list[int]] = {}
    for i, p in enumerate(prompts):
        rid = f"r{i}"
        eng.submit(Request(id=rid, prompt=p, max_new_tokens=n))
        eng.add_token_listener(
            rid, (lambda r: lambda t, d: out.setdefault(r, [])
                  .append(t))(rid))
    eng.run_until_drained()
    return out


def test_swap_weights_token_identity_zero_recompiles(tiny_model):
    """The hot-swap contract end to end: swapping an identical-value
    weight set mid-decode installs with ZERO new compiles, in-flight
    requests finish token-identically to the never-swapped run, and
    every record carries the run-length version tags spanning the
    swap point."""
    model, params = tiny_model
    rng = np.random.default_rng(41)
    prompts = [rng.integers(1, 255, size=5).astype(np.int32)
               for _ in range(3)]
    ref = _greedy_reference(model, params, prompts, 8)

    eng = _engine(model, params)
    got: dict[str, list[int]] = {}
    for i, p in enumerate(prompts):
        rid = f"r{i}"
        eng.submit(Request(id=rid, prompt=p, max_new_tokens=8))
        eng.add_token_listener(
            rid, (lambda r: lambda t, d: got.setdefault(r, [])
                  .append(t))(rid))
    for _ in range(6):
        eng.step()
    counts = eng.compile_counts()
    # Same values, fresh buffers: a real publish never aliases the
    # incumbent arrays.
    fresh = jax.tree.map(lambda x: jnp.array(x), params)
    assert eng.swap_weights(fresh, "v1") == 0  # unbounded: none stale
    while not eng.idle:
        eng.step()
    assert eng.compile_counts() == counts, "swap recompiled"
    assert eng.weights_version == "v1"
    assert eng.swap_stats["installed"] == 1
    for rid in got:
        assert got[rid] == ref[rid], rid
    for rec in eng.completed:
        wv = rec["weights_versions"]
        assert [v for v, _n in wv] == ["v0", "v1"]
        assert sum(n for _v, n in wv) == len(rec["tokens"])


def test_swap_refusals_leave_engine_serving(tiny_model):
    """Every refusal path — provenance mismatch, missing provenance,
    wrong tree structure, wrong leaf shape, injected swap_corrupt —
    raises WITHOUT installing anything: the incumbent version keeps
    serving and finishes token-identically."""
    from distributed_training_tpu.resilience.faults import (
        FaultInjector, parse_fault_plan)
    from distributed_training_tpu.serving.disagg import (
        ProvenanceError)
    from distributed_training_tpu.serving.engine import Engine

    model, params = tiny_model
    rng = np.random.default_rng(43)
    p = rng.integers(1, 255, size=5).astype(np.int32)
    ref = _greedy_reference(model, params, [p], 8)["r0"]

    prov = {"name": "plan_a", "fingerprint": "fp_a"}
    eng = Engine(model, params,
                 EngineConfig(max_batch=4, page_size=8, num_pages=64,
                              max_seq_len=64, prefill_chunk=8),
                 weights_provenance=prov)
    got: list[int] = []
    eng.submit(Request(id="r0", prompt=p, max_new_tokens=8))
    eng.add_token_listener("r0", lambda t, d: got.append(t))
    for _ in range(4):
        eng.step()

    incumbent = eng.params
    with pytest.raises(ProvenanceError):
        eng.swap_weights(params, "bad1",
                         provenance={"name": "plan_a",
                                     "fingerprint": "fp_b"})
    with pytest.raises(ProvenanceError):
        eng.swap_weights(params, "bad2")  # provenance-less publish
    with pytest.raises(ValueError):
        eng.swap_weights({"lonely": jnp.zeros((2,))}, "bad3",
                         provenance=prov)
    leaves, treedef = jax.tree.flatten(
        jax.tree.map(lambda x: jnp.array(x), params))
    leaves[0] = jnp.zeros((3, 3), jnp.float32)
    with pytest.raises(ValueError):
        eng.swap_weights(jax.tree.unflatten(treedef, leaves),
                         "bad4", provenance=prov)
    # Injected torn publish: the artifact no longer verifies.
    inj = FaultInjector(parse_fault_plan("swap_corrupt@1"))
    eng.faults = inj
    with pytest.raises(ProvenanceError):
        eng.swap_weights(params, "bad5", provenance=prov)
    eng.faults = None

    # No partial install on any path: same object, same version.
    assert eng.params is incumbent
    assert eng.weights_version == "v0"
    assert eng.swap_stats == {"installed": 0, "refused": 5,
                              "stale_preempted": 0}
    while not eng.idle:
        eng.step()
    assert got == ref


def test_swap_staleness_bound_preempts_exactly_once(tiny_model):
    """cfg.swap_staleness_tokens=K: a sequence with more than K
    old-version tokens is preempted-and-resubmitted at swap time;
    greedy decode regenerates its prefix token-identically and the
    high-water mark suppresses re-delivery — the client stream sees
    each token ONCE, and the completed record shows only the new
    version."""
    model, params = tiny_model
    rng = np.random.default_rng(47)
    p = rng.integers(1, 255, size=5).astype(np.int32)
    ref = _greedy_reference(model, params, [p], 8)["r0"]

    eng = _engine(model, params, swap_staleness_tokens=2)
    got: list[int] = []
    eng.submit(Request(id="s0", prompt=p, max_new_tokens=8))
    eng.add_token_listener("s0", lambda t, d: got.append(t))
    for _ in range(6):
        eng.step()
    emitted_before = len(got)
    assert emitted_before > 2  # over the bound: must be preempted
    assert eng.swap_weights(
        jax.tree.map(lambda x: jnp.array(x), params), "v1") == 1
    assert eng.swap_stats["stale_preempted"] == 1
    while not eng.idle:
        eng.step()
    assert got == ref  # exactly once, in order, no duplicates
    (rec,) = eng.completed
    # The record is the post-swap incarnation: all-new-version.
    assert [v for v, _n in rec["weights_versions"]] == ["v1"]
    # Bound respected at the contract level: the FINISHED request
    # carries <= K tokens from a superseded version.
    old = sum(n for v, n in rec["weights_versions"] if v != "v1")
    assert old <= 2


def test_drain_finishes_in_flight_and_reports(tiny_model):
    """drain(): admission stops, in-flight work runs to completion,
    queued-but-never-admitted requests are reported ``requeued`` and
    stay queued for a successor; resuming admission serves them."""
    model, params = tiny_model
    rng = np.random.default_rng(53)
    eng = _engine(model, params, max_batch=2)
    for i in range(4):
        p = rng.integers(1, 255, size=4).astype(np.int32)
        eng.submit(Request(id=f"d{i}", prompt=p, max_new_tokens=4))
    for _ in range(2):
        eng.step()  # admit up to max_batch, start decoding
    rep = eng.drain()
    assert eng.draining
    assert sorted(rep["finished"] + rep["requeued"]) == \
        ["d0", "d1", "d2", "d3"]
    assert rep["persisted"] == []
    assert len(rep["finished"]) >= 2  # everything admitted finished
    assert eng.in_flight == 0
    # Reopen admission: the requeued tail is served.
    eng.draining = False
    eng.run_until_drained()
    assert sorted(r["id"] for r in eng.completed) == \
        ["d0", "d1", "d2", "d3"]


@pytest.mark.parametrize("shape", ["tiny_gqa16", "odd64",
                                   "h128_bf16"])
def test_drain_deadline_persists_kv_for_adoption(shape):
    """A drain that hits its deadline exports still-in-flight
    sequences' exact KV + token history; a successor engine adopts
    them and finishes token-identically with no re-prefill — and the
    pool accounting on BOTH engines returns to zero. The exchanged
    format is dense KV a sequence, ``(L, Hkv, len, hd)``, whatever
    the pools' stored layout: what the successor holds after the
    adoption exports again bit for bit."""
    from distributed_training_tpu.serving.disagg import (
        export_kv_batch)

    model, params, _dense = _layout_model(shape)
    c = model.cfg
    rng = np.random.default_rng(59)
    p = rng.integers(1, 255, size=5).astype(np.int32)
    ref = _greedy_reference(model, params, [p], 10)["r0"]

    eng = _engine(model, params)
    eng.submit(Request(id="k0", prompt=p, max_new_tokens=10))
    for _ in range(5):
        eng.step()
    assert eng.in_flight == 1
    rep = eng.drain(deadline_s=0.0)  # expire immediately
    assert rep["persisted"] == ["k0"]
    assert rep["finished"] == []
    assert eng.cache.pages_used == 0
    (item,) = rep["export"]["adoptable"]
    req, toks, k, v = item
    assert req.id == "k0" and len(toks) >= 1
    held = len(p) + len(toks) - 1           # the decode invariant
    assert k.shape == v.shape == (c.n_layers, c.n_kv_heads, held,
                                  c.head_dim)
    assert np.abs(k.astype(np.float32)).min() > 0   # no padding in it

    succ = _engine(model, params)
    succ.adopt_batch(rep["export"]["adoptable"])
    (k2,), (v2,) = export_kv_batch(succ.cache, ["k0"])
    assert k2.dtype == k.dtype and v2.dtype == v.dtype
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)
    for r in rep["export"]["requests"]:
        succ.submit(r)
    succ.run_until_drained()
    (rec,) = [r for r in succ.completed if r["id"] == "k0"]
    assert rec["tokens"] == ref
    assert succ.cache.pages_used == 0


def test_server_drain_sheds_and_healthz_tristate(tiny_model):
    """The HTTP story of a drain: /healthz flips ok -> draining,
    POST /generate 503s with a Retry-After header, in-flight work
    finishes, resume_admission() restores ok + service. A bounded
    queue (max_queue_depth) sheds the same way when full."""
    import http.client

    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    srv = ServingServer(_engine(model, params), port=0,
                        max_queue_depth=64, retry_after_s=2.0)
    assert srv.start() is not None
    try:
        def _get(path):
            c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=60)
            c.request("GET", path)
            r = c.getresponse()
            return r.status, json.loads(r.read())

        def _post(body):
            c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=60)
            c.request("POST", "/generate", json.dumps(body).encode(),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            return r.status, json.loads(r.read()), \
                r.getheader("Retry-After")

        code, hz = _get("/healthz")
        assert (code, hz["status"]) == (200, "ok")
        st, rec, _ra = _post({"prompt_ids": [5, 7, 11],
                              "max_new_tokens": 4})
        assert st == 200 and len(rec["tokens"]) == 4

        rep = srv.drain()
        assert rep["persisted"] == []  # no deadline: all finished
        assert srv.draining
        code, hz = _get("/healthz")
        assert (code, hz["status"]) == (200, "draining")
        st, err, ra = _post({"prompt_ids": [5, 7, 11],
                             "max_new_tokens": 4})
        assert st == 503 and "draining" in err["error"]
        assert ra == "2"

        srv.resume_admission()
        code, hz = _get("/healthz")
        assert (code, hz["status"]) == (200, "ok")
        st, rec, _ra = _post({"prompt_ids": [5, 7, 11],
                              "max_new_tokens": 4})
        assert st == 200 and len(rec["tokens"]) == 4
    finally:
        srv.stop()


def test_server_swap_during_load_token_identical(tiny_model):
    """swap_weights through the server control path lands between
    engine launches while HTTP requests are in flight: every
    completion is token-identical to the unswapped engine, zero
    recompiles, and /debug/requests reports the new version."""
    import http.client
    import threading

    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    ref = _greedy_reference(
        model, params,
        [np.asarray([5, 7, 11], np.int32)], 12)["r0"]

    srv = ServingServer(_engine(model, params), port=0)
    assert srv.start() is not None
    try:
        results = {}

        def _client(i):
            c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=120)
            c.request("POST", "/generate",
                      json.dumps({"prompt_ids": [5, 7, 11],
                                  "max_new_tokens": 12}).encode(),
                      {"Content-Type": "application/json"})
            results[i] = json.loads(c.getresponse().read())

        # Warm the programs first so counts0 is the POST-warmup
        # plateau (the recompile gate measures the swap, not the
        # first-ever trace).
        warm = srv.generate(np.asarray([5, 7, 11], np.int32), 12)
        assert warm["tokens"] == ref
        counts0 = srv.engine.compile_counts()
        threads = [threading.Thread(target=_client, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        fresh = jax.tree.map(lambda x: jnp.array(x), params)
        srv.swap_weights(fresh, "v1")
        for t in threads:
            t.join(120)
        assert srv.engine.compile_counts() == counts0
        assert srv.engine.weights_version == "v1"
        for rec in results.values():
            assert rec["tokens"] == ref
        snap = srv.debug_snapshot()
        assert snap["weights"]["version"] == "v1"
        assert snap["weights"]["swaps"]["installed"] == 1
    finally:
        srv.stop()


def test_server_stop_clean_no_leaked_threads(tiny_model, tmp_path):
    """stop() joins every thread it started, counts leakers instead
    of lying, and emits the ``serving_stop`` telemetry event; a clean
    stop reports zero and leaves no live serving thread behind."""
    import threading

    from distributed_training_tpu.serving.server import ServingServer
    from distributed_training_tpu.telemetry import (
        Telemetry, install, uninstall)

    model, params = tiny_model
    events = []
    tel = Telemetry(events_jsonl=str(tmp_path / "events.jsonl"))
    tel.add_observer(lambda r: events.append(r)
                     if r.get("kind") == "serving_stop" else None)
    install(tel)
    try:
        srv = ServingServer(_engine(model, params), port=0)
        assert srv.start() is not None
        srv.generate(np.asarray([5, 7, 11], np.int32), 4)
        before = {t.name for t in threading.enumerate()}
        srv.stop()
        assert srv.leaked_threads == 0
        alive = {t.name for t in threading.enumerate()
                 if t.is_alive()}
        assert not any(n.startswith("serving-") for n in alive), \
            alive & before
        (ev,) = events
        assert ev["leaked_threads"] == 0
        assert ev["engine_error"] is None
    finally:
        uninstall()
        tel.close()


def test_supervise_serving_restart_adopts_and_streams_once(
        tiny_model, tmp_path):
    """The serving supervisor against an injected engine_crash:
    restart in-process, re-adopt the salvaged KV, resubmit, finish —
    every client stream token-identical to the fault-free run with
    no duplicate emission, an incident bundle on disk carrying the
    request snapshot, and the doctor classifying it
    ``serving_engine_crash``."""
    from distributed_training_tpu.resilience.faults import (
        FaultInjector, parse_fault_plan)
    from distributed_training_tpu.resilience.supervisor import (
        RestartPolicy, supervise_serving)
    from distributed_training_tpu.telemetry import (
        Telemetry, install, uninstall)
    from distributed_training_tpu.telemetry.doctor import (
        diagnose_path)

    model, params = tiny_model
    rng = np.random.default_rng(61)
    prompts = [rng.integers(1, 255, size=5).astype(np.int32)
               for _ in range(3)]
    ref = _greedy_reference(model, params, prompts, 8)

    tel = Telemetry(events_jsonl=str(tmp_path / "events.jsonl"))
    install(tel)
    inj = FaultInjector(
        parse_fault_plan("engine_crash@4"),
        ledger_path=str(tmp_path / "fault_ledger.json"))
    incident_dir = str(tmp_path / "incidents")
    got: dict[str, list[int]] = {}

    def make_engine():
        eng = _engine(model, params)
        eng.faults = inj  # SHARED injector: the one-shot ledger
        return eng        # keeps the crash from re-firing

    def run(eng, incarnation):
        if incarnation == 0:
            for i, p in enumerate(prompts):
                rid = f"r{i}"
                eng.submit(Request(id=rid, prompt=p,
                                   max_new_tokens=8))
                eng.add_token_listener(
                    rid, (lambda r: lambda t, d: got.setdefault(
                        r, []).append(t))(rid))
        eng.run_until_drained()
        return eng.finished_total

    try:
        res = supervise_serving(
            make_engine, run,
            policy=RestartPolicy(max_restarts=3, backoff_base_s=0.0,
                                 backoff_max_s=0.0),
            incident_dir=incident_dir)
    finally:
        uninstall()
        tel.close()
    assert res["gave_up"] is False
    assert res["incarnations"] == 2 and len(res["crashes"]) == 1
    eng = res["engine"]
    assert eng.finished_total == 3
    assert eng.cache.pages_used == 0
    for rid in ref:
        assert got[rid] == ref[rid], rid
    (bundle,) = sorted((tmp_path / "incidents").iterdir())
    with open(bundle / "meta.json") as f:
        meta = json.load(f)
    assert meta["kind"] == "engine_crash"
    # extra is spread into the meta envelope by the bundle writer.
    assert meta["weights_version"] == "v0"
    assert meta["incarnation"] == 0
    with open(bundle / "serving_requests.json") as f:
        snap = json.load(f)
    assert "requests" in snap
    verdict = diagnose_path(str(bundle))
    assert verdict["verdict"] == "serving_engine_crash"
    assert verdict["incident"]["kind"] == "engine_crash"


def test_supervise_serving_gives_up_on_crash_loop(tiny_model,
                                                  tmp_path):
    """A crash on every launch burns the restart budget: the
    supervisor stops retrying, reports gave_up, and leaves a
    ``give_up`` bundle."""
    from distributed_training_tpu.resilience.faults import (
        FaultInjector, parse_fault_plan)
    from distributed_training_tpu.resilience.supervisor import (
        RestartPolicy, supervise_serving)

    model, params = tiny_model

    def make_engine():
        eng = _engine(model, params)
        # A FRESH injector each incarnation: the crash re-fires
        # every time (the pathological torn deploy).
        eng.faults = FaultInjector(parse_fault_plan("engine_crash@1"))
        return eng

    def run(eng, incarnation):
        if incarnation == 0:
            eng.submit(Request(
                id="r0", prompt=np.asarray([5, 7, 11], np.int32),
                max_new_tokens=8))
        eng.run_until_drained()
        return eng.finished_total

    res = supervise_serving(
        make_engine, run,
        policy=RestartPolicy(max_restarts=2, backoff_base_s=0.0,
                             backoff_max_s=0.0),
        incident_dir=str(tmp_path / "incidents"))
    assert res["gave_up"] is True
    assert len(res["crashes"]) == res["incarnations"]
    kinds = []
    for d in sorted((tmp_path / "incidents").iterdir()):
        with open(d / "meta.json") as f:
            kinds.append(json.load(f)["kind"])
    assert kinds.count("give_up") == 1
    assert kinds.count("engine_crash") == len(res["crashes"])


def test_server_engine_crash_unhealthy_and_bundle(tiny_model,
                                                  tmp_path):
    """An engine-thread death inside the HTTP server: waiting
    clients get an error reply (not a hang), /healthz flips to 503
    unhealthy, new POSTs shed, and the flight-recorder bundle lands
    in incident_dir."""
    import http.client

    from distributed_training_tpu.resilience.faults import (
        FaultInjector, parse_fault_plan)
    from distributed_training_tpu.serving.server import ServingServer

    model, params = tiny_model
    eng = _engine(model, params)
    eng.faults = FaultInjector(parse_fault_plan("engine_crash@2"))
    srv = ServingServer(eng, port=0,
                        incident_dir=str(tmp_path / "incidents"))
    assert srv.start() is not None
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                       timeout=60)
        c.request("POST", "/generate",
                  json.dumps({"prompt_ids": [5, 7, 11],
                              "max_new_tokens": 16}).encode(),
                  {"Content-Type": "application/json"})
        rec = json.loads(c.getresponse().read())
        assert "engine crashed" in rec["error"]

        c2 = http.client.HTTPConnection("127.0.0.1", srv.port,
                                        timeout=60)
        c2.request("GET", "/healthz")
        r2 = c2.getresponse()
        hz = json.loads(r2.read())
        assert r2.status == 503 and hz["status"] == "unhealthy"
        assert "InjectedCrash" in hz["error"]

        c3 = http.client.HTTPConnection("127.0.0.1", srv.port,
                                        timeout=60)
        c3.request("POST", "/generate",
                   json.dumps({"prompt_ids": [5],
                               "max_new_tokens": 2}).encode(),
                   {"Content-Type": "application/json"})
        assert c3.getresponse().status == 503

        (bundle,) = list((tmp_path / "incidents").iterdir())
        with open(bundle / "meta.json") as f:
            assert json.load(f)["kind"] == "engine_crash"
        assert (bundle / "serving_requests.json").exists()
    finally:
        srv.stop()


def test_randomized_fault_plans_exactly_once_and_leak_free(
        tiny_model, tmp_path):
    """Property test: random fault plans (engine crashes, torn swap
    publishes, client disconnects at random launch counts) against
    the supervisor + a mid-run swap. Invariants per trial: every
    still-attached client stream equals the fault-free greedy stream
    exactly once; every request finishes; the KV pool returns to
    zero pages used."""
    from distributed_training_tpu.resilience.faults import (
        FaultInjector, parse_fault_plan)
    from distributed_training_tpu.resilience.supervisor import (
        RestartPolicy, supervise_serving)

    model, params = tiny_model
    base = np.random.default_rng(67)
    prompts = [base.integers(1, 255, size=5).astype(np.int32)
               for _ in range(4)]
    ref = _greedy_reference(model, params, prompts, 8)

    for trial in range(4):
        rng = np.random.default_rng(100 + trial)
        plan = [f"engine_crash@{int(rng.integers(2, 10))}"]
        if rng.integers(0, 2):
            plan.append(
                f"client_disconnect@{int(rng.integers(1, 6))}")
        swap_at = int(rng.integers(1, 8))
        swap_corrupt = bool(rng.integers(0, 2))
        if swap_corrupt:
            plan.append(f"swap_corrupt@{swap_at}")
        inj = FaultInjector(
            parse_fault_plan(",".join(plan)),
            ledger_path=str(tmp_path / f"ledger_{trial}.json"))
        got: dict[str, list[int]] = {}

        def make_engine(inj=inj):
            eng = _engine(model, params)
            eng.faults = inj
            return eng

        def run(eng, incarnation, swap_at=swap_at, got=got):
            if incarnation == 0:
                for i, p in enumerate(prompts):
                    rid = f"r{i}"
                    eng.submit(Request(id=rid, prompt=p,
                                       max_new_tokens=8))
                    eng.add_token_listener(
                        rid, (lambda r: lambda t, d: got.setdefault(
                            r, []).append(t))(rid))
            swapped = False
            while not eng.idle:
                eng.step()
                if not swapped and eng.launch_count >= swap_at:
                    swapped = True
                    try:
                        eng.swap_weights(
                            jax.tree.map(lambda x: jnp.array(x),
                                         params), "v1")
                    except ValueError:
                        pass  # torn publish refused: keep serving
            return eng.finished_total

        res = supervise_serving(
            make_engine, run,
            policy=RestartPolicy(max_restarts=4, backoff_base_s=0.0,
                                 backoff_max_s=0.0))
        assert res["gave_up"] is False, plan
        eng = res["engine"]
        assert eng.cache.pages_used == 0, plan
        assert all(s is None for s in eng.slots), plan
        assert eng.idle and not eng.queue, plan
        # Surviving streams (a client_disconnect drops ONE listener,
        # possibly delivering a prefix) are exact, duplicate-free
        # prefixes of the reference; non-dropped streams are the
        # full reference.
        for rid, toks in got.items():
            assert toks == ref[rid][:len(toks)], (plan, rid)
        full = [rid for rid, toks in got.items()
                if toks == ref[rid]]
        assert len(full) >= 3, (plan, {k: len(v)
                                       for k, v in got.items()})


def test_serving_r07_ledger_committed_and_coherent():
    """SERVING_r07.json: the resilience acceptance gates stay
    machine-checked — chaos drain goodput >= 0.85 with completed
    requests token-identical to the fault-free greedy reference,
    zero recompiles across the mid-storm swap, and the swapped
    engine's host-sync count equal to the unswapped drain's."""
    import os

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    with open(os.path.join(root, "SERVING_r07.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "SERVING_r06.json")) as f:
        r06 = json.load(f)
    assert doc["revision"] == "r07"
    sw = doc["swap"]
    assert sw["recompiles_after_warmup"] == 0
    assert sw["tokens_identical"] is True
    assert sw["host_syncs_swapped"] == sw["host_syncs_unswapped"]
    chaos = doc["chaos"]
    assert chaos["goodput"] >= 0.85
    assert chaos["completed_tokens_identical"] is True
    assert chaos["crashes"] >= 1 and chaos["restarts"] >= 1
    assert chaos["swap_installed"] is True
    assert chaos["kv_leaked_pages"] == 0
    cmp_block = doc["compared_to"]
    assert cmp_block["revision"] == "r06"
    assert cmp_block["tokens_per_s"] == \
        r06["saturated"]["tokens_per_s"]
    # The r06 lanes all still ride the r07 entry.
    assert doc["steady"]["recompiles_after_warmup"] == 0
    assert doc["tracing"]["host_syncs_unchanged"] is True
