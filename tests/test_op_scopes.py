"""Device time by the program's own layers, the program's half
(``telemetry/op_scopes.py``, the ``jax.named_scope``s of the serving
path, ``Engine.warmup``'s ``program_scopes`` record, the ``iters``
counter of a decode step record), on the CPU: ``scope_map`` on a
hand-written optimized-HLO text, a tiny engine of each of the four
blocks under an in-memory sink and without one, and every cadence's
decode records."""

import os
import re

import jax
import numpy as np
import pytest

from distributed_training_tpu.models import build_model
from distributed_training_tpu.models.transformer import (
    Transformer, TransformerConfig)
from distributed_training_tpu.serving import engine as E
from distributed_training_tpu.serving.engine import (Engine,
                                                     EngineConfig,
                                                     Request)
from distributed_training_tpu.telemetry import (Telemetry, install,
                                                op_scopes, uninstall)
from distributed_training_tpu.telemetry.op_scopes import (SCOPES,
                                                          UNSCOPED,
                                                          scope_map,
                                                          scope_of)

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "distributed_training_tpu")

# Written by hand (``tests/data/op_scopes_hand.hlo``): what a TPU compile
# prints, cut to what the parser reads: a header with its source tables,
# a comparator and a reducer (never listed), three fused computations, a
# layer loop inside the resident loop, the entry.
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "op_scopes_hand.hlo")) as _f:
    HLO = _f.read()

WANT = {
    "dtt.engine": {"while.1", "while.2", "add.5", "lt.2"},
    # ``fusion.1``: its root's scope, though ``mul.1`` inside is the
    # projection's; ``custom-call.4`` a Pallas kernel.
    "dtt.attn.core": {"fusion.1", "custom-call.4"},
    # ``fusion.2``: its root is a tuple the compiler made, its insides
    # lie in one scope.
    "dtt.mlp": {"fusion.2"},
    # ``fusion.4``: a projection whose root is the reshape its consumer
    # asked for: the root only re-lays, the product says what it is.
    "dtt.attn.project": {"fusion.4"},
    # The merged name: the last path that names a scope decides.
    "dtt.kv.read": {"squeeze.52"},
    "dtt.attn.select": {"sort.23"},
    # No metadata at all, in a loop body, a fusion and the entry.
    UNSCOPED: {"copy.31", "fusion.3", "lt.4", "convert.1"},
}


@pytest.fixture(scope="module")
def hand_map():
    return scope_map(HLO)


@pytest.mark.parametrize("scope", sorted(WANT))
def test_scope_map_lists_each_instruction_under_its_scope(hand_map,
                                                          scope):
    assert set(hand_map["scopes"][scope]) == WANT[scope]
    assert len(hand_map["scopes"][scope]) == len(WANT[scope])


def test_scope_map_lists_what_runs_once_and_nothing_else(hand_map):
    assert hand_map["module"] == "jit_serving_resident_decode"
    assert set(hand_map["scopes"]) == set(WANT)
    assert hand_map["instructions"] == sum(map(len, WANT.values()))
    # The insides of fused computations, the reducer and the comparator,
    # and what runs as nothing are not operations of their own.
    listed = {n for names in hand_map["scopes"].values() for n in names}
    assert not listed & {"mul.1", "dot.1", "neg.1", "exp.1", "sum.1",
                         "copy.7", "add.0", "lt.0", "x", "w", "zero",
                         "dot.4", "reshape.9",
                         "tuple.5", "out", "bitcast.2", "x.1", "one"}
    # A fusion whose insides span two scopes: under its root's, and
    # under ``mixed``; one whose insides agree is not mixed.
    assert hand_map["mixed"] == ["fusion.1", "fusion.4"]


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/dtt.engine/while/body/dtt.attn.core/dot_general",
     "dtt.attn.core"),
    ("jit(f)/dtt.engine/while/body/closed_call/dtt.attn.core/"
     "dtt.kv.read/gather", "dtt.kv.read"),
    ("jit(f)/dtt.engine/while", "dtt.engine"),
    # Merged: later paths lack the prefix they share with the first.
    ("jit(f)/dtt.engine/while/body/dtt.attn.core/dtt.kv.read/reshape;"
     "dtt.attn.core/dtt.kv.read/squeeze", "dtt.kv.read"),
    ("jit(f)/dtt.engine/while/body/dtt.mlp/mul;add", "dtt.mlp"),
    ("jit(f)/dtt.attn.project/mul;dtt.attn.out/add", "dtt.attn.out"),
    # Not the vocabulary's: a name that merely starts like one.
    ("jit(f)/dtt.engineer/add", None),
    ("jit(f)/while/body/add", None), ("", None),
])
def test_scope_of_takes_the_innermost_scope(op_name, want):
    assert scope_of(op_name) == want


def test_every_scope_in_the_program_is_in_the_one_vocabulary():
    """``SCOPES`` is the one place: every ``jax.named_scope`` literal
    under the package is one of its names, and each name is used."""
    used = set()
    for folder in ("serving", "models", "ops"):
        for name in os.listdir(os.path.join(PACKAGE, folder)):
            if name.endswith(".py"):
                with open(os.path.join(PACKAGE, folder, name)) as f:
                    text = f.read()
                used |= set(re.findall(r'"(dtt\.[a-z.]+)"', text))
    assert used == set(SCOPES)
    assert len(set(SCOPES)) == len(SCOPES) and UNSCOPED not in SCOPES


# -- tiny engines of the five blocks --------------------------------------

COMMON = {"dtt.embed", "dtt.attn.project", "dtt.kv.write", "dtt.kv.read",
          "dtt.attn.core", "dtt.attn.out", "dtt.head", "dtt.engine"}
MOE = {"dtt.moe.route", "dtt.moe.experts"}
FULL, SLIDING = "full_attention", "sliding_attention"
ENGINE = dict(max_batch=3, page_size=4, num_pages=60, max_seq_len=64,
              prefill_chunk=8, prefill_slots=2, prefix_sharing=False,
              resident_k=4)


def gpt2():
    model = Transformer(TransformerConfig(
        vocab_size=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=64, dtype="float32", param_dtype="float32",
        pos_encoding="learned", tie_embeddings=True))
    return model, COMMON | {"dtt.mlp"}


def latent():
    return build_model(
        "latent_moe", dtype="float32", vocab_size=96, d_model=32,
        n_layers=3, n_dense_layers=1, n_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, d_ff=48, moe_d_ff=12, n_routed_experts=16,
        moe_top_k=4, max_seq_len=64), COMMON | MOE | {"dtt.mlp"}


def window():
    # Every layer an expert layer: no dense feed-forward.
    return build_model(
        "window_moe", dtype="float32", vocab_size=96, d_model=32,
        n_layers=4, n_heads=4, n_kv_heads=2, head_dim=8, moe_d_ff=12,
        n_routed_experts=16, moe_top_k=3, window=16,
        window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
        rope_theta=500.0, qk_std=0.2, max_seq_len=64), COMMON | MOE


def sparse():
    return build_model(
        "sparse_latent_moe", dtype="float32", vocab_size=96, d_model=32,
        n_layers=5, n_dense_layers=1,
        layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING), n_heads=4,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, rope_theta=500.0,
        index_n_heads=4, index_head_dim=8, index_topk=8, swa_n_heads=2,
        swa_q_lora_rank=16, swa_kv_lora_rank=16, swa_qk_nope_head_dim=12,
        swa_qk_rope_head_dim=4, swa_v_head_dim=8, swa_rope_theta=100.0,
        window=5, d_ff=48, moe_d_ff=12, n_routed_experts=16, moe_top_k=3,
        qk_std=0.2, max_seq_len=64), \
        COMMON | MOE | {"dtt.mlp", "dtt.attn.select"}


def parallel():
    # The shared experts under a scope of their own, inside the experts'.
    return build_model(
        "parallel_moe", dtype="float32", vocab_size=96, d_model=32,
        n_layers=4, n_heads=4, n_kv_heads=2, head_dim=8, moe_d_ff=12,
        n_routed_experts=16, moe_top_k=3, n_shared_experts=4, window=16,
        window_layout=(1, 1, 1, 0), rope_layout=(1, 1, 1, 0),
        rope_theta=500.0, qk_std=0.2, max_seq_len=64), \
        COMMON | MOE | {"dtt.moe.shared"}


BLOCKS = {"gpt2": gpt2, "latent": latent, "window": window,
          "sparse_latent": sparse, "parallel": parallel}


def engine_of(name):
    model, scopes = BLOCKS[name]()
    return Engine(model, model.init(jax.random.PRNGKey(0)),
                  EngineConfig(**ENGINE)), scopes


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_warmup_writes_one_map_a_program_under_a_sink(name, tmp_path,
                                                      monkeypatch):
    records: list = []
    tel = install(Telemetry(events_jsonl=str(tmp_path / "ev.jsonl")))
    tel.add_observer(records.append)
    flag = "jax_compilation_cache_include_metadata_in_key"
    keyed: list = []
    lower = jax.stages.Lowered.compile
    monkeypatch.setattr(jax.stages.Lowered, "compile", lambda *a, **kw: (
        keyed.append(getattr(jax.config, flag)), lower(*a, **kw))[1])
    try:
        eng, want = engine_of(name)
        counts = eng.warmup()
    finally:
        uninstall()
        tel.close()
    # Compiled under this tree's metadata (``own_metadata``), and the
    # flag is as it was.
    assert keyed == [True] * len(counts)
    assert getattr(jax.config, flag) is False
    maps = [r for r in records if r["kind"] == "program_scopes"]
    programs = [fn.__wrapped__.__name__ for fn, _a in eng._warmup_calls()]
    assert [m["program"] for m in maps] == programs
    assert len(maps) == len(counts) >= 3
    found: set = set()
    for m in maps:
        assert m["module"] == "jit_" + m["program"]
        assert set(m["scopes"]) <= set(SCOPES) | {UNSCOPED}
        names = [n for v in m["scopes"].values() for n in v]
        assert m["instructions"] == len(names) == len(set(names)) > 0
        assert set(m["mixed"]) <= set(names)
        found |= {s for s, v in m["scopes"].items() if v}
    # Every scope the block should have is non-empty, and no other:
    # the selection the sparse block's alone, experts the three expert
    # blocks', a dense feed-forward where a layer has one.
    assert found - {UNSCOPED} == want
    by_program = {m["program"]: m for m in maps}
    assert set(by_program["serving_seed"]["scopes"]) == {"dtt.engine"}
    for program in ("serving_resident_decode", "serving_prefill_batch"):
        have = {s for s, v in by_program[program]["scopes"].items() if v}
        assert have - {UNSCOPED} == want, program


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_without_a_sink_nothing_is_emitted_compiled_twice_or_parsed(
        name, monkeypatch):
    calls = {"as_text": 0, "scope_map": 0, "lower": 0}

    def counted(key, fn):
        def wrapped(*a, **kw):
            calls[key] += 1
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(jax.stages.Compiled, "as_text", counted(
        "as_text", jax.stages.Compiled.as_text))
    monkeypatch.setattr(E, "scope_map", counted("scope_map",
                                                op_scopes.scope_map))
    eng, _want = engine_of(name)
    for fn in eng._programs().values():
        monkeypatch.setattr(fn, "lower", counted("lower", fn.lower),
                            raising=False)
    counts = eng.warmup()
    assert calls == {"as_text": 0, "scope_map": 0, "lower": 0}
    assert all(n == 1 for n in counts.values())


def test_a_text_that_names_no_scope_gives_no_record(tmp_path,
                                                    monkeypatch):
    """An executable whose text kept no metadata (one that came back
    from a cache without it) has no map: no record, its readers report
    nothing, and the warm-up goes on."""
    monkeypatch.setattr(E, "scope_map", lambda text: scope_map(
        re.sub(r", metadata=\{[^}]*\}", "", text)))
    records: list = []
    tel = install(Telemetry(events_jsonl=str(tmp_path / "ev.jsonl")))
    tel.add_observer(records.append)
    try:
        eng, _want = engine_of("gpt2")
        eng.warmup()
    finally:
        uninstall()
        tel.close()
    kinds = [r["kind"] for r in records]
    assert "program_scopes" not in kinds and "serving_warmup" in kinds


CADENCES = {"plain": {"resident_k": 1}, "spec4": {"resident_k": 1,
                                                  "spec_k": 4},
            "resident4": {"resident_k": 4},
            "resident4_spec2": {"resident_k": 4, "spec_k": 2}}


@pytest.mark.parametrize("cadence", sorted(CADENCES))
def test_a_launching_decode_record_carries_its_iterations(cadence):
    model, _scopes = gpt2()
    cfg = {**ENGINE, **CADENCES[cadence]}
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 EngineConfig(**cfg))
    rng = np.random.default_rng(3)
    for i, n in enumerate((5, 11, 3)):
        eng.submit(Request(id=f"r{i}", max_new_tokens=9,
                           prompt=rng.integers(0, 96, n).astype(np.int32)))
    records = []
    while not eng.idle:
        records.append(eng.step())
    decode = [r for r in records if r["op"] == "decode"]
    assert decode and sum(r["tokens"] for r in decode) == 3 * 9 - 3
    k = cfg["resident_k"]
    for r in decode:
        assert 1 <= r["iters"] <= k
        # One dp group: the launch's iterations are its group's.
        assert r["iters"] == r.get("resident_steps_per_launch", 1)
        assert r["slot_iters"] <= r["iters"] * r["slots_stepped"]
    assert all("iters" not in r for r in records if r["op"] != "decode")
    if k > 1:
        assert max(r["iters"] for r in decode) > 1
