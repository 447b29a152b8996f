"""The parallel-block expert configuration end to end at a toy size
through the real harness: driver, engine behind its server (two pools,
rings that turn: prompts of 16-160 over a window of 32), the plain
reference with its constants set to the toy's, the readers the cell
joins, and the two new ones reporting nothing on a trace with no
``XLA Modules`` line."""

import json
import os

import pytest

from perfbench import common, run
from perfbench.tests import rehearse

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_parallel")
TOY = dict(N_KV_HEAD=2, HEAD_DIM=16, WINDOW=32,
           LAYER_TYPES=("sliding_attention",) * 3 + ("full_attention",),
           ROPE_THETA=50000.0, NUM_EXPERTS_PER_TOK=3,
           NUM_SHARED_EXPERTS=4, Q_BLOCK=64, V_BLOCK=128)
JOINED = {"kv_cache.window_bound_share.decode",
          "kv_cache.window_pool_peak_share.decode",
          "kv_cache.global_pool_peak_share.decode",
          "moe.load_max_over_mean.decode",
          "moe.tokens_per_held_expert.decode"}
NEW = {"moe.shared_time_share.decode", "attn.project_time_share.decode"}


def toy_reference(config, load=common.load_reference):
    module = load(config)
    for name, value in TOY.items():
        setattr(module, name, value)
    return module


def lively(init):
    """``ParallelMoE.init`` with every leaf moved by 0.05: at the toy's
    width of 64 the layers' outputs are smaller than a tied embedding's
    rows (0.02 an element), so the seed's model only repeats its last
    token and no fault in a layer changes what it streams; at the
    published width the layers' outputs are twenty times the
    embedding's."""
    import jax

    def moved(self, rng):
        leaves, tree = jax.tree.flatten(init(self, rng))
        keys = jax.random.split(jax.random.fold_in(rng, 1), len(leaves))
        return jax.tree.unflatten(tree, [
            x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
            for x, k in zip(leaves, keys)])
    return moved


def admit_toy(setattr_) -> None:
    from distributed_training_tpu.models.parallel_moe import ParallelMoE

    rehearse.admit_cpu(setattr_)
    setattr_(common, "load_reference", toy_reference)
    setattr_(ParallelMoE, "init", lively(ParallelMoE.init))


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(monkeypatch, capsys, trace):
    admit_toy(monkeypatch.setattr)
    rc = run.main(["--workload", "tiny_parallel.closed", "--seed",
                   "3000000019", "--seconds", "2", "--trace", str(trace)],
                  root=TINY)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_out_tok_s", "setup_s"}
        return
    assert JOINED <= set(line["metrics"])
    # The canned trace has no ``XLA Modules`` line: the two shares find
    # nothing to read and the line leaves them out, it does not fail.
    assert not NEW & set(line["metrics"])
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # Most prompts are longer than the window of 32.
    assert 50 < value["kv_cache.window_bound_share.decode"] <= 100
    assert 0 < value["kv_cache.global_pool_peak_share.decode"] < 100
    assert 0 < value["kv_cache.window_pool_peak_share.decode"] <= 100
    assert value["moe.load_max_over_mean.decode"] >= 1.0
