"""The two-latent, learned-selection expert configuration end to end at
a toy size through the real harness: driver, engine behind its server
(three pools: tables with their index keys, rings that turn; prompts of
24-160 over a window of 9 and a selection of 16), the plain reference
with its constants set to the toy's, and the two readers of the
selection's counters beside the accepted ones."""

import json
import os

import pytest

from perfbench import common, run
from perfbench.tests import rehearse

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_sparse")
TYPES = ("full_attention", "full_attention", "sliding_attention",
         "sliding_attention", "sliding_attention")
TOY = dict(LAYER_TYPES=TYPES, FIRST_K_DENSE=1, QK_NOPE=16, QK_ROPE=8,
           ROPE_THETA=10000.0, INDEX_TOPK=16, SWA_N_HEAD=2,
           SWA_QK_NOPE=24, SWA_QK_ROPE=8, SWA_ROPE_THETA=1000.0,
           WINDOW=9, RESCALE=True, NUM_EXPERTS_PER_TOK=3,
           INDEX_KEY_DTYPE=None, Q_BLOCK=64, HEAD_BLOCK=2, ROW_BLOCK=64)
NEW = {"attn.sparse_bound_share.decode", "attn.kept_key_share.decode"}


def toy_reference(config, load=common.load_reference):
    module = load(config)
    for name, value in TOY.items():
        setattr(module, name, value)
    return module


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(monkeypatch, capsys, trace):
    rehearse.admit_cpu(monkeypatch.setattr)
    monkeypatch.setattr(common, "load_reference", toy_reference)
    rc = run.main(["--workload", "tiny_sparse.closed", "--seed",
                   "3000000019", "--seconds", "2", "--trace", str(trace)],
                  root=TINY)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_out_tok_s", "setup_s"}
        return
    assert NEW <= set(line["metrics"])
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # Every prompt is longer than the selection's 16 and the window's 9.
    assert value["attn.sparse_bound_share.decode"] == 100
    assert value["kv_cache.window_bound_share.decode"] == 100
    # 16 of 24-256 visible positions are kept.
    assert 16 / 256 < value["attn.kept_key_share.decode"] < 16 / 24
    assert 0 < value["kv_cache.global_pool_peak_share.decode"] < 100
    assert 0 < value["kv_cache.window_pool_peak_share.decode"] <= 100
    assert value["moe.load_max_over_mean.decode"] >= 1.0
