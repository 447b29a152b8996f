"""The window-and-global expert configuration end to end at a toy size
through the real harness: driver, engine behind its server (two pools,
rings that turn: prompts of 16-160 over a window of 32), the plain
reference with its constants set to the toy's, and the three readers of
the window counter and the two pools' occupancy."""

import json
import os

import pytest

from perfbench import common, run
from perfbench.tests import rehearse

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_window")
TOY = dict(N_KV_HEAD=2, HEAD_DIM=16, WINDOW=32, WINDOW_LAYOUT=(0, 1, 1, 1),
           ROPE_LAYOUT=(0, 1, 1, 1), ROPE_THETA=10000.0,
           NUM_EXPERTS_PER_TOK=3, Q_BLOCK=64)
NEW = {"kv_cache.window_bound_share.decode",
       "kv_cache.window_pool_peak_share.decode",
       "kv_cache.global_pool_peak_share.decode"}


def toy_reference(config, load=common.load_reference):
    module = load(config)
    for name, value in TOY.items():
        setattr(module, name, value)
    return module


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(monkeypatch, capsys, trace):
    rehearse.admit_cpu(monkeypatch.setattr)
    monkeypatch.setattr(common, "load_reference", toy_reference)
    rc = run.main(["--workload", "tiny_window.closed", "--seed",
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
    # Most prompts are longer than the window of 32.
    assert 50 < value["kv_cache.window_bound_share.decode"] <= 100
    # A ring is (32 + 16) / 8 = 6 pages of a table's 32; the sum of the
    # two pools' pages is what the accepted reader already reports.
    assert 0 < value["kv_cache.global_pool_peak_share.decode"] < 100
    assert 0 < value["kv_cache.window_pool_peak_share.decode"] <= 100
    assert 0 < value["kv_cache.pool_peak_share.decode"] < 100
    assert value["moe.load_max_over_mean.decode"] >= 1.0
