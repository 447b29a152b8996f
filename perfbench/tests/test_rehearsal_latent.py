"""The latent-attention expert configuration end to end at a toy size
through the real harness: driver, engine behind its server, the plain
reference (its constants as committed: the toy keeps the head widths),
and the three readers of the expert counters and the prefill share."""

import json
import os

import pytest

from perfbench import run
from perfbench.tests import rehearse

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_latent")
NEW = {"moe.load_max_over_mean.decode", "moe.tokens_per_held_expert.decode",
       "engine.prefill_time_share.decode"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(monkeypatch, capsys, trace):
    rehearse.admit_cpu(monkeypatch.setattr)
    rc = run.main(["--workload", "tiny_latent.closed", "--seed",
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
    # 4 slots x 8 picks x 16/32 held over 16 held experts = 1 a call
    # at full occupancy; the largest count is at least the mean.
    assert 0 < value["moe.tokens_per_held_expert.decode"] <= 1.0
    assert value["moe.load_max_over_mean.decode"] >= 1.0
    assert 0 < value["engine.prefill_time_share.decode"] < 100
