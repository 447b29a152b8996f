"""``check_serving_sensitivity.py``'s cases through the real driver at
a toy size: the control flow, and that the program is what is tampered
with, under a reference made from the untampered weights."""

import json
import os

import pytest

from perfbench.tests import check_serving_sensitivity as sens
from perfbench.tests import rehearse

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_latent")


@pytest.mark.parametrize("case", ["committed", "routed_experts_out",
                                  "shared_expert_out",
                                  "one_layers_experts_out", "rope_off",
                                  "experts_int8", "all_float8"])
def test_case_runs_and_reads_its_gap(monkeypatch, capsys, case):
    rehearse.admit_cpu(monkeypatch.setattr)
    rc = sens.one_case(case, 3000000019, 2.0, root=TINY,
                       cell="tiny_latent.closed",
                       setattr_=monkeypatch.setattr)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["case"] == case and line["failed"] == 0
    assert line["tokens"] > 0 and line["p99"] <= line["worst_logit_gap"]
    # The toy's logits lie close together and which requests are done
    # when the window closes is the host's timing: the sizes of the
    # gaps mean nothing here, only that the committed program passes.
    if case == "committed":
        assert line["harness_ok"]
