"""``check_sparse_sensitivity.py``'s cases through the real driver at a
toy size: the control flow, and that the program is what is tampered
with, under a reference made from the untampered weights."""

import json

import pytest

from perfbench import common
from perfbench.tests import check_sparse_sensitivity as sens
from perfbench.tests import rehearse
from perfbench.tests.test_rehearsal_sparse import TINY, toy_reference


@pytest.mark.parametrize("case", sens.CASES)
def test_case_runs_and_reads_its_gap(monkeypatch, capsys, case):
    rehearse.admit_cpu(monkeypatch.setattr)
    monkeypatch.setattr(common, "load_reference", toy_reference)
    rc = sens.one_case(case, 3000000019, 2.0, root=TINY,
                       cell="tiny_sparse.closed",
                       setattr_=monkeypatch.setattr)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["case"] == case
    if case == "selection_flips":
        # float32 on both sides here: the two keep the same positions.
        assert line["queries_past_topk"] == 256 - 16
        assert line["kept_a_query"] == 16 and line["share"] < 0.01
        return
    assert line["failed"] == 0
    assert line["tokens"] > 0 and line["p99"] <= line["worst_logit_gap"]
    # The sizes of the toy's gaps are the seed's; the committed program
    # passes, and what changes which positions a query attends, or how
    # its heads are weighed, is seen even here.
    if case == "committed":
        assert line["harness_ok"]
    if case in ("selection_off", "topk_half", "window_off", "gate_off",
                "rescale_off", "index_rope_off"):
        assert line["worst_logit_gap"] > 1e-3
