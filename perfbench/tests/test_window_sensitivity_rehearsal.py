"""``check_window_sensitivity.py``'s cases through the real driver at a
toy size: the control flow, and that the program is what is tampered
with, under a reference made from the untampered weights."""

import json
import os

import pytest

from perfbench import common
from perfbench.tests import check_window_sensitivity as sens
from perfbench.tests import rehearse
from perfbench.tests.test_rehearsal_window import TINY, toy_reference


@pytest.mark.parametrize("case", sens.CASES)
def test_case_runs_and_reads_its_gap(monkeypatch, capsys, case):
    rehearse.admit_cpu(monkeypatch.setattr)
    monkeypatch.setattr(common, "load_reference", toy_reference)
    rc = sens.one_case(case, 3000000019, 2.0, root=TINY,
                       cell="tiny_window.closed",
                       setattr_=monkeypatch.setattr)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["case"] == case and line["failed"] == 0
    assert line["tokens"] > 0 and line["p99"] <= line["worst_logit_gap"]
    # The sizes of the toy's gaps are the seed's; the committed program
    # passes, and the mask and position faults are seen even here.
    if case == "committed":
        assert line["harness_ok"]
    if case in ("window_mask_off", "ring_one_page_wrong",
                "rope_on_global", "rope_off_window"):
        assert line["worst_logit_gap"] > 1e-3
