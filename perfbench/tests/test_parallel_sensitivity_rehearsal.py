"""``check_parallel_sensitivity.py``'s cases through the real driver at a
toy size: the control flow, and that the program is what is tampered
with, under a reference made from the untampered weights."""

import json

import pytest

from perfbench.tests import check_parallel_sensitivity as sens
from perfbench.tests.test_rehearsal_parallel import TINY, admit_toy


@pytest.mark.parametrize("case", sens.CASES)
def test_case_runs_and_reads_its_gap(monkeypatch, capsys, case):
    admit_toy(monkeypatch.setattr)
    rc = sens.one_case(case, 3000000019, 2.0, root=TINY,
                       cell="tiny_parallel.closed",
                       setattr_=monkeypatch.setattr)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["case"] == case and line["failed"] == 0
    assert line["tokens"] > 0 and line["p99"] <= line["worst_logit_gap"]
    # The sizes of the toy's gaps are the seed's; the committed program
    # passes, and a missing position, the shared experts' scale, the
    # norm's mean and a misplaced ring are seen even here (a position
    # too many on the one full layer and the serial block move no
    # argmax of this toy: the chip's readings are in the traffic file).
    if case == "committed":
        assert line["harness_ok"]
    if case in ("rope_off_sliding", "shared_summed", "rms_norm",
                "ring_one_page_off"):
        assert line["worst_logit_gap"] > 1e-3
