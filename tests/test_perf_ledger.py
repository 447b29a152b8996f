"""Perf-ledger regression gate (tools/perf_ledger.py): the committed
*_r*.json trajectory must stay internally consistent — copied
compared_to values match what the cited ledger recorded, gates
reproduce from their inputs, revisions are contiguous. Red cases are
exercised on tampered copies of the real ledgers."""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "perf_ledger.py")

sys.path.insert(0, os.path.join(REPO, "tools"))
import perf_ledger  # noqa: E402


def _copy_ledgers(tmp_path):
    for p in glob.glob(os.path.join(REPO, "*_r*.json")):
        shutil.copy(p, tmp_path / os.path.basename(p))
    return str(tmp_path)


def _edit(root, name, fn):
    path = os.path.join(root, name)
    with open(path) as f:
        d = json.load(f)
    fn(d)
    with open(path, "w") as f:
        json.dump(d, f)


def test_committed_ledgers_are_green():
    trajectory, problems = perf_ledger.check(REPO)
    assert problems == []
    assert len(trajectory) >= 14
    families = {row["family"] for row in trajectory}
    assert {"MULTICHIP", "SERVING"} <= families


def test_trajectory_rows_carry_gates():
    trajectory, _ = perf_ledger.check(REPO)
    by_file = {r["file"]: r for r in trajectory}
    # SERVING_r05 honestly records a sub-1 speedup — the gate is
    # internal consistency, NEVER speedup >= 1.
    assert by_file["SERVING_r05.json"]["speedup"] < 1.0
    assert by_file["MULTICHIP_r07.json"]["step_time_speedup"] > 1.0


def test_red_on_edited_gate(tmp_path):
    """Someone bumps a recorded speedup without re-deriving it."""
    root = _copy_ledgers(tmp_path)

    def bump(d):
        d["compared_to"]["speedup"] = \
            d["compared_to"]["speedup"] * 1.5
    _edit(root, "SERVING_r03.json", bump)
    _, problems = perf_ledger.check(root)
    assert any("SERVING_r03.json: gate speedup" in p
               and "regressed its own recorded gate" in p
               for p in problems)


def test_red_on_edited_chain_copy(tmp_path):
    """Someone re-runs a bench and edits one file: the value it
    claims for its predecessor no longer matches."""
    root = _copy_ledgers(tmp_path)

    def skew(d):
        d["compared_to"]["tokens_per_s"] = \
            d["compared_to"]["tokens_per_s"] * 2.0
    _edit(root, "SERVING_r04.json", skew)
    _, problems = perf_ledger.check(root)
    assert any("SERVING_r04.json: compared_to.tokens_per_s" in p
               for p in problems)


def test_red_on_multichip_step_time_tamper(tmp_path):
    root = _copy_ledgers(tmp_path)

    def skew(d):
        d["step_time_ms"] = d["step_time_ms"] * 0.5
    _edit(root, "MULTICHIP_r06.json", skew)
    _, problems = perf_ledger.check(root)
    # r07 copies r06's step_time_ms into its compared_to block.
    assert any("MULTICHIP_r07.json: compared_to.step_time_ms" in p
               for p in problems)


def test_red_on_revision_gap(tmp_path):
    root = _copy_ledgers(tmp_path)
    os.remove(os.path.join(root, "SERVING_r03.json"))
    _, problems = perf_ledger.check(root)
    assert any("SERVING: revisions" in p and "not(" not in p
               for p in problems)
    # And r04's chain now cites an uncommitted entry.
    assert any("SERVING_r04.json" in p and "not committed" in p
               for p in problems)


def test_red_on_unparseable_ledger(tmp_path):
    root = _copy_ledgers(tmp_path)
    with open(os.path.join(root, "MULTICHIP_r01.json"), "w") as f:
        f.write("{not json")
    _, problems = perf_ledger.check(root)
    assert any("MULTICHIP_r01.json: unreadable" in p for p in problems)


def test_cli_by_path_green_and_red(tmp_path):
    """The tier-1 wiring contract: invoked BY PATH, stdlib-only,
    rc 0 on the committed set, rc 1 + RED lines on a tampered set."""
    out = subprocess.run([sys.executable, TOOL, "--check"],
                         capture_output=True, text=True, timeout=120,
                         cwd=REPO)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "0 problems" in out.stderr

    root = _copy_ledgers(tmp_path)
    _edit(root, "SERVING_r02.json",
          lambda d: d["compared_to"].update(speedup=99.0))
    out = subprocess.run([sys.executable, TOOL, "--check",
                          "--root", root],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    assert "RED:" in out.stdout


def test_json_output():
    out = subprocess.run([sys.executable, TOOL, "--json"],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0
    payload = json.loads(out.stdout)
    assert payload["problems"] == []
    assert len(payload["trajectory"]) >= 14


def test_close_tolerances():
    assert perf_ledger._close(1.0, 1.0005, perf_ledger.GATE_RTOL)
    assert not perf_ledger._close(1.0, 1.01, perf_ledger.GATE_RTOL)
    assert not perf_ledger._close(1.0, None, perf_ledger.GATE_RTOL)
    assert perf_ledger._close(0.0, 0.0, perf_ledger.COPY_RTOL)


@pytest.mark.parametrize("entry,problem", [
    ("MULTICHIP_r01.json", "crosses families"),
    ("SERVING_r09.json", "not an earlier revision"),
    ("nonsense", "not a ledger filename"),
])
def test_red_on_bad_chain_entry(tmp_path, entry, problem):
    root = _copy_ledgers(tmp_path)
    _edit(root, "SERVING_r02.json",
          lambda d: d["compared_to"].update(entry=entry))
    _, problems = perf_ledger.check(root)
    assert any("SERVING_r02.json" in p and problem in p
               for p in problems)
