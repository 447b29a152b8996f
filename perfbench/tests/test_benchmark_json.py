"""``BENCHMARK.json`` against the files it names and the contract's
limits that can be checked without a chip."""

import json
import os
import re

import pytest

from perfbench import common, run, yardstick

ROOT = common.ROOT
BENCH = run.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in BENCH[g]]
    names += [c["name"] for c in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names + [c["traffic"] for c in BENCH["workloads"]]:
        assert NAME.match(n), n
    for g in ("end_to_end", "per_layer"):
        for m in BENCH[g]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_files_exist_and_cells_are_consistent():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        body = run.load_json(ROOT, c["file"])
        ref = os.path.join(common.HERE, "reference",
                           body["reference"] + ".py")
        assert os.path.isfile(ref)
    used = set()
    for cell in BENCH["workloads"]:
        assert cell["config"] in configs
        used.add(cell["config"])
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        t = run.load_json(common.HERE, "traffic", cell["traffic"] + ".json")
        kind = t["kind"].split("_")[0]
        assert os.path.isfile(os.path.join(common.HERE, "drivers",
                                           kind + ".py"))
        if kind == "train":
            from perfbench.drivers import train
            for key in train.TOLERANCES.values():
                assert 0 < t[key] < 1, (cell["traffic"], key)
            assert t["tolerance_why"]
    assert used == set(configs)
    four = sum(c["chips"] == 4 for c in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_every_cell_reports_setup_and_more():
    for cell in BENCH["workloads"]:
        e2e = [m["name"] for m in run.metrics_of(BENCH, "end_to_end",
                                                 cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_of(BENCH, "per_layer", cell["name"])
    for m in BENCH["end_to_end"]:
        assert m["bound"] <= 0.1 and m["source"] in ("host_clock",
                                                     "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_reader_file_agrees_with_the_entry(metric):
    mod = common.load_file("layer_metrics", metric["name"])
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        metric["layer"], metric["unit"], metric["better"],
        metric["source"], metric["moves"])
    cells = metric.get("workloads",
                       [c["name"] for c in BENCH["workloads"]])
    for cell in cells:
        assert metric["moves"] in [
            m["name"] for m in run.metrics_of(BENCH, "end_to_end", cell)]
    # A reader that finds nothing to read returns nothing.
    empty = {"engine_steps": [], "serving_traces": [], "requests": [],
             "window": (0.0, 1.0), "max_batch": 1}
    if metric["source"] in ("program_span", "program_counter") \
            and metric["layer"] != "device":
        assert mod.read(empty) is None


def test_peaks_table_refuses_an_unknown_device():
    row = yardstick.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["source"]
    with pytest.raises(KeyError):
        yardstick.peaks_for("cpu")


def test_flops_arithmetic_matches_the_program_today():
    """The benchmark's own copy, pinned against the program's at the
    time of copying; if the program's moves, this fails and the copy
    stays."""
    from distributed_training_tpu.models import build_model

    for name in ("gpt2-small", "gpt2-xl"):
        c = run.load_json(common.HERE, "configs", name + ".json")
        kw = c["program"]["kwargs"]
        model = build_model(c["program"]["build_model"], **kw)
        mine = yardstick.train_flops_per_token(
            c["n_embd"], c["n_layer"], kw["vocab_size"],
            c["n_positions"], 1024)
        assert mine == pytest.approx(model.flops_per_token(1024), rel=1e-9)
