"""``engine.run_ahead_share.decode`` on hand-made step records, on the
records of a program that writes no ``ran_ahead``, and its entry in
``BENCHMARK.json``."""

import pytest

from perfbench import common, run


def read(steps):
    return common.load_file(
        "layer_metrics", "engine.run_ahead_share.decode").read(
            {"engine_steps": steps})


def test_run_ahead_share_is_of_the_steps_that_launched():
    steps = [{"op": "prefill", "ran_ahead": 0},
             {"op": "decode", "ran_ahead": 1},
             {"op": "decode", "ran_ahead": 1},
             {"op": "prefill", "ran_ahead": 1},
             {"op": "idle"}]
    assert read(steps) == pytest.approx(75.0)
    assert read([{"op": "decode", "ran_ahead": 0}]) == 0.0


def test_records_without_the_counter_read_nothing():
    # The parent's records: the metric is left out, nothing raises.
    assert read([{"op": "decode", "dur_s": 0.5}, {"op": "idle"}]) is None
    assert read([]) is None


def test_the_entry_names_the_reader_and_both_serving_cells():
    bench = run.load_json(common.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "engine.run_ahead_share.decode"]
    reader = common.load_file("layer_metrics", entry["name"])
    assert (entry["layer"], entry["unit"], entry["better"],
            entry["source"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE,
        reader.MOVES)
    assert entry["workloads"] == ["gpt2xl.serve_decode",
                                  "joyai_ep4.serve_decode"]
    assert bench["per_layer"][-1] is entry
