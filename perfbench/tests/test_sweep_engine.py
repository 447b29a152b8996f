"""``sweep_engine.py`` overrides the engine geometry in a scratch copy
that the harness reads as it reads the committed files."""

from perfbench import common, run
from perfbench.tests import sweep_engine

CELL = "joyai_ep4.serve_decode"


def test_scratch_root_overrides_the_engine_of_the_cells_configuration(
        tmp_path, monkeypatch):
    monkeypatch.setattr(common, "OUT", str(tmp_path))
    root = sweep_engine.scratch_root(CELL, {"prefill_slots": 2})
    conf = run.load_json(root, "perfbench", "configs",
                         "joyai-llm-flash-ep4.json")
    kept = run.load_json(common.ROOT, "perfbench", "configs",
                         "joyai-llm-flash-ep4.json")
    assert conf["serving"]["engine"] == {**kept["serving"]["engine"],
                                         "prefill_slots": 2}
    assert conf["program"] == kept["program"]
    assert run.load_json(root, "BENCHMARK.json") == run.load_json(
        common.ROOT, "BENCHMARK.json")
    assert run.load_json(root, "perfbench", "traffic",
                         "decode_long_saturated.json")["clients"] == 32
