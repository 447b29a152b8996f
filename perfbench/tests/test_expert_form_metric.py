"""The reader of the rows a prompt chunk's expert layers compute a held
pick, on hand-made step records (``test_moe_metrics.py``'s manner), and
its entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from perfbench import common

NAME = "moe.prefill_rows_per_held_pick.decode"
CELLS = ["joyai_ep4.serve_decode", "smallthinker_ep4.serve_long",
         "dots3_ep8.serve_sparse", "commanda_ep16.serve_rag"]


def read(steps):
    return common.load_file("layer_metrics", NAME).read(
        {"engine_steps": steps})


def step(op, **more):
    return {"op": op, "dur_s": 0.04, "tokens": 0, **more}


def test_rows_a_pick_are_read_from_the_prefill_records_that_fetched():
    steps = [step("prefill", moe_picks_held=500, moe_rows_computed=1536),
             step("prefill", moe_picks_held=524, moe_rows_computed=1536),
             # a chunk that ended no prompt: nothing fetched
             step("prefill"),
             # the resident loop's records are not read
             step("decode", moe_picks_held=64, moe_rows_computed=8192),
             step("idle")]
    assert read(steps) == pytest.approx(3072 / 1024)


def test_the_dense_form_reads_held_experts_times_rows_over_picks():
    # command-a's chunk: 1,024 rows x 8 held, about 512 held picks
    assert read([step("prefill", moe_picks_held=512,
                      moe_rows_computed=8192)]) == pytest.approx(16.0)


@pytest.mark.parametrize("steps", [
    [],
    # the parent's records: the counters without the rows
    [step("prefill", moe_picks=4096, moe_picks_held=512), step("decode")],
    # a model without experts
    [step("prefill", first_tokens=1)],
    # no pick landed here
    [step("prefill", moe_picks_held=0, moe_rows_computed=0)],
])
def test_records_without_rows_give_nothing(steps):
    assert read(steps) is None


def test_the_entry_names_the_reader_and_the_four_expert_cells():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[NAME]
    mod = common.load_file("layer_metrics", NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT,
                     "better": mod.BETTER, "source": mod.SOURCE,
                     "layer": mod.LAYER, "moves": mod.MOVES,
                     "workloads": CELLS}
