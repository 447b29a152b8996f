"""The reader of the prompt chunk's attention kernel on a hand-made
trace (``test_program_metrics.py``'s manner), and its entry."""

import json
import os

import pytest

from perfbench import common

NAME = "ops.paged_prefill_time_share.decode"
KERNEL = "dtt_paged_prefill.%d custom-call:tpu_custom_call"


def test_paged_prefill_time_share_sums_the_named_kernel_only():
    read = common.load_file("layer_metrics", NAME).read
    # One instruction a scan of like layers: twelve layers in six scans.
    ops = {KERNEL % 1: 0.11, KERNEL % 7: 0.03, KERNEL % 12: 0.06,
           "dtt_flash_fwd.1 custom-call:tpu_custom_call": 0.3,
           "dtt_paged_prefill.3 fusion": 0.5, "fusion.1130 fusion": 0.6}
    assert read({"trace": {"op_self_s": ops, "window_s": 4.0}}) == \
        pytest.approx(5.0)


def test_a_program_without_the_kernel_gives_nothing():
    """The parent's prefill program, and every engine whose shapes stay
    under the rule: not found is not zero."""
    read = common.load_file("layer_metrics", NAME).read
    ops = {"fusion.1130 fusion": 0.598, "fusion.1139 fusion": 0.498,
           "closed_call.7 custom-call:tpu_custom_call": 0.2}
    assert read({"trace": {"op_self_s": ops, "window_s": 4.0}}) is None


def test_the_entry_names_the_reader_and_the_cell():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    module = common.load_file("layer_metrics", NAME)
    assert entry == {"name": NAME, "unit": module.UNIT,
                     "better": module.BETTER, "source": module.SOURCE,
                     "layer": module.LAYER, "moves": module.MOVES,
                     "workloads": ["smallthinker_ep4.serve_long"]}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] != NAME}
