"""The reader of the kernel of a prompt chunk's attention under a
selection on a hand-made trace (``test_paged_prefill_metric.py``'s
manner), and its entry."""

import json
import os

import pytest

from perfbench import common

NAME = "ops.sparse_prefill_time_share.decode"
KERNEL = "dtt_sparse_prefill.%d custom-call:tpu_custom_call"


def test_sparse_prefill_time_share_sums_the_named_kernel_only():
    read = common.load_file("layer_metrics", NAME).read
    # One instruction a scan of like layers: two full layers in two.
    ops = {KERNEL % 1: 0.11, KERNEL % 2: 0.09,
           "dtt_paged_prefill.1 custom-call:tpu_custom_call": 0.3,
           "dtt_sparse_prefill.3 fusion": 0.5, "sort.28 sort": 0.6}
    assert read({"trace": {"op_self_s": ops, "window_s": 4.0}}) == \
        pytest.approx(5.0)


def test_a_program_without_the_kernel_gives_nothing():
    """The parent's prefill program (the gather form), and every engine
    without a selection: not found is not zero."""
    read = common.load_file("layer_metrics", NAME).read
    ops = {"fusion.771 fusion": 0.430, "sort.28 sort": 0.108,
           "dtt_paged_prefill.1 custom-call:tpu_custom_call": 0.2}
    assert read({"trace": {"op_self_s": ops, "window_s": 4.0}}) is None


def test_the_entry_names_the_reader_and_the_cell():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    module = common.load_file("layer_metrics", NAME)
    assert entry == {"name": NAME, "unit": module.UNIT,
                     "better": module.BETTER, "source": module.SOURCE,
                     "layer": module.LAYER, "moves": module.MOVES,
                     "workloads": ["dots3_ep8.serve_sparse"]}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] != NAME}
