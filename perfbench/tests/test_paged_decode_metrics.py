"""The two readers the ragged form of paged attention brought: the
kernel's share of a hand-made trace (``test_paged_prefill_metric.py``'s
manner), the walked share of hand-made step records
(``test_sparse_metrics.py``'s), and their entries."""

import json
import os

import pytest

from perfbench import common

KERNEL_SHARE = "ops.paged_decode_time_share.decode"
LIVE_SHARE = "kv_cache.live_read_share.decode"
KERNEL = "dtt_paged_decode.%d custom-call:tpu_custom_call"


def reader(name):
    return common.load_file("layer_metrics", name).read


def step(op, **more):
    return {"op": op, "dur_s": 0.5, "tokens": 0, **more}


def test_paged_decode_time_share_sums_the_named_kernel_only():
    # One instruction a scan of like layers: global and window runs.
    ops = {KERNEL % 2: 0.9, KERNEL % 5: 0.5, KERNEL % 11: 0.2,
           "dtt_paged_prefill.42 custom-call:tpu_custom_call": 0.3,
           "dtt_paged_decode.3 fusion": 0.5, "fusion.1106 fusion": 0.26}
    assert reader(KERNEL_SHARE)(
        {"trace": {"op_self_s": ops, "window_s": 4.0}}) == \
        pytest.approx(40.0)


def test_a_program_without_the_kernel_gives_nothing():
    """The parent's resident decode, and every engine whose shapes keep
    the pool or the gather form: not found is not zero."""
    ops = {"fusion.1051 fusion": 0.137, "reshape.2382 reshape": 0.116,
           "dtt_paged_prefill.42 custom-call:tpu_custom_call": 0.008}
    assert reader(KERNEL_SHARE)(
        {"trace": {"op_self_s": ops, "window_s": 4.0}}) is None


def test_the_live_read_share_is_of_the_decode_records():
    steps = [step("decode", slot_iters=256, kv_pages_walked=300_000,
                  kv_pages_tabled=1_000_000),
             step("decode", slot_iters=64, kv_pages_walked=100_000,
                  kv_pages_tabled=250_000),
             # A decode launch that stepped nothing tabled nothing.
             step("decode", slot_iters=0, kv_pages_walked=0,
                  kv_pages_tabled=0),
             step("prefill", kv_pages_walked=7, kv_pages_tabled=9),
             step("idle")]
    assert reader(LIVE_SHARE)({"engine_steps": steps}) == \
        pytest.approx(0.32)


def test_records_without_the_counters_give_nothing():
    # The parent's records, and a program in the pool or gather form.
    steps = [step("decode", slot_iters=16, slots_stepped=4,
                  window_bound_iters=3, pages_used=5, pages_total=10),
             step("prefill", pages_used=5, pages_total=10)]
    assert reader(LIVE_SHARE)({"engine_steps": steps}) is None
    assert reader(LIVE_SHARE)({"engine_steps": []}) is None


@pytest.mark.parametrize("name", [KERNEL_SHARE, LIVE_SHARE])
def test_the_entry_names_the_reader_and_the_ragged_cells(name):
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    module = common.load_file("layer_metrics", name)
    cells = entry.pop("workloads")
    assert entry == {"name": name, "unit": module.UNIT,
                     "better": module.BETTER, "source": module.SOURCE,
                     "layer": module.LAYER, "moves": module.MOVES}
    # The cells whose resident decode reads a layer in the ragged form,
    # and no other: elsewhere the reader has nothing to read.
    assert cells == ["gpt2xl.serve_decode",
                     "smallthinker_ep4.serve_long"]
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] != name}
