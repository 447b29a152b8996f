"""The trace reduction: its interval arithmetic on hand-made timelines,
and the whole of it on the small trace recorded on a TPU v5e
(``data/small.xplane.pb``), with exact expected numbers."""

import numpy as np
import pytest

from perfbench import trace_reduce as tr


def test_union_merges_overlapping_and_touching_intervals():
    got = tr.union(np.array([[0, 5], [3, 8], [10, 12], [12, 13], [20, 21.]]))
    assert got.tolist() == [[0, 8], [10, 13], [20, 21]]
    assert tr.union(np.zeros((0, 2))).shape == (0, 2)


def test_covered_measures_inside_each_query():
    merged = tr.union(np.array([[0, 5], [3, 8], [10, 12.]]))
    got = tr.covered(merged, [4., 0, 9, -5, 11], [11., 20, 9.5, 1, 11.5])
    assert got.tolist() == [5, 10, 0, 1, 0.5]


def test_self_intervals_give_each_instant_to_the_innermost_event():
    ev = [("while", 0, 100), ("a", 10, 30), ("b", 30, 50), ("c", 60, 80),
          ("d", 200, 260)]
    assert tr.self_intervals(ev) == [
        ("while", 0, 10), ("a", 10, 30), ("b", 30, 50), ("while", 50, 60),
        ("c", 60, 80), ("while", 80, 100), ("d", 200, 260)]


def test_reduce_on_a_hand_made_timeline():
    """Window 0-300 ns. Device busy 0-100 (a while holding a, b and an
    all-gather) and 200-260. Host: train_step 90-210 with x nested
    100-150."""
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 30, 50),
           ("all-gather-done.3", 60, 80), ("fusion.4", 200, 260)]
    trace = {"devices": {"/device:TPU:0": ops,
                         "/device:TPU:1": [("fusion.9", 0, 300)]},
             "annotations": [("perfbench.window", 0, 300),
                             ("perfbench.train_step", 90, 210),
                             ("perfbench.x", 100, 150)]}
    r = tr.reduce(trace)
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s_by_device"] == pytest.approx([160e-9, 300e-9])
    assert r["busy_s"] == pytest.approx(230e-9)
    assert r["idle_share"] == pytest.approx(1 - 230 / 300)
    # Operations and gaps are device 0's.
    assert r["op_self_s"]["while.1"] == pytest.approx(40e-9)
    assert r["device_ops"][0] == ["fusion.4", pytest.approx(60e-9)]
    assert r["exposed_collective_s"] == pytest.approx(20e-9)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx({
        "perfbench.train_step": 50e-9, "perfbench.x": 50e-9,
        "_no_annotation_": 40e-9})


def test_exposed_collective_time_is_the_collectives_self_time():
    """A synchronous all-reduce, and an asynchronous all-gather whose
    ``-start`` is short and whose ``-done`` lasts as long as the core
    waits; the fusion between them is compute, not a collective."""
    ops = [("all-reduce.2 all-reduce", 0, 40),
           ("all-gather-start.3 all-gather-start", 40, 41),
           ("fusion.1 fusion", 41, 100),
           ("all-gather-done.3 all-gather-done", 100, 130)]
    r = tr.reduce({"devices": {"/device:TPU:0": ops}, "annotations": []})
    assert r["exposed_collective_s"] == pytest.approx(71e-9)
    assert r["op_self_s"]["fusion.1 fusion"] == pytest.approx(59e-9)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "annotations": []})


SMALL = __file__.rsplit("/", 1)[0] + "/data/small.xplane.pb"


def test_short_name_keeps_name_opcode_and_custom_call_target():
    assert tr.short_name(
        "%fusion.167 = bf16[32,64]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8]{0} "
        "%p), kind=kLoop, calls=%fused") == "fusion.167 fusion"
    assert tr.short_name(
        "%while = (s32[]{:T(128)}, bf16[256,512]{1,0:T(8,128)(2,1)}) "
        "while((s32[]{:T(128)}, bf16[256,512]{1,0}) %t), body=%b"
    ) == "while while"
    assert tr.short_name(
        '%closed_call.7 = (bf16[2]{0}, f32[2]{0}) custom-call(bf16[2]{0} '
        '%a), custom_call_target="tpu_custom_call", operand_layout={}'
    ) == "closed_call.7 custom-call:tpu_custom_call"
    assert tr.short_name("no equals sign") == "no equals sign"


def test_reduce_on_the_recorded_v5e_trace():
    """Recorded on one TPU v5e chip in PR 24 (``perfbench.window`` around
    four rounds of sleep 2 ms, a small jitted matmul loop, fetch). The
    expected numbers were also counted by brute force on a 1 ns grid."""
    trace = tr.load(SMALL)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert len(trace["devices"]["/device:TPU:0"]) == 68
    assert len(trace["annotations"]) == 13
    r = tr.reduce(trace)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(17221470 * ns, rel=1e-12)
    assert r["busy_s"] == pytest.approx(29087 * ns, rel=1e-12)
    assert r["idle_share"] == pytest.approx(1 - 29087 / 17221470, rel=1e-12)
    assert r["exposed_collective_s"] == 0
    assert {k: round(v / ns) for k, v in r["op_self_s"].items()} == {
        "copy-done.1 copy-done": 12682,
        "convolution_tanh_fusion.2 fusion": 9581,
        "copy-done copy-done": 3218, "broadcast_add_fusion fusion": 2559,
        "copy.12 copy": 535, "reduce reduce": 314, "while while": 123,
        "copy-start.1 copy-start": 54, "copy-start copy-start": 21}
    assert r["device_ops"][0][0] == "copy-done.1 copy-done"
    assert {k: round(v / ns) for k, v in r["idle_gaps"]} == {
        "perfbench.next_batch": 10176443, "_no_annotation_": 3911000,
        "perfbench.fetch_host": 1888110, "perfbench.train_step": 1216830}
    assert sum(v for _k, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-12)
