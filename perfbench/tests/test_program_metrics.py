"""The readers of what the program itself writes (kernel names, the
``phase_s`` and counters of a step record, the ``submitted`` event, the
``serving.*`` trace annotations), each on a hand-made ``obs``, and
``program_spans`` on the trace recorded on a TPU v5e."""

import os
import shutil
import time

import pytest

from perfbench import common, program_spans
from perfbench.tests.test_trace_reduce import SMALL

FWD = "dtt_flash_fwd.1 custom-call:tpu_custom_call"
BWD = "dtt_flash_bwd_fused.3 custom-call:tpu_custom_call"


def reader(name):
    return common.load_file("layer_metrics", name)


def step(op, dur_s=0.5, fetch=0.4, **more):
    return {"op": op, "dur_s": dur_s, "tokens": 0, "host_syncs": 0,
            "phase_s": {"admit": 0.01, "pack": 0.02, "launch": 0.03,
                        "fetch": fetch, "emit": 0.04}, **more}


def test_flash_time_share_sums_the_named_kernels_only():
    read = reader("ops.flash_time_share.train").read
    ops = {FWD: 0.05, BWD: 0.12,
           "dtt_flash_bwd_dq.2 custom-call:tpu_custom_call": 0.01,
           "dtt_flash_bwd_dkv.4 custom-call:tpu_custom_call": 0.02,
           "checkpoint.10 custom-call:tpu_custom_call": 0.3,
           "dtt_flash_fwd.1 fusion": 0.3, "fusion.2 fusion": 0.4}
    assert read({"trace": {"op_self_s": ops, "window_s": 2.0}}) == \
        pytest.approx(10.0)
    # A kernel that is not found is not a kernel that took no time.
    unnamed = {"closed_call.7 custom-call:tpu_custom_call": 0.2}
    assert read({"trace": {"op_self_s": unnamed,
                           "window_s": 2.0}}) is None


def test_host_ms_per_launch_is_the_step_less_its_fetch():
    read = reader("engine.host_ms_per_launch.decode").read
    steps = [step("decode", 0.5, 0.4), step("prefill", 0.25, 0.05),
             step("idle", 9.0, 0.0)]
    assert read({"engine_steps": steps}) == pytest.approx(150.0)
    # The parent's records have no phases.
    assert read({"engine_steps": [{"op": "decode", "dur_s": 0.5}]}) \
        is None


def test_ttft_and_mailbox_wait_read_the_request_records():
    spans = lambda t: [{"ev": "queued", "t": 0.0},  # noqa: E731
                       {"ev": "submitted", "t": t},
                       {"ev": "admitted", "t": t + 0.05}]
    traces = [{"ttft_s": 0.2, "spans": spans(0.001)},
              {"ttft_s": 0.4, "spans": spans(0.003)},
              {"ttft_s": 1.2, "spans": spans(0.002)},
              {"ttft_s": None, "spans": [{"ev": "queued", "t": 0.0}]}]
    obs = {"serving_traces": traces}
    assert reader("engine.ttft_p50_ms.decode").read(obs) == \
        pytest.approx(400.0)
    assert reader("server.mailbox_wait_p50_ms.decode").read(obs) == \
        pytest.approx(2.0)
    old = {"serving_traces": [{"ttft_s": 0.2, "spans": spans(0)[::2]}]}
    assert reader("server.mailbox_wait_p50_ms.decode").read(old) is None


def test_counters_are_read_from_the_step_records_alone():
    steps = [step("decode", tokens=30, slots_stepped=4, slot_iters=31,
                  host_syncs=1),
             step("decode", tokens=10, slots_stepped=2, slot_iters=10,
                  host_syncs=1),
             step("prefill", tokens=256, first_tokens=2, host_syncs=1),
             step("prefill", tokens=128, first_tokens=0, host_syncs=0),
             step("idle")]
    obs = {"engine_steps": steps}
    assert reader("engine.emitted_per_slot_iter.decode").read(obs) == \
        pytest.approx(40 / 41)
    assert reader("engine.host_syncs_per_emitted_tok.decode").read(
        obs) == pytest.approx(3 / 42)
    old = {"engine_steps": [{"op": "decode", "tokens": 8,
                             "host_syncs": 1}]}
    assert reader("engine.emitted_per_slot_iter.decode").read(old) \
        is None
    assert reader("engine.host_syncs_per_emitted_tok.decode").read(
        old) is None


@pytest.mark.parametrize("prefix,want", [
    # The recorded trace predates the program's spans: under their
    # prefix all of its idle is uncovered.
    (program_spans.PREFIX, {program_spans.UNCOVERED: 17192383}),
    # Under the benchmark's own prefix it has to repeat what
    # trace_reduce.reduce attributes (test_trace_reduce.py).
    ("perfbench.", {"perfbench.next_batch": 10176443,
                    program_spans.UNCOVERED: 3911000,
                    "perfbench.fetch_host": 1888110,
                    "perfbench.train_step": 1216830})])
def test_idle_by_span_on_the_recorded_v5e_trace(prefix, want):
    table = program_spans.idle_by_span(SMALL, prefix)
    assert table["window_s"] == pytest.approx(17221470e-9, rel=1e-12)
    assert {k: round(v * 1e9) for k, v in table["idle_s"].items()} \
        == want
    assert sum(table["idle_s"].values()) == pytest.approx(
        (17221470 - 29087) * 1e-9, rel=1e-9)


def test_idle_share_finds_this_runs_trace_or_reports_nothing(
        monkeypatch, tmp_path):
    """The readers get no path: the trace is the newest one under
    ``perfbench_out/trace/`` that this process wrote."""
    monkeypatch.setattr(common, "OUT", str(tmp_path))
    obs = {"trace": {"window_s": 1.0}}
    names = ("perfbench.fetch_host", "perfbench.train_step")
    assert program_spans.idle_share(obs, names) is None   # no trace
    run_dir = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
    run_dir.mkdir(parents=True)
    shutil.copy(SMALL, run_dir / "host.xplane.pb")
    assert program_spans.this_runs_xplane() == str(
        run_dir / "host.xplane.pb")
    assert program_spans.idle_share({}, names) is None    # not traced
    assert program_spans.idle_share(obs, names, "perfbench.") == \
        pytest.approx(100.0 * (1888110 + 1216830) / 17221470)
    # None of the names in the trace: the program has no such span.
    assert program_spans.idle_share(
        obs, program_spans.ENGINE_SPANS) is None
    # A trace older than this process is another run's.
    old = time.time() - 7 * 86400
    os.utime(run_dir / "host.xplane.pb", (old, old))
    assert program_spans.this_runs_xplane() is None


SERVING = ["engine.host_ms_per_launch.decode", "engine.ttft_p50_ms.decode",
           "server.mailbox_wait_p50_ms.decode",
           "engine.emitted_per_slot_iter.decode",
           "engine.host_syncs_per_emitted_tok.decode",
           "engine.idle_share.decode", "server.idle_share.decode"]


def test_the_serving_readers_on_a_real_engines_records(
        monkeypatch, tmp_path, capsys):
    """The toy closed-loop cell through the real harness, in a
    rehearsal root of its own whose ``BENCHMARK.json`` lists the new
    serving metrics: the readers of records report, the two that need
    this run's ``.xplane.pb`` find none (the CPU rehearsal's trace is
    canned) and leave their metric out without raising."""
    import json

    from perfbench import run
    from perfbench.tests import rehearse
    from perfbench.tests.test_rehearsal import TINY

    root = tmp_path / "root"
    shutil.copytree(TINY, root)
    monkeypatch.setattr(common, "OUT", str(tmp_path / "out"))
    bench = run.load_json(str(root), "BENCHMARK.json")
    real = {m["name"]: m for m in run.load_json(
        common.ROOT, "BENCHMARK.json")["per_layer"]}
    bench["per_layer"] += [{**real[n], "workloads": ["tiny.closed"]}
                           for n in SERVING]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rehearse.admit_cpu(monkeypatch.setattr)
    assert run.main(["--workload", "tiny.closed", "--seed", "3000000019",
                     "--seconds", "2", "--trace", "1"],
                    root=str(root)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = {n: line["metrics"][n]["value"] for n in SERVING
           if n in line["metrics"]}
    assert set(got) == set(SERVING[:5])
    assert got["engine.host_ms_per_launch.decode"] > 0
    assert got["engine.ttft_p50_ms.decode"] > \
        got["server.mailbox_wait_p50_ms.decode"] >= 0
    assert 1.0 <= got["engine.emitted_per_slot_iter.decode"]
    assert 0 < got["engine.host_syncs_per_emitted_tok.decode"] <= 1.0
