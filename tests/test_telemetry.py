"""Telemetry subsystem: span nesting + events.jsonl schema, goodput
ledger accounting, hang watchdog postmortems, HBM sampling, the
summarizer CLI, and the end-to-end CPU demo (trainer wiring: a tiny
run must produce metrics.jsonl + events.jsonl + a goodput report whose
buckets sum to wall-clock)."""

import json
import os
import time

import numpy as np
import pytest

from distributed_training_tpu import telemetry
from distributed_training_tpu.config import Config
from distributed_training_tpu.data import (ShardedDataLoader,
                                           SyntheticRegressionDataset)
from distributed_training_tpu.models import build_model
from distributed_training_tpu.telemetry.goodput import GoodputLedger
from distributed_training_tpu.telemetry.hbm import HBMSampler
from distributed_training_tpu.telemetry.watchdog import (
    HangWatchdog, write_postmortem)
from distributed_training_tpu.train.trainer import Trainer


@pytest.fixture(autouse=True)
def _fresh_ambient():
    """Ambient telemetry is process state (like the root logger);
    every test starts and ends uninstalled."""
    telemetry.uninstall()
    yield
    telemetry.uninstall()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- spans / events --------------------------------------------------------


def test_spans_nest_and_record_depth_parent(tmp_path):
    path = str(tmp_path / "events.jsonl")
    t = telemetry.Telemetry(events_jsonl=path)
    with t.span("outer"):
        with t.span("inner", step=3):
            pass
    rows = _read_jsonl(path)
    assert rows[0]["kind"] == "run_start"
    inner, outer = rows[1], rows[2]  # inner closes first
    assert (inner["name"], inner["depth"], inner["parent"]) == \
        ("inner", 1, "outer")
    assert inner["step"] == 3
    assert (outer["name"], outer["depth"], outer["parent"]) == \
        ("outer", 0, None)
    assert outer["dur_s"] >= inner["dur_s"] >= 0


def test_span_reentrant_after_exception(tmp_path):
    t = telemetry.Telemetry(events_jsonl=str(tmp_path / "e.jsonl"))
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    # The stack must unwind: a following span is depth 0 again.
    with t.span("after"):
        pass
    rows = _read_jsonl(str(tmp_path / "e.jsonl"))
    assert rows[-1]["name"] == "after" and rows[-1]["depth"] == 0


def test_ambient_span_is_noop_until_installed(tmp_path):
    with telemetry.span("nobody-listening"):
        pass  # must not raise, must not write anywhere
    path = str(tmp_path / "events.jsonl")
    telemetry.install(telemetry.Telemetry(events_jsonl=path))
    with telemetry.span("recorded"):
        pass
    telemetry.event("ping", n=1)
    names = [r.get("name", r["kind"]) for r in _read_jsonl(path)]
    assert names == ["run_start", "recorded", "ping"]


def test_tail_is_bounded(tmp_path):
    t = telemetry.Telemetry(events_jsonl=str(tmp_path / "e.jsonl"),
                            tail_events=4)
    for i in range(10):
        t.event("tick", i=i)
    tail = t.tail()
    assert len(tail) == 4 and tail[-1]["i"] == 9


def test_nan_fields_sanitized(tmp_path):
    path = str(tmp_path / "e.jsonl")
    t = telemetry.Telemetry(events_jsonl=path)
    t.event("stats", value=float("nan"))
    assert _read_jsonl(path)[-1]["value"] is None


def test_close_stops_recording_keeps_tail(tmp_path):
    path = str(tmp_path / "e.jsonl")
    t = telemetry.Telemetry(events_jsonl=path)
    t.event("before", i=1)
    t.close()
    t.close()  # idempotent
    t.event("after", i=2)  # no-op, must not raise on a closed handle
    assert [r["kind"] for r in _read_jsonl(path)] == \
        ["run_start", "before"]
    assert t.tail()[-1]["kind"] == "before"


def test_fresh_false_appends_not_truncates(tmp_path):
    """The resume/eval path: fresh=False must append after a run_start
    marker, never wipe the training run's stream."""
    path = str(tmp_path / "e.jsonl")
    t1 = telemetry.Telemetry(events_jsonl=path)
    t1.event("train_era", i=1)
    t1.close()
    t2 = telemetry.Telemetry(events_jsonl=path, fresh=False)
    t2.event("eval_era", i=2)
    kinds = [r["kind"] for r in _read_jsonl(path)]
    assert kinds == ["run_start", "train_era", "run_start", "eval_era"]


# -- goodput ledger --------------------------------------------------------


def test_ledger_buckets_sum_to_wall_clock(tmp_path):
    t = telemetry.Telemetry(events_jsonl=str(tmp_path / "e.jsonl"))
    ledger = GoodputLedger(flops_per_step=1e6, num_devices=2,
                           peak_flops=1e9)
    t.attach_ledger(ledger)
    ledger.reset()
    wall0 = time.perf_counter()
    with t.span("compile"):
        time.sleep(0.03)
    for _ in range(3):
        with t.span("data_wait"):
            time.sleep(0.005)
        with t.span("step"):
            time.sleep(0.02)
    with t.span("ckpt_save"):
        time.sleep(0.01)
    time.sleep(0.02)  # untracked -> idle
    rep = ledger.report()
    wall = time.perf_counter() - wall0
    b = rep["buckets"]
    # Tracked + idle sums to the ledger's wall exactly (idle is
    # derived); the ledger's wall tracks the external clock.
    assert sum(b.values()) == pytest.approx(rep["wall_s"], rel=0.02)
    assert rep["wall_s"] == pytest.approx(wall, rel=0.05, abs=0.02)
    assert rep["steps"] == 3
    assert b["compile"] >= 0.03 and b["checkpoint"] >= 0.01
    assert b["idle"] >= 0.015
    assert 0 < rep["goodput"] < 1
    # MFU arithmetic: steps * flops / (wall * devices * peak).
    assert rep["mfu_wall"] == pytest.approx(
        3 * 1e6 / (rep["wall_s"] * 2 * 1e9), rel=0.01)


def test_nested_span_does_not_double_count(tmp_path):
    t = telemetry.Telemetry(events_jsonl=str(tmp_path / "e.jsonl"))
    ledger = GoodputLedger()
    t.attach_ledger(ledger)
    with t.span("step"):
        with t.span("ckpt_save"):  # nested: events-only
            time.sleep(0.01)
    rep = ledger.report()
    assert rep["buckets"]["checkpoint"] == 0.0
    assert rep["buckets"]["step"] >= 0.01


def test_window_report_resets(tmp_path):
    ledger = GoodputLedger()
    ledger.add("step", 0.5, steps=1)
    w1 = ledger.window_report()
    assert w1["buckets"]["step"] == 0.5 and w1["steps"] == 1
    w2 = ledger.window_report()
    assert w2["buckets"]["step"] == 0.0 and w2["steps"] == 0
    # The cumulative report still carries everything.
    assert ledger.report()["buckets"]["step"] == 0.5


# -- watchdog --------------------------------------------------------------


def _postmortem_complete(path):
    names = set(os.listdir(path))
    return {"meta.json", "stacks.txt", "events_tail.jsonl",
            "memory_stats.json"} <= names


def test_watchdog_fires_on_stall_and_writes_postmortem(tmp_path):
    tel = telemetry.Telemetry(
        events_jsonl=str(tmp_path / "e.jsonl"))
    tel.event("before_stall", step=7)
    wd = HangWatchdog(0.15, str(tmp_path / "pm"), telemetry=tel,
                      poll_s=0.02)
    try:
        wd.arm(step=7)
        time.sleep(0.6)  # the "stalled step"
    finally:
        wd.stop()
    assert wd.fired_path and _postmortem_complete(wd.fired_path)
    meta = json.load(open(os.path.join(wd.fired_path, "meta.json")))
    assert meta["step"] == 7 and meta["watchdog_timeout_s"] == 0.15
    stacks = open(os.path.join(wd.fired_path, "stacks.txt")).read()
    assert "Thread" in stacks or "Stack" in stacks
    tail = _read_jsonl(os.path.join(wd.fired_path,
                                    "events_tail.jsonl"))
    assert any(r.get("kind") == "before_stall" for r in tail)
    # The firing itself is in the event stream.
    kinds = [r["kind"] for r in _read_jsonl(str(tmp_path / "e.jsonl"))]
    assert "watchdog_fired" in kinds


def test_watchdog_disarm_prevents_firing(tmp_path):
    wd = HangWatchdog(0.1, str(tmp_path / "pm"), poll_s=0.02)
    try:
        wd.arm(step=1)
        time.sleep(0.04)
        wd.disarm()
        time.sleep(0.3)
    finally:
        wd.stop()
    assert wd.fired_path is None
    assert not os.path.exists(str(tmp_path / "pm"))


def test_watchdog_per_arm_timeout_override(tmp_path):
    # The trainer gives the compile step a larger allowance; an armed
    # override must be honored for that arm only.
    wd = HangWatchdog(0.05, str(tmp_path / "pm"), poll_s=0.02)
    try:
        wd.arm(step=1, timeout_s=1.0)
        time.sleep(0.2)  # beyond default, inside override: no fire
        assert wd.fired_path is None
        wd.arm(step=2)
        time.sleep(0.25)  # default applies again: fires
    finally:
        wd.stop()
    assert wd.fired_path is not None


def test_write_postmortem_unique_dirs(tmp_path):
    p1 = write_postmortem(str(tmp_path), "first")
    p2 = write_postmortem(str(tmp_path), "second")
    assert p1 != p2 and _postmortem_complete(p1) \
        and _postmortem_complete(p2)


# -- hbm sampler -----------------------------------------------------------


class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_hbm_sampler_cadence_and_schema(tmp_path):
    path = str(tmp_path / "e.jsonl")
    tel = telemetry.Telemetry(events_jsonl=path)
    devices = [_FakeDevice({"bytes_in_use": 10, "peak_bytes_in_use": 99,
                            "irrelevant_counter": 5}),
               _FakeDevice(None),
               _FakeDevice(RuntimeError("backend wedged"))]
    s = HBMSampler(tel, every=2, estimate_bytes=123, devices=devices)
    s.maybe_sample(1)   # off cadence
    s.maybe_sample(2)   # samples
    rows = [r for r in _read_jsonl(path) if r["kind"] == "hbm"]
    assert len(rows) == 1
    rec = rows[0]
    assert rec["step"] == 2 and rec["estimate_bytes"] == 123
    d0, d1, d2 = rec["devices"]
    assert d0["stats"] == {"bytes_in_use": 10, "peak_bytes_in_use": 99}
    assert d1["stats"] is None                # CPU-style backend
    assert "backend wedged" in d2["error"]    # never raises


# -- summarizer CLI --------------------------------------------------------


def _synthetic_run_dir(tmp_path):
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    with open(run_dir / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"run_start": True, "step": 0}) + "\n")
        f.write(json.dumps({"epoch": 0, "step": 1, "loss": 2.0,
                            "warmup": True}) + "\n")
        for i, loss in ((2, 1.5), (3, 1.0)):
            f.write(json.dumps(
                {"epoch": 0, "step": i, "loss": loss,
                 "steps_per_sec": 10.0,
                 "samples_per_sec_per_chip": 40.0,
                 "mfu": 0.3 + i / 100}) + "\n")
        f.write("{torn line\n")  # crashed-writer tolerance
    with open(run_dir / "events.jsonl", "w") as f:
        f.write(json.dumps({"kind": "run_start", "t": 0.0,
                            "step": 0}) + "\n")
        for name, dur in (("compile", 2.0), ("data_wait", 0.1),
                          ("step", 0.5), ("step", 0.5)):
            f.write(json.dumps({"kind": "span", "name": name,
                                "t": 3.0, "dur_s": dur, "depth": 0,
                                "parent": None}) + "\n")
        f.write(json.dumps(
            {"kind": "goodput", "scope": "run", "t": 4.0,
             "wall_s": 4.0, "steps": 2, "goodput": 0.25,
             "buckets": {"compile": 2.0, "data_wait": 0.1,
                         "step": 1.0, "checkpoint": 0.0,
                         "eval": 0.0, "idle": 0.9}}) + "\n")
        f.write(json.dumps(
            {"kind": "hbm", "t": 3.5, "step": 2, "estimate_bytes": 64,
             "devices": [{"id": 0, "stats":
                          {"peak_bytes_in_use": 2 ** 30}}]}) + "\n")
    (run_dir / "postmortem" / "x_pid1").mkdir(parents=True)
    return run_dir


def test_summarize_run_synthetic(tmp_path):
    from distributed_training_tpu.telemetry.summarize import (
        render, summarize_run)
    s = summarize_run(str(_synthetic_run_dir(tmp_path)))
    assert s["loss"]["first"] == 2.0 and s["loss"]["last"] == 1.0
    # warmup row excluded from trajectories
    assert s["mfu"]["first"] == pytest.approx(0.32)
    assert s["mfu"]["last"] == pytest.approx(0.33)
    assert s["goodput"]["goodput"] == 0.25
    assert s["hbm"]["peak_gib"] == 1.0
    assert s["postmortems"] == ["x_pid1"]
    text = render(s)
    assert "goodput" in text and "postmortem bundle" in text


def test_summarizer_cli_renders_and_json(tmp_path, capsys):
    from distributed_training_tpu.telemetry.summarize import main
    run_dir = str(_synthetic_run_dir(tmp_path))
    assert main([run_dir]) == 0
    out = capsys.readouterr().out
    assert "loss: 2 -> 1" in out
    assert main([run_dir, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["metrics_rows"] == 4
    assert main([run_dir + "/nope"]) == 2


def test_summarizer_goodput_reconstructed_without_run_event(tmp_path):
    """A killed run writes no final report; the summarizer rebuilds
    the breakdown from depth-0 spans."""
    run_dir = tmp_path / "dead"
    run_dir.mkdir()
    with open(run_dir / "events.jsonl", "w") as f:
        f.write(json.dumps({"kind": "run_start", "t": 10.0}) + "\n")
        f.write(json.dumps({"kind": "span", "name": "step", "t": 12.0,
                            "dur_s": 1.5, "depth": 0}) + "\n")
    from distributed_training_tpu.telemetry.summarize import (
        summarize_run)
    gp = summarize_run(str(run_dir))["goodput"]
    assert gp["reconstructed"] and gp["wall_s"] == 2.0
    assert gp["buckets"]["step"] == 1.5
    assert gp["buckets"]["idle"] == pytest.approx(0.5)


def test_summarizer_fallback_wall_segments_per_run_start(tmp_path):
    """An eval (or resume) appended hours after a crash must not book
    the dead time between sessions as idle: wall is summed per
    run_start segment."""
    run_dir = tmp_path / "crashed_then_evaled"
    run_dir.mkdir()
    with open(run_dir / "events.jsonl", "w") as f:
        f.write(json.dumps({"kind": "run_start", "t": 100.0}) + "\n")
        f.write(json.dumps({"kind": "span", "name": "step", "t": 102.0,
                            "dur_s": 1.5, "depth": 0}) + "\n")
        # 10000s later: eval appends its own session.
        f.write(json.dumps({"kind": "run_start", "t": 10102.0}) + "\n")
        f.write(json.dumps({"kind": "span", "name": "eval",
                            "t": 10103.0, "dur_s": 1.0,
                            "depth": 0}) + "\n")
    from distributed_training_tpu.telemetry.summarize import (
        summarize_run)
    gp = summarize_run(str(run_dir))["goodput"]
    # wall = (102-100) + (10103-10102), NOT 10103-100.
    assert gp["wall_s"] == pytest.approx(3.0)
    assert gp["buckets"]["idle"] == pytest.approx(0.5)


# -- trainer wiring (the CPU demo, as a pinned test) -----------------------


def _demo_trainer(rt, tmp_path, **train_over):
    cfg = Config()
    cfg.train.batch_size = 4
    cfg.train.total_epochs = 2
    cfg.train.save_every = 1
    cfg.train.log_every = 2
    cfg.train.dataset_size = 32
    cfg.train.hbm_sample_every = 2
    cfg.train.metrics_jsonl = str(tmp_path / "run" / "metrics.jsonl")
    cfg.train.events_jsonl = str(tmp_path / "run" / "events.jsonl")
    for k, v in train_over.items():
        setattr(cfg.train, k, v)
    model = build_model("mlp", input_size=20, output_size=1,
                        loss="mse")
    ds = SyntheticRegressionDataset(size=32, in_dim=20, out_dim=1,
                                    seed=0)
    loader = ShardedDataLoader(ds, rt, batch_size=4)
    from distributed_training_tpu.checkpoint import Checkpointer
    ckpt = Checkpointer(str(tmp_path / "run" / "ckpt"))
    return cfg, model, loader, ckpt


def test_trainer_end_to_end_telemetry(cpu8, tmp_path):
    cfg, model, loader, ckpt = _demo_trainer(cpu8, tmp_path)
    telemetry.install(telemetry.Telemetry(
        events_jsonl=cfg.train.events_jsonl))
    trainer = Trainer(cfg, cpu8, model, loader, ckpt)
    t0 = time.perf_counter()
    summary = trainer.train()
    wall = time.perf_counter() - t0
    assert np.isfinite(summary["mean_loss"])

    # Both streams exist and parse.
    metrics_rows = _read_jsonl(cfg.train.metrics_jsonl)
    events = _read_jsonl(cfg.train.events_jsonl)
    assert metrics_rows[0] == {"run_start": True, "step": 0}
    span_names = {e["name"] for e in events if e["kind"] == "span"}
    assert {"compile", "step", "data_wait", "data_assemble",
            "ckpt_save", "ckpt_wait"} <= span_names

    # The acceptance check: goodput buckets (incl. idle) sum to the
    # run's wall-clock within 5%, and wall matches reality.
    gp = summary["goodput"]
    assert sum(gp["buckets"].values()) == pytest.approx(
        gp["wall_s"], rel=0.05)
    assert gp["wall_s"] == pytest.approx(wall, rel=0.2, abs=0.5)
    assert gp["steps"] > 0 and gp["buckets"]["compile"] > 0
    assert gp["buckets"]["checkpoint"] > 0

    # Window reports on the log cadence + the final run report.
    scopes = [e["scope"] for e in events if e["kind"] == "goodput"]
    assert "window" in scopes and scopes[-1] == "run"

    # HBM samples on cadence (CPU backend: stats may be null, but the
    # cross-check estimate from utils/memory.py rides along).
    hbm = [e for e in events if e["kind"] == "hbm"]
    assert hbm and hbm[0]["estimate_bytes"] > 0

    # The summarizer renders the real run_dir without error.
    from distributed_training_tpu.telemetry.summarize import (
        render, summarize_run)
    text = render(summarize_run(str(tmp_path / "run")))
    assert "goodput" in text


def test_trainer_watchdog_fires_on_stalled_step(cpu8, tmp_path):
    """A deliberately-stalled step (slow _step_fn) must produce a
    complete postmortem bundle through the real training loop — and
    with abort=False training still completes."""
    # Two epochs = two steps: the FIRST step's compile allowance (10x)
    # covers the 0.5s stall; the second step runs at the 0.15s default
    # and must fire mid-stall.
    cfg, model, loader, ckpt = _demo_trainer(
        cpu8, tmp_path, total_epochs=2, save_every=0)
    tel = telemetry.install(telemetry.Telemetry(
        events_jsonl=cfg.train.events_jsonl))
    wd = HangWatchdog(0.15, str(tmp_path / "run" / "postmortem"),
                      telemetry=tel, poll_s=0.02)
    trainer = Trainer(cfg, cpu8, model, loader, ckpt, watchdog=wd)
    orig = trainer._step_fn

    def slow_step(state, batch, rng):
        time.sleep(0.5)  # > timeout, < the first-step 10x allowance...
        return orig(state, batch, rng)

    trainer._step_fn = slow_step
    try:
        summary = trainer.train()
    finally:
        wd.stop()
    assert np.isfinite(summary["mean_loss"])
    assert wd.fired_path and _postmortem_complete(wd.fired_path)
    events = _read_jsonl(cfg.train.events_jsonl)
    assert any(e["kind"] == "watchdog_fired" for e in events)


def test_trainer_watchdog_covers_data_wait(cpu8, tmp_path):
    """A wedged input pipeline (loader blocks, no batch arrives) is
    armed too: the watchdog must fire during the data fetch, not only
    during the step."""
    cfg, model, loader, ckpt = _demo_trainer(
        cpu8, tmp_path, total_epochs=2, save_every=0)
    tel = telemetry.install(telemetry.Telemetry(
        events_jsonl=cfg.train.events_jsonl))
    wd = HangWatchdog(0.15, str(tmp_path / "run" / "postmortem"),
                      telemetry=tel, poll_s=0.02)
    trainer = Trainer(cfg, cpu8, model, loader, ckpt, watchdog=wd)
    orig_epoch = trainer.loader.epoch

    def stalling_epoch(epoch):
        for i, batch in enumerate(orig_epoch(epoch)):
            if epoch > 0:
                time.sleep(0.6)  # the wedged-prefetch stand-in
            yield batch

    trainer.loader.epoch = stalling_epoch
    try:
        summary = trainer.train()
    finally:
        wd.stop()
    assert np.isfinite(summary["mean_loss"])
    assert wd.fired_path and _postmortem_complete(wd.fired_path)


def test_trainer_binds_telemetry_installed_after_construction(
        cpu8, tmp_path):
    """install() after Trainer() must still instrument the run: the
    trainer re-resolves the ambient sink at train() (a snapshot taken
    only at construction would silently bind the ledger and every
    trainer span to the null sink)."""
    # Two epochs = two steps (the global batch covers the dataset):
    # the first dispatch is the compile span, the second a step span.
    cfg, model, loader, ckpt = _demo_trainer(cpu8, tmp_path,
                                             total_epochs=2)
    trainer = Trainer(cfg, cpu8, model, loader, ckpt)  # before install
    telemetry.install(telemetry.Telemetry(
        events_jsonl=cfg.train.events_jsonl))
    summary = trainer.train()
    assert "goodput" in summary
    events = _read_jsonl(cfg.train.events_jsonl)
    span_names = {e["name"] for e in events if e["kind"] == "span"}
    assert {"compile", "step", "data_wait"} <= span_names


def test_trainer_no_telemetry_still_trains(cpu8, tmp_path):
    """Uninstalled ambient telemetry: spans are pure trace
    annotations; no events file, no ledger in the summary."""
    cfg, model, loader, ckpt = _demo_trainer(cpu8, tmp_path,
                                             total_epochs=1)
    trainer = Trainer(cfg, cpu8, model, loader, ckpt)
    summary = trainer.train()
    assert np.isfinite(summary["mean_loss"])
    assert "goodput" not in summary
    assert not os.path.exists(cfg.train.events_jsonl)


# -- serving observability: histograms, trace schema, SLO report -----------


def test_serving_histograms_bucket_math():
    """The tenant-labeled latency histograms against hand-computed
    cumulative bucket counts: each observation lands in EVERY bucket
    whose bound admits it (Prometheus-cumulative), +Inf equals the
    count, and the sum is exact. The hand-computed nearest-rank p50
    must fall inside the first bucket whose cumulative count reaches
    rank — the quantile a scraper would reconstruct brackets the
    true one."""
    from distributed_training_tpu.telemetry.metrics_server import (
        HIST_BUCKETS, MetricsServer)

    ms = MetricsServer(0)
    ttfts = {"a": [0.004, 0.011, 0.011, 0.3], "b": [0.05]}
    for tenant, vs in ttfts.items():
        for v in vs:
            ms.observe({"kind": "serving_request", "tenant": tenant,
                        "id": "x", "ttft_s": v, "latency_s": 2 * v,
                        "queue_wait_s": 0.0, "new_tokens": 3})
    body = ms.render()
    fam = "dtt_serving_time_to_first_token_seconds"
    # Cumulative counts for tenant a over the pinned bounds.
    bounds = HIST_BUCKETS["serving_time_to_first_token_seconds"]
    want = {b: sum(1 for v in ttfts["a"] if v <= b) for b in bounds}
    assert want[0.005] == 1 and want[0.01] == 1 \
        and want[0.025] == 3 and want[0.25] == 3 and want[0.5] == 4
    for b, c in want.items():
        bs = str(int(b)) if b == int(b) else repr(float(b))
        assert f'{fam}_bucket{{tenant="a",le="{bs}"}} {c}' in body
    assert f'{fam}_bucket{{tenant="a",le="+Inf"}} 4' in body
    assert f'{fam}_count{{tenant="a"}} 4' in body
    sum_line = [ln for ln in body.splitlines()
                if ln.startswith(f'{fam}_sum{{tenant="a"}}')][0]
    assert float(sum_line.split()[-1]) == pytest.approx(0.326)
    # le is inclusive: 0.05 lands in the 0.05 bucket.
    assert f'{fam}_bucket{{tenant="b",le="0.05"}} 1' in body
    # Nearest-rank p50 of [0.004, 0.011, 0.011, 0.3] is 0.011; the
    # first bucket with cumulative count >= 2 is le=0.025 — the
    # scrape-side quantile estimate brackets the exact one.
    from distributed_training_tpu.telemetry.serving_trace import (
        percentile)
    exact = percentile(sorted(ttfts["a"]), 50)
    est_bucket = min(b for b, c in want.items() if c >= 2)
    assert exact == 0.011 and exact <= est_bucket == 0.025
    # The four families all carry the tenant label.
    for name in ("dtt_serving_e2e_seconds",
                 "dtt_serving_queue_wait_seconds",
                 "dtt_serving_tokens_per_request"):
        assert f'{name}_count{{tenant="a"}} 4' in body
        assert f"# TYPE {name} histogram" in body


def test_serving_trace_schema_keys_pinned():
    """The serving_trace record schema is pinned: additive keys only
    (TRACE_KEYS is the contract the offline analyzer and the span
    tests consume), and the aggregate stream schema stays 1."""
    from distributed_training_tpu.telemetry import aggregate
    from distributed_training_tpu.telemetry.serving_trace import (
        OUTCOMES, SPAN_EVENTS, TRACE_KEYS)

    assert TRACE_KEYS == (
        "id", "tenant", "outcome", "prompt_tokens", "new_tokens",
        "queue_wait_s", "ttft_s", "e2e_s", "prefix_hit_tokens",
        "tokens_discarded", "spans", "weights_versions")
    assert set(SPAN_EVENTS) == {
        "queued", "submitted", "admitted", "resumed", "adopted",
        "prefill", "decode", "session_retain", "finished",
        "preempted"}
    assert OUTCOMES == ("finished", "preempted")
    assert aggregate.SCHEMA == 1


def _synthetic_serving_run(tmp_path):
    """A run dir whose events.jsonl holds hand-written serving_trace
    records with KNOWN latencies, so the report's nearest-rank
    percentiles and attainment fractions are exact pins."""
    run_dir = tmp_path / "srun"
    run_dir.mkdir()
    ttfts = {"chat": [0.01, 0.02, 0.03, 0.04, 0.05],
             "docs": [0.1, 0.2, 0.3, 0.4, 0.5]}
    with open(run_dir / "events.jsonl", "w") as f:
        f.write(json.dumps({"kind": "run_start", "t": 0.0,
                            "step": 0}) + "\n")
        i = 0
        for tenant, ts in ttfts.items():
            for ttft in ts:
                f.write(json.dumps(
                    {"kind": "serving_trace", "t": float(i),
                     "id": f"{tenant}-{i}", "tenant": tenant,
                     "outcome": "finished", "prompt_tokens": 8,
                     "new_tokens": 4, "queue_wait_s": 0.001,
                     "ttft_s": ttft, "e2e_s": ttft + 0.03,
                     "prefix_hit_tokens": 2, "tokens_discarded": 0,
                     "spans": [
                         {"ev": "queued", "t": 0.0},
                         {"ev": "admitted", "t": 0.001, "slot": 0},
                         {"ev": "prefill", "t": 0.005, "tokens": 8},
                         {"ev": "decode", "t": ttft, "emitted": 4},
                         {"ev": "finished", "t": ttft + 0.03},
                     ]}) + "\n")
                i += 1
        f.write(json.dumps(
            {"kind": "serving_trace", "t": float(i), "id": "chat-x",
             "tenant": "chat", "outcome": "preempted",
             "prompt_tokens": 8, "new_tokens": 2,
             "queue_wait_s": 0.001, "ttft_s": 0.01, "e2e_s": None,
             "prefix_hit_tokens": 0, "tokens_discarded": 2,
             "spans": [{"ev": "queued", "t": 0.0},
                       {"ev": "admitted", "t": 0.001, "slot": 1},
                       {"ev": "preempted", "t": 0.02,
                        "tokens_discarded": 2}]}) + "\n")
    return run_dir


def test_serving_report_cli_pinned(tmp_path, capsys):
    """`--serving-report` on the synthetic fixture: nearest-rank
    percentiles and SLO attainment are EXACT pins (chat n=5 ttfts
    10..50ms all inside the 250ms deadline; docs 100..500ms with
    only 100/200ms attaining), the preempted trace counts toward
    preemptions/retry cost but never toward attainment."""
    from distributed_training_tpu.telemetry.summarize import main

    run_dir = str(_synthetic_serving_run(tmp_path))
    assert main([run_dir, "--serving-report", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["traces"] == 11
    chat, docs = rep["tenants"]["chat"], rep["tenants"]["docs"]
    # Nearest-rank on n=5: p50 -> rank 3, p95/p99 -> rank 5.
    assert chat["ttft_s"]["p50"] == 0.03
    assert chat["ttft_s"]["p95"] == 0.05
    assert chat["ttft_s"]["p99"] == 0.05
    assert docs["ttft_s"]["p50"] == 0.3
    assert docs["ttft_s"]["p99"] == 0.5
    # conf deadlines: ttft 0.25, per-token 0.05 (decode tail 0.03
    # over 3 post-first tokens attains everywhere).
    assert chat["slo"] == {"attained": 1.0, "met": 5, "requests": 5,
                           "ttft_deadline_s": 0.25,
                           "per_token_deadline_s": 0.05}
    assert docs["slo"]["attained"] == pytest.approx(0.4)
    assert rep["overall"]["slo"]["attained"] == pytest.approx(0.7)
    assert rep["overall"]["slo"]["requests"] == 10
    assert chat["preemptions"] == 1
    assert chat["tokens_discarded"] == 2
    # Hit rate is over FINISHED prompts (20 hit / 80 prompt tokens);
    # the preempted trace's prompt never counts.
    assert rep["overall"]["prefix_hit_rate"] == pytest.approx(0.25)
    # CLI deadline override wins over the conf block.
    assert main([run_dir, "--serving-report", "--json",
                 "--slo-ttft-s", "0.15"]) == 0
    rep2 = json.loads(capsys.readouterr().out)
    assert rep2["tenants"]["docs"]["slo"]["attained"] == \
        pytest.approx(0.2)
    assert rep2["overall"]["slo"]["attained"] == \
        pytest.approx(0.6)
    # Human rendering names every tenant.
    assert main([run_dir, "--serving-report"]) == 0
    out = capsys.readouterr().out
    assert "chat" in out and "docs" in out
    # A run dir with no serving_trace records refuses politely.
    assert main([str(_synthetic_run_dir(tmp_path)),
                 "--serving-report"]) == 1


def test_serving_report_prints_the_run_ahead_share(tmp_path, capsys):
    """The step records' ``ran_ahead`` reaches ``step_phases`` and the
    report: the share of the launches that were dispatched while the
    launch before was un-retired, over the records that launched; a
    stream whose records carry none (a cadence that never runs ahead
    writes 0, an older stream nothing) leaves the line out."""
    from distributed_training_tpu.telemetry.serving_trace import (
        step_phases)
    from distributed_training_tpu.telemetry.summarize import main

    def step(op, **more):
        return {"kind": "serving", "op": op, "dur_s": 0.05,
                "phase_s": {"admit": 0.001, "pack": 0.002,
                            "launch": 0.003, "fetch": 0.03,
                            "emit": 0.01}, **more}

    steps = [step("prefill", ran_ahead=0), step("decode", ran_ahead=1),
             step("decode", ran_ahead=1), step("prefill", ran_ahead=1),
             step("idle")]
    ph = step_phases(steps)
    assert ph["steps"] == 4 and ph["ran_ahead_share"] == 0.75
    assert ph["mean_phase_s"]["other"] == pytest.approx(0.004)
    assert "ran_ahead_share" not in step_phases([step("decode")])
    run_dir = _synthetic_serving_run(tmp_path)
    with open(run_dir / "events.jsonl", "a") as f:
        for rec in steps:
            f.write(json.dumps(rec) + "\n")
    assert main([str(run_dir), "--serving-report"]) == 0
    out = capsys.readouterr().out
    assert "step split (4 launching steps" in out
    assert "run-ahead: 75.0% of the launches" in out


def test_summarizer_includes_serving_section(tmp_path):
    """The plain summarizer report grows a serving section when the
    run dir holds serving_trace records — same analyzer as the
    dedicated --serving-report path."""
    from distributed_training_tpu.telemetry.summarize import (
        render, summarize_run)

    s = summarize_run(str(_synthetic_serving_run(tmp_path)))
    assert s["serving"]["traces"] == 11
    assert "chat" in s["serving"]["tenants"]
    text = render(s)
    assert "serving" in text and "chat" in text
