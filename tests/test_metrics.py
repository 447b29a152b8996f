"""utils/metrics.py: MFU arithmetic, the durable jsonl stream's
truncate-vs-append resume semantics, NaN sanitization, and the
first-window warmup flag (compile time must not fold into the first
row's throughput)."""

import json
import math

import pytest

from distributed_training_tpu.utils.metrics import (MetricsLogger,
                                                    compute_mfu,
                                                    peak_flops_per_chip)


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_peak_flops_lookup_substring_matches():
    # device_kind strings are free-form ("TPU v5 lite"); the lookup is
    # substring-based and an unknown kind is an error, not a default.
    assert peak_flops_per_chip("TPU v4") == 275e12
    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    assert peak_flops_per_chip("TPU v5p") == 459e12
    with pytest.raises(ValueError, match="weird accelerator"):
        peak_flops_per_chip("weird accelerator")


def test_compute_mfu_hand_computed():
    # 27.5 TF/s/chip achieved on a 275 TF/s v4 chip = 0.1 MFU, exactly.
    assert compute_mfu(27.5e12, "TPU v4") == pytest.approx(0.1)
    assert compute_mfu(275e12, "TPU v4") == pytest.approx(1.0)


def test_mfu_entry_arithmetic_hand_computed(monkeypatch):
    """Pin the recorded-entry MFU against by-hand arithmetic: 10 steps
    in exactly 2s of stubbed clock, 4 samples/step, 1e9 FLOPs/sample,
    2 devices, v4 peak 275e12 -> mfu = (10 samples/s/chip * 1e9) /
    275e12. The clock is frozen: with a real perf_counter the ms-scale
    work between the two record() calls (logging I/O, a loaded test
    host) leaks into the 2s window and the tight tolerance flakes."""
    from distributed_training_tpu.utils import metrics as metrics_mod

    frozen = metrics_mod.time.perf_counter()
    monkeypatch.setattr(metrics_mod.time, "perf_counter",
                        lambda: frozen)
    m = MetricsLogger(log_every=10, samples_per_step=4,
                      flops_per_sample=1e9, num_devices=2,
                      device_kind="TPU v4")
    m.record(10, {"loss": 1.0})          # warmup row opens the window
    m._last_time -= 2.0                  # rewind the window start 2s
    m.record(20, {"loss": 1.0})
    row = m.history[-1]
    assert row["steps_per_sec"] == pytest.approx(5.0, rel=1e-3)
    assert row["samples_per_sec_per_chip"] == pytest.approx(
        10.0, rel=1e-3)
    assert row["mfu"] == pytest.approx(10.0 * 1e9 / 275e12, rel=1e-3)


def test_first_row_is_warmup_flagged(tmp_path):
    """The construction->first-record gap is compile-dominated: the
    first row must carry no throughput numbers (it used to understate
    steps_per_sec silently)."""
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(log_every=1, samples_per_step=4,
                      jsonl_path=path)
    m.record(1, {"loss": 3.0})
    m.record(2, {"loss": 2.0})
    rows = _read_jsonl(path)
    assert rows[0] == {"run_start": True, "step": 0}
    assert rows[1]["warmup"] is True
    assert "steps_per_sec" not in rows[1]
    assert rows[2]["steps_per_sec"] > 0
    assert "warmup" not in rows[2]


def test_jsonl_fresh_truncates_previous_run(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"stale": True}) + "\n")
    MetricsLogger(log_every=1, jsonl_path=path, jsonl_fresh=True)
    rows = _read_jsonl(path)
    # Truncation happens eagerly at construction (a crash before the
    # first record must not leave the stale stream in place).
    assert rows == [{"run_start": True, "step": 0}]


def test_jsonl_resume_appends_with_marker(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m1 = MetricsLogger(log_every=1, jsonl_path=path)
    m1.record(1, {"loss": 1.0})
    m2 = MetricsLogger(log_every=1, jsonl_path=path,
                       jsonl_fresh=False, start_step=1)
    m2.record(2, {"loss": 0.5})
    rows = _read_jsonl(path)
    # Both runs' rows present, separated by the resume marker.
    assert rows[0] == {"run_start": True, "step": 0}
    assert rows[1]["step"] == 1
    assert rows[2] == {"run_start": True, "step": 1}
    assert rows[3]["step"] == 2


def test_nan_loss_sanitized_to_null(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(log_every=1, jsonl_path=path)
    m.record(1, {"loss": float("nan")})
    m.record(2, {"loss": float("inf")})
    # Strict parsers (json.loads with no extensions, jq) must accept
    # every line; non-finite floats arrive as null.
    rows = _read_jsonl(path)
    assert rows[1]["loss"] is None
    assert rows[2]["loss"] is None
    # The in-memory history keeps the real float for local consumers.
    assert math.isnan(m.history[0]["loss"])


def test_record_scalar_unthrottled(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(log_every=100, jsonl_path=path)
    m.record(3, {"loss": 1.0})  # off-cadence: dropped
    m.record_scalar(3, "val_loss", 0.25)
    rows = _read_jsonl(path)
    assert len(rows) == 2
    assert rows[1] == {"epoch": 0, "step": 3, "val_loss": 0.25}


def test_disabled_logger_writes_nothing(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(log_every=1, jsonl_path=path, enabled=False)
    m.record(1, {"loss": 1.0})
    m.record_scalar(1, "val_loss", 1.0)
    assert not (tmp_path / "m.jsonl").exists()
    assert m.history == []
