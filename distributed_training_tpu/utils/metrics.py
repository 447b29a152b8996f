"""Metrics: step timing, throughput, MFU accounting.

The reference logs only epoch boundaries and batch counts
(src/distributed_trainer.py:169-173); its README's performance guides are
an unfulfilled roadmap item (README.md:198). The BASELINE.json metric —
samples/sec/chip + MFU — requires real instrumentation, so this module is
a first-class subsystem (SURVEY.md §5.1/§5.5).
"""

from __future__ import annotations

import logging
import math
import time
from dataclasses import dataclass, field

logger = logging.getLogger(__name__)


def sanitize_for_json(value):
    """Map non-finite floats to null, recursively through dicts/lists
    — bare NaN/Infinity are not valid JSON and break strict consumers
    (jq, JSON.parse). Shared by the metrics and telemetry jsonl
    writers so the two streams stay parseable by the same tools."""
    if isinstance(value, float) and not math.isfinite(value):
        return None
    if isinstance(value, dict):
        return {k: sanitize_for_json(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [sanitize_for_json(v) for v in value]
    return value

# Peak dense bf16 FLOPs per chip. Sources: public TPU spec sheets.
TPU_PEAK_FLOPS: dict[str, float] = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
    "cpu": 1e11,  # nominal; read by CPU tests and the CPU-priced planner
}


def peak_flops_per_chip(device_kind: str) -> float:
    """Peak for ``device_kind`` (free-form, e.g. "TPU v5 lite";
    substring match). A kind that is not in the table is an error —
    an MFU against a made-up peak is not a measurement."""
    kind = device_kind.lower()
    for key, flops in TPU_PEAK_FLOPS.items():
        if key in kind:
            return flops
    raise ValueError(
        f"no peak FLOP/s recorded for device kind {device_kind!r}; add "
        f"it (with its source) to TPU_PEAK_FLOPS")


def compute_mfu(model_flops_per_sec_per_chip: float,
                device_kind: str) -> float:
    return model_flops_per_sec_per_chip / peak_flops_per_chip(device_kind)


@dataclass
class MetricsLogger:
    """Rolling per-step throughput/loss logging on the coordinator.

    ``jsonl_path`` (optional) appends every recorded entry as one JSON
    line — the durable metrics stream (loss curves, samples/sec/chip,
    MFU, val_loss) that BASELINE.json's measurement protocol calls for;
    the reference has only transient log lines (SURVEY.md §5.5).
    ``jsonl_fresh=True`` truncates the file at the first write (a
    from-scratch run in a reused run_dir must not interleave with the
    previous run's rows); resumed runs append, separated by a
    ``run_start`` marker line carrying the resume step.

    The first recorded row is flagged ``"warmup": true`` and carries
    no throughput numbers: the interval from construction to the
    first record is jit-compile dominated, so the steps/sec window
    opens at the first row and the second row is the first clean
    throughput measurement."""

    log_every: int = 10
    samples_per_step: int = 0
    flops_per_sample: float = 0.0
    num_devices: int = 1
    enabled: bool = True
    device_kind: str = "cpu"
    jsonl_path: str | None = None
    jsonl_fresh: bool = True
    start_step: int = 0
    # Optional callback invoked with every appended entry dict. The
    # entry is already fully host-side (the loss float above is the
    # one device sync, and it happens regardless) — the trainer wires
    # this to re-emit entries as ``train_metrics`` telemetry events so
    # the anomaly detector sees loss/throughput with ZERO new syncs.
    # Exceptions are swallowed: a consumer must not break logging.
    on_entry: object = None

    # None until the first record(): the throughput window starts at
    # the first recorded row, NOT at construction — the gap between
    # them is jit compile time, which used to fold into the first
    # row's steps_per_sec and silently understate throughput.
    _last_time: float | None = field(default=None)
    _last_step: int = 0
    history: list[dict] = field(default_factory=list)

    def __post_init__(self) -> None:
        # Resume: the throughput window must start at the resume step,
        # or the first row computes dsteps from 0 and reports a
        # ~(start_step/log_every)x inflated rate into the ledger.
        self._last_step = self.start_step
        if self.jsonl_path and self.enabled:
            # Eager open: a fresh run must truncate a reused run_dir's
            # previous stream even if it crashes before the first
            # recorded entry (stale curves misattribute silently).
            import json
            import os
            os.makedirs(os.path.dirname(self.jsonl_path) or ".",
                        exist_ok=True)
            mode = "w" if self.jsonl_fresh else "a"
            with open(self.jsonl_path, mode) as f:
                f.write(json.dumps(
                    {"run_start": True,
                     "step": self.start_step}) + "\n")

    def _append(self, entry: dict) -> None:
        self.history.append(entry)
        if self.jsonl_path:
            import json
            with open(self.jsonl_path, "a") as f:
                f.write(json.dumps(sanitize_for_json(entry),
                                   allow_nan=False) + "\n")
        if self.on_entry is not None:
            try:
                self.on_entry(sanitize_for_json(entry))
            except Exception as e:  # noqa: BLE001 — an observer must
                # not take down the metrics path (telemetry observer
                # discipline).
                logger.debug("metrics on_entry failed: %s: %s",
                             type(e).__name__, e)

    def record(self, step: int, metrics: dict, epoch: int = 0) -> None:
        if not self.enabled or self.log_every <= 0:
            return
        if step % self.log_every != 0:
            return
        now = time.perf_counter()
        if self._last_time is None:
            # First row: compile/warmup dominated — no throughput
            # numbers, flagged so consumers (and the summarizer's
            # trajectory stats) can exclude it. The clean window
            # starts here.
            entry = {"epoch": epoch, "step": step,
                     "loss": float(metrics.get("loss", float("nan"))),
                     "warmup": True}
            self._append(entry)
            logger.info("step %d | epoch %d | loss %.6f | (warmup "
                        "row: throughput window starts here)",
                        step, epoch, entry["loss"])
            self._last_time = now
            self._last_step = step
            return
        dsteps = max(step - self._last_step, 1)
        dt = max(now - self._last_time, 1e-9)
        steps_per_sec = dsteps / dt
        samples_per_sec = steps_per_sec * self.samples_per_step
        entry = {
            "epoch": epoch,
            "step": step,
            "loss": float(metrics.get("loss", float("nan"))),
            "steps_per_sec": steps_per_sec,
            "samples_per_sec_per_chip": samples_per_sec / self.num_devices,
        }
        if self.flops_per_sample:
            flops_per_chip = (samples_per_sec * self.flops_per_sample
                              / self.num_devices)
            entry["mfu"] = compute_mfu(flops_per_chip, self.device_kind)
        self._append(entry)
        logger.info(
            "step %d | epoch %d | loss %.6f | %.1f samples/s/chip%s",
            step, epoch, entry["loss"], entry["samples_per_sec_per_chip"],
            f" | mfu {entry['mfu']:.3f}" if "mfu" in entry else "")
        self._last_time = now
        self._last_step = step

    def record_scalar(self, step: int, name: str, value: float,
                      epoch: int = 0) -> None:
        """Unthrottled single-scalar entry (eval metrics, one-off
        events). Does not touch the throughput window."""
        if not self.enabled:
            return
        self._append({"epoch": epoch, "step": step,
                      name: float(value)})
        logger.info("step %d | epoch %d | %s %.6f", step, epoch, name,
                    float(value))
