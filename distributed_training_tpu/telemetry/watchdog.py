"""Hang watchdog + postmortem bundles.

A run that hangs with nothing on disk saying where cannot be
debugged. This module makes a hang produce evidence: a daemon thread
is armed before each step and disarmed after; if a step exceeds the timeout it writes a postmortem
directory — faulthandler stacks of ALL threads (works even when the
main thread is blocked inside an uninterruptible C call, e.g. a wedged
PJRT collective), per-device ``memory_stats()``, and the tail of the
telemetry event stream — before optionally aborting the process.

Dump ordering is deliberate: meta + stacks first (pure host-side,
cannot hang), device memory stats last (touches the backend, which is
exactly what may be wedged) — a hang mid-dump still leaves the stacks.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time

logger = logging.getLogger(__name__)


def _device_memory_stats() -> list[dict]:
    """Best-effort per-device ``memory_stats()``. Queries jax only if a
    backend is ALREADY initialized — merely-imported is not enough (this
    package's own __init__ imports jax), and ``jax.devices()`` in a
    jax-idle process would initialize (and claim) a backend from inside
    a postmortem, which is how a dump turns into a second hang."""
    jax = sys.modules.get("jax")
    if jax is None:
        return []
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not getattr(xb, "_backends", None):
        return []
    out = []
    for i, d in enumerate(jax.devices()):
        try:
            stats = d.memory_stats()
        except Exception as e:  # noqa: BLE001 — postmortem best-effort
            out.append({"id": i, "error": f"{type(e).__name__}: {e}"})
            continue
        out.append({"id": i, "kind": d.device_kind,
                    "stats": dict(stats) if stats else None})
    return out


def write_postmortem(base_dir: str, reason: str,
                     events_tail: list | None = None,
                     extra: dict | None = None) -> str:
    """Write one timestamped postmortem bundle; returns its path.

    Since the incident flight recorder landed, a postmortem IS an
    incident bundle (``kind="watchdog"``): this delegates to
    ``telemetry.incident.write_incident_bundle``, so postmortems and
    anomaly/preemption/give-up incidents share one on-disk format
    (meta.json with schema+kind, stacks.txt, events_tail.jsonl,
    memory_stats.json) and the offline ``--doctor`` reads either.
    Never raises — a postmortem writer that can crash its host process
    is worse than no postmortem."""
    from distributed_training_tpu.telemetry.incident import (
        write_incident_bundle)
    return write_incident_bundle(base_dir, reason=reason,
                                 kind="watchdog",
                                 events_tail=events_tail, extra=extra)


class HangWatchdog:
    """Per-step hang detector: ``arm()`` before dispatch, ``disarm()``
    after the step's host work completes. A step that stays armed past
    ``timeout_s`` gets a postmortem bundle under ``postmortem_dir``;
    ``abort=True`` then hard-exits (rc 42) — the mode for unattended
    runs where a hung process holding the accelerator is worse than a
    dead one. Re-arming after a firing resets the trigger, so a run
    that recovers can still document a later hang.
    """

    EXIT_CODE = 42

    def __init__(self, timeout_s: float, postmortem_dir: str,
                 telemetry=None, abort: bool = False,
                 poll_s: float | None = None):
        self.timeout_s = timeout_s
        self.postmortem_dir = postmortem_dir
        self.telemetry = telemetry
        self.abort = abort
        self.fired_path: str | None = None
        self._cond = threading.Condition()
        self._armed_at: float | None = None
        self._timeout_cur = timeout_s
        self._info: dict = {}
        self._context: dict = {}
        self._fired = False
        self._stopped = False
        self._poll = poll_s if poll_s is not None else max(
            0.05, min(1.0, timeout_s / 4))
        self._thread = threading.Thread(
            target=self._loop, name="hang-watchdog", daemon=True)
        self._thread.start()

    def arm(self, timeout_s: float | None = None, **info) -> None:
        """Start the countdown for one step. ``timeout_s`` overrides
        the default for this arm only (the trainer gives the first,
        compile-dominated step a larger allowance)."""
        with self._cond:
            self._armed_at = time.monotonic()
            self._timeout_cur = (timeout_s if timeout_s is not None
                                 else self.timeout_s)
            self._info = info
            self._fired = False
            self._cond.notify()

    def disarm(self) -> None:
        with self._cond:
            self._armed_at = None
            self._cond.notify()

    def set_context(self, ctx: dict) -> None:
        """Replace the persistent context merged into every future
        postmortem (on top of the per-arm info). The trainer feeds the
        straggler detector's latest verdicts through here, so a
        postmortem for a collective hang says "host 3 is 2.1x median
        on data_wait" instead of nothing. Pass {} to clear."""
        with self._cond:
            self._context = dict(ctx)

    def stop(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify()
        self._thread.join(timeout=5)

    def _loop(self) -> None:
        while True:
            with self._cond:
                if self._stopped:
                    return
                armed_at, fired = self._armed_at, self._fired
                timeout = self._timeout_cur
                info = {**self._info, **self._context}
                self._cond.wait(self._poll)
            if (armed_at is None or fired
                    or time.monotonic() - armed_at < timeout):
                continue
            with self._cond:
                # Re-check under the lock: the step may have disarmed
                # (or re-armed a NEWER step) while we were deciding.
                if self._armed_at != armed_at or self._fired:
                    continue
                self._fired = True
            self._fire(info, timeout)

    def _fire(self, info: dict, timeout_s: float) -> None:
        tail = self.telemetry.tail() if self.telemetry else None
        self.fired_path = write_postmortem(
            self.postmortem_dir,
            f"step exceeded watchdog timeout {timeout_s}s",
            events_tail=tail,
            extra={"watchdog_timeout_s": timeout_s, **info})
        if self.telemetry is not None:
            self.telemetry.event("watchdog_fired",
                                 postmortem=self.fired_path,
                                 timeout_s=timeout_s, **info)
        if self.abort:
            # Exit-status sentinel FIRST: the restart supervisor
            # classifies this death as watchdog_abort (vs crash) by
            # reading it — rc 42 alone also classifies, but the
            # sentinel carries the postmortem path into the incident
            # log. Best-effort: the abort must fire regardless.
            try:
                from distributed_training_tpu.resilience.supervisor \
                    import WATCHDOG_ABORT, write_exit_status
                write_exit_status(WATCHDOG_ABORT,
                                  postmortem=self.fired_path)
            except Exception as e:  # noqa: BLE001
                logger.debug("watchdog abort sentinel not written: "
                             "%s: %s", type(e).__name__, e)
            # The stacks are on disk; a process wedged in a C call
            # cannot run atexit handlers anyway.
            os._exit(self.EXIT_CODE)
