"""Span/event core: the structured ``events.jsonl`` stream.

The metrics stream (utils/metrics.py) answers "how is the loss/MFU
curve doing"; this stream answers "where did the wall-clock go and
what was the process doing when it stopped". One JSON object per line:

- ``{"kind": "span", "name": "step", "t": <end unix>, "dur_s": ...,
   "depth": 0, "parent": null, ...attrs}`` — emitted when a span
  closes (start time = ``t - dur_s``). Spans nest per thread.
- ``{"kind": "<event name>", "t": ..., ...fields}`` — point events
  (hbm samples, goodput windows, watchdog firings, run_start).

Every ``span()`` also opens a ``jax.profiler.TraceAnnotation`` so the
same region names show up in XProf timelines — one instrumentation
surface for both the always-on jsonl stream and on-demand traces
(the TorchTitan stance: metrics/tracing as one first-class subsystem,
arxiv 2410.06511). ``phase()`` is the same annotation without a
record of its own, for regions that would be too many lines.

Ambient use (the ``logging`` model): entrypoints ``install()`` one
``Telemetry``; library code calls the module-level ``span()`` /
``event()``, which no-op (except the trace annotation) until something
is installed. A run that dies with zero artifacts is the motivating
counterexample — with this installed, the watchdog
(telemetry/watchdog.py) can dump the last N events of exactly this
stream into a postmortem.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import threading
import time

import jax

from distributed_training_tpu.utils.metrics import sanitize_for_json

logger = logging.getLogger(__name__)


class Telemetry:
    """Thread-safe event sink: jsonl file + bounded in-memory tail.

    ``events_jsonl=None`` or ``enabled=False`` keeps the full span API
    (including trace annotations) but writes nothing — the default for
    library code running outside an instrumented entrypoint.
    ``fresh=False`` appends (resumed runs), separated by a
    ``run_start`` marker, mirroring MetricsLogger's semantics.

    ``host_id`` (the jax process index on multi-host runs) stamps a
    ``host`` field onto EVERY record, so per-host streams stay
    attributable after the multi-host aggregator merges them into one
    timeline (telemetry/aggregate.py). None (single-process default)
    keeps the stream byte-identical to the single-host schema.
    """

    def __init__(self, events_jsonl: str | None = None,
                 enabled: bool = True, fresh: bool = True,
                 tail_events: int = 256, start_step: int = 0,
                 host_id: int | None = None):
        self.enabled = enabled and events_jsonl is not None
        self.events_jsonl = events_jsonl if self.enabled else None
        self.host_id = host_id
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._observers: list = []
        self._tail: collections.deque = collections.deque(
            maxlen=tail_events)
        self.ledger = None  # GoodputLedger, attached by the trainer
        self._fh = None
        if self.events_jsonl:
            os.makedirs(os.path.dirname(self.events_jsonl) or ".",
                        exist_ok=True)
            # One persistent line-buffered handle for the run: _emit
            # fires at least twice per training step (data_wait +
            # step spans), and an open/close pair per record under
            # the lock would stall the prefetch thread's spans behind
            # the main loop's I/O. Line buffering keeps every record
            # durable-on-write for tail readers and postmortems.
            self._fh = open(self.events_jsonl,
                            "w" if fresh else "a", buffering=1)
            start: dict = {"kind": "run_start", "t": time.time(),
                           "step": start_step}
            if self.host_id is not None:
                start["host"] = self.host_id
            self._fh.write(json.dumps(start) + "\n")

    # -- sinks ------------------------------------------------------------

    def attach_ledger(self, ledger) -> None:
        """Feed top-level span durations into a GoodputLedger."""
        self.ledger = ledger

    def add_observer(self, fn) -> None:
        """Register a live consumer of every emitted record (the
        metrics endpoint, telemetry/metrics_server.py). Called with
        the sanitized record AFTER it is written, outside the sink
        lock; an observer that raises is logged and does not disturb
        emission — the jsonl stream stays the source of truth."""
        with self._lock:
            self._observers.append(fn)

    def _emit(self, rec: dict) -> None:
        if not self.enabled:  # cheap fast path; authoritative below
            return
        if self.host_id is not None:
            rec = {**rec, "host": self.host_id}
        safe = sanitize_for_json(rec)
        line = json.dumps(safe, allow_nan=False)
        with self._lock:
            # Re-check under the lock: close() (cli shutdown) may race
            # an emitting prefetch/watchdog thread past the unlocked
            # enabled check above.
            if self._fh is None:
                return
            self._tail.append(safe)
            self._fh.write(line + "\n")
            observers = list(self._observers)
        for fn in observers:
            try:
                fn(safe)
            except Exception as e:  # noqa: BLE001 — a broken live
                # consumer must not take down the emission path.
                logger.debug("telemetry observer failed: %s: %s",
                             type(e).__name__, e)

    def close(self) -> None:
        """Stop recording and release the stream handle (idempotent).
        The in-memory tail stays readable for postmortems."""
        with self._lock:
            self.enabled = False
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def tail(self) -> list[dict]:
        """Most recent events, oldest first (postmortem payload)."""
        with self._lock:
            return list(self._tail)

    # -- API --------------------------------------------------------------

    def event(self, name: str, **fields) -> None:
        self._emit({"kind": name, "t": time.time(), **fields})

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Timed region: jsonl span record + XProf trace annotation.

        Nesting is tracked per thread; only DEPTH-0 spans feed the
        goodput ledger, so an instrumented sub-operation (e.g. an
        orbax wait inside a save) never double-counts its parent's
        bucket."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        parent = stack[-1] if stack else None
        stack.append(name)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
            depth = len(stack)
            if self.ledger is not None and depth == 0:
                self.ledger.add(name, dur,
                                steps=1 if name in ("step", "compile")
                                else 0)
            self._emit({"kind": "span", "name": name,
                        "t": time.time(), "dur_s": round(dur, 6),
                        "depth": depth, "parent": parent, **attrs})


class phase:
    """A span that writes no record of its own: the XProf trace
    annotation ``name``, and, where the caller passes ``into``, the
    region's seconds added to ``into[key]``. For regions too many for
    a jsonl line each (the five parts of a serving step, whose one
    ``serving`` record carries the dict as ``phase_s``) and for loops
    that write no record at all (the server's). Always compiled in: an
    annotation and two clock reads when no profiler listens."""

    __slots__ = ("_annotation", "_into", "_key", "_t0")

    def __init__(self, name: str, into: dict | None = None,
                 key: str | None = None):
        self._annotation = jax.profiler.TraceAnnotation(name)
        self._into = into
        self._key = key

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        if self._into is not None:
            self._into[self._key] = (self._into.get(self._key, 0.0)
                                     + time.perf_counter() - self._t0)
        return False


# A permanently-disabled instance: the ambient default, so library
# call sites never need a None check.
_NULL = Telemetry(enabled=False)
_current: Telemetry = _NULL


def install(telemetry: Telemetry) -> Telemetry:
    """Make ``telemetry`` the process-ambient sink (one per process,
    like the root logger). Returns it for chaining."""
    global _current
    _current = telemetry
    return telemetry


def uninstall() -> None:
    global _current
    _current = _NULL


def current() -> Telemetry:
    return _current


def span(name: str, **attrs):
    """Module-level span against the ambient Telemetry (always a valid
    trace annotation; a jsonl record only once ``install()``-ed)."""
    return _current.span(name, **attrs)


def event(name: str, **fields) -> None:
    _current.event(name, **fields)
