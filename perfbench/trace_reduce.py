"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction every PR uses, kept with the benchmark so that no PR that
claims a gain can change it. Read with nothing but JAX
(``jax.profiler.ProfileData``); checked on the small recorded trace in
``perfbench/tests/data/`` with exact expected numbers.

What a TPU v5e trace holds (looked at by hand, PR 24): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event for every
HLO operation the core ran, named by the whole HLO instruction,
enclosing operations (``while``, ``conditional``, ``call``) included, so
events nest. Asynchronous operations (copies, collectives) are there
as a short ``-start`` and a ``-done`` that lasts as long as the core
waits; their whole flight is on the line ``Async XLA Ops``, which is
not read, since the core computes meanwhile. The plane ``/host:CPU`` has
a line a thread, on which every ``jax.profiler.TraceAnnotation`` is an
event under its own name. Times are whole nanoseconds.

- busy: the union of the ``XLA Ops`` events of a device, clipped to
  the window. idle share = 1 - busy / window.
- window: the ``perfbench.window`` annotation where the driver wrote
  one, else the span of the device events.
- an operation's time is its SELF time: its duration less the events
  nested in it, so that an enclosing ``while`` does not swallow its body.
- exposed collective time: the self time of the collective operations
  on that line, which is the time the core spends in the synchronous
  ones and waits in the ``-done`` halves of the asynchronous ones. A
  collective that is hidden under compute shows only on ``Async XLA
  Ops`` and costs the core nothing, so it is not in this number.
- idle gaps are attributed to what the host was doing: every instant of
  the window belongs to the innermost ``perfbench.*`` annotation that
  covers it (else ``_no_annotation_``), and an annotation's idle
  seconds are its seconds less the device's busy seconds inside them.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
WINDOW = "perfbench.window"
ANNOTATION_PREFIX = "perfbench."
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


@functools.cache   # a few thousand instructions, a million events
def short_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%fusion.167 = bf16[...]{...} fusion(...), kind=...``. Keep the
    instruction's name and opcode, and a custom call's target (a Pallas
    kernel is ``custom-call:tpu_custom_call``): ``fusion.167 fusion``."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text[:80]
    rest = rest.lstrip()
    if rest.startswith("("):          # a tuple of result shapes
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return (f"{head.lstrip('%')} {rest.partition('(')[0]}"
            + (f":{target.group(1)}" if target else ""))


def load(path: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns), ...]},
    "annotations": [(name, start_ns, end_ns), ...]}``: the ``XLA Ops``
    events of every device plane and the ``perfbench.*`` events of the
    host planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    annotations: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.setdefault(plane.name, []).extend(
                    (short_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith(ANNOTATION_PREFIX))
    return {"devices": devices, "annotations": annotations}


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``(n, 2)`` [start, end) intervals into disjoint sorted
    ones."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def covered(merged: np.ndarray, lo, hi) -> np.ndarray:
    """How much of ``merged`` (disjoint, sorted) lies inside each
    [lo, hi)."""
    lo, hi = np.asarray(lo, float), np.asarray(hi, float)
    if len(merged) == 0:
        return np.zeros(lo.shape)
    starts, ends = merged[:, 0], merged[:, 1]
    cum = np.r_[0.0, np.cumsum(ends - starts)]

    def upto(x):
        i = np.searchsorted(starts, x, side="right")
        beyond = np.maximum(ends[np.maximum(i - 1, 0)] - x, 0.0)
        return cum[i] - np.where(i > 0, beyond, 0.0)
    return upto(hi) - upto(lo)


def self_intervals(events: list) -> list:
    """Split nested events into self-time pieces: ``[(name, start,
    end), ...]`` that do not overlap, each instant given to the
    innermost event covering it. Events that overlap without nesting
    (annotations of two threads) are not counted twice: the one that
    started later takes the overlap."""
    out = []
    stack: list = []  # (name, end, cursor)
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            n, e, cur = stack.pop()
            if e > cur:
                out.append((n, cur, e))
            if stack:
                stack[-1][2] = max(stack[-1][2], e)
        if stack:
            n, e, cur = stack[-1]
            if start > cur:
                out.append((n, cur, start))
            stack[-1][2] = start
        stack.append([name, end, start])
    while stack:
        n, e, cur = stack.pop()
        if e > cur:
            out.append((n, cur, e))
        if stack:
            stack[-1][2] = max(stack[-1][2], e)
    return out


def _arr(pieces: list) -> np.ndarray:
    return np.array([(s, e) for _n, s, e in pieces], float).reshape(-1, 2)


def reduce(trace: dict, top: int = 10) -> dict:
    """The summary the per-layer readers and ``breakdown`` use. Times in
    seconds. ``trace`` is what ``load`` returns."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    ann = trace["annotations"]
    windows = [(s, e) for n, s, e in ann if n == WINDOW]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for evs in devices.values() for _n, s, _e in evs)
        hi = max(e for evs in devices.values() for _n, _s, e in evs)
    window_ns = float(hi - lo)
    if window_ns <= 0:
        raise ValueError("empty trace window")

    busy_ns = []
    merged0 = pieces0 = None
    for plane in sorted(devices):
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[plane]
               if e > lo and s < hi]
        merged = union(np.array([(s, e) for _n, s, e in evs],
                                float).reshape(-1, 2))
        busy_ns.append(float((merged[:, 1] - merged[:, 0]).sum()))
        if merged0 is None:
            merged0, pieces0 = merged, self_intervals(evs)
    busy = sum(busy_ns) / len(busy_ns)

    by_op: dict = {}
    for n, s, e in pieces0:
        by_op[n] = by_op.get(n, 0) + (e - s)
    coll_ns = sum(v for n, v in by_op.items() if COLLECTIVE.search(n))

    host = [(n, max(s, lo), min(e, hi)) for n, s, e in ann
            if n != WINDOW and e > lo and s < hi]
    gaps: dict = {}
    segs = self_intervals(host)
    if segs:
        iv = _arr(segs)
        idle = (iv[:, 1] - iv[:, 0]) - covered(merged0, iv[:, 0], iv[:, 1])
        for (n, _s, _e), ns in zip(segs, idle):
            gaps[n] = gaps.get(n, 0.0) + float(ns)
    idle0 = window_ns - busy_ns[0]
    rest = idle0 - sum(gaps.values())
    if rest > 0:
        gaps["_no_annotation_"] = rest

    def ranked(d):
        return [[n, v / 1e9] for n, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top] if v > 0]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy / 1e9,
        "busy_s_by_device": [b / 1e9 for b in busy_ns],
        "idle_share": 1.0 - busy / window_ns,
        "exposed_collective_s": coll_ns / 1e9,
        "op_self_s": {n: v / 1e9 for n, v in by_op.items()},
        "device_ops": ranked(by_op),
        "idle_gaps": ranked(gaps),
    }
