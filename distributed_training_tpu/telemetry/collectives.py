"""Static collective-traffic accounting for a compiled SPMD step.

The sharding design never spells out its communication — XLA's SPMD
partitioner derives psum/all-gather/reduce-scatter/all-to-all from the
sharding annotations on the jitted step. This module walks the
compiled step's optimized HLO text and accounts every collective (op
kind, element type, shape, estimated bytes moved per step) and, when
given the mesh, attributes each one to the mesh axis (or axis combo)
whose replica groups it communicates over — so the summarizer can put
a comms roofline next to MFU and a layout regression shows up as a
diffable number instead of silent extra traffic.

This is the library form of ``benchmarks/audit_collectives.py`` (which
now imports its parser from here); the CLI stays in benchmarks, the
schema here is stable (``schema`` version field) because trainer-emitted
``collectives`` events and the multi-host aggregator both consume it.

Why HLO text and not the jaxpr: under GSPMD there are no collective
primitives in the jaxpr at all — the partitioner inserts them during
compilation, so the compiled artifact is the only truthful source.

Byte accounting: each row's ``bytes`` is the collective's result-tuple
payload on one participant (the '-done' form's output for async HLO) —
an estimate of traffic per step per device, not a link-level model.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import re
import sys
import tempfile
from collections import defaultdict

import numpy as np

# Bump when the report dict's keys change meaning — consumers
# (summarize.py, aggregate.py) check this before rendering.
SCHEMA = 1

# The stable consumer surface of a trainer-emitted ``collectives``
# event (everything except the per-row detail). Single-host and
# multi-host summaries both filter through this, so a SCHEMA bump
# cannot leave the two reports disagreeing about which keys exist.
# ``sharding_plan`` (additive, absent on unplanned runs) is the
# resolved auto-parallelism plan's provenance — name/fingerprint/
# remat/base_strategy from parallel/planner.py.
SUMMARY_KEYS = ("schema", "total_collectives", "bytes_per_step",
                "by_kind", "by_axis", "mesh", "spmd_reshard_warnings",
                "sharding_plan")


def summary_of_event(rec: dict) -> dict:
    """The SUMMARY_KEYS subset of a ``collectives`` event/report."""
    return {k: rec[k] for k in SUMMARY_KEYS if k in rec}


def render_lines(coll: dict) -> list[str]:
    """Human-readable lines for a collectives summary — one headline
    (total MB/step by kind, or the explicit none case) plus one line
    per mesh axis. Shared by the single-run summarizer and the
    multi-host report so the same event never renders two ways."""
    parts = ", ".join(
        f"{k} x{v['count']} {v['bytes'] / 1e6:.2f}MB"
        for k, v in sorted(coll.get("by_kind", {}).items(),
                           key=lambda kv: -kv[1]["bytes"]))
    lines = [
        f"collectives: {coll['bytes_per_step'] / 1e6:.2f} MB/step"
        f" ({parts})" if parts else
        "collectives: none (single-device or fully replicated)"]
    for axis, v in sorted(coll.get("by_axis", {}).items(),
                          key=lambda kv: -kv[1]["bytes"]):
        lines.append(f"  axis {axis:10s} x{v['count']:3d}  "
                     f"{v['bytes'] / 1e6:9.3f} MB")
    if coll.get("spmd_reshard_warnings"):
        lines.append(
            f"  SPMD reshard warnings: {coll['spmd_reshard_warnings']} "
            "(involuntary full rematerialization — see "
            "docs/static-analysis.md)")
    sp = coll.get("sharding_plan")
    if sp:
        lines.append(
            f"  sharding plan: {sp.get('name')}@"
            f"{sp.get('fingerprint')} ({sp.get('base_strategy')}, "
            f"remat={sp.get('remat')})")
    return lines

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "s64": 8, "u64": 8}

# One optimized-HLO instruction: "%name = TYPE op(...)" where TYPE is
# either a single "dt[shape]{layout}" or a tuple "(dt[s], dt[s], ...)"
# — tuple results are how XLA emits FUSED collectives (e.g. one
# all-reduce syncing every gradient leaf), so a single-type parser
# silently undercounts exactly the most important instruction.
# Async HLO (the TPU compiler's usual form) splits a collective into a
# '-start'/'-done' pair; counting both would double the count and
# ~triple the bytes (the start's result tuple aliases operand AND
# result buffers). Count sync base forms and async '-done' lines —
# the done's result type is the collective's true output — and let
# '-start' lines fall through unmatched (the base-form alternative
# cannot match them: the char after the op name is '-', not '(').
_OP_LINE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-done)?\(")
_TYPE = re.compile(r"(\w+)\[([\d,]*)\]")

# A TPU-pipeline fused reduce-scatter: the executed op is one RS
# kernel, but its HLO form is a kCustom fusion whose CALLED computation
# holds an all-reduce + dynamic-slice pair. Count the fusion (output
# shape = the true bytes moved per receiver) and skip the called
# computation's body — otherwise the inner all-reduce is double-counted
# at FULL pre-scatter bytes, which is exactly how the r4 audit misread
# the TPU grad sync as "all-reduce at 2x optimal traffic".
_FUSED_RS_LINE = re.compile(
    r"=\s+(.*?)\s+fusion\([^\n]*kind=kCustom,\s*"
    r"calls=(%all-reduce-scatter[\w.\-]*)")
_RS_COMPUTATION = re.compile(r"^(%all-reduce-scatter[\w.\-]*)\s", re.M)

# replica_groups in either explicit form {{0,1},{2,3}} or the iota
# form [G,S]<=[d0,d1,...]T(p...) (iota over [d...], transpose p,
# reshape to G groups of S).
_GROUPS_EXPLICIT = re.compile(r"replica_groups=\{(\{[\d, \{\}]*\})\}")
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


# ---------------------------------------------------------------------------
# SPMD-partitioner diagnostics. XLA's spmd_partitioner.cc reports the
# "Involuntary full rematerialization" cliff (it must fully replicate a
# tensor to move between two shardings — silent extra traffic that
# scales with the tensor, exactly the pod-scale perf cliff ROADMAP item
# 1 gates on) as a C++ log line on the process's stderr FD. It never
# surfaces through any Python API, so the only faithful way to observe
# it is to capture fd 2 around the ``.compile()`` call. Wording differs
# across XLA vintages ("cannot go from sharding X to Y efficiently" vs
# "was not able to go from sharding X to Y without doing a full
# rematerialization"); the regexes below accept both.
# ---------------------------------------------------------------------------

RESHARD_MARKER = "Involuntary full rematerialization"
_RESHARD_SHARDINGS = re.compile(
    r"from sharding \{(.*?)\} to \{(.*?)\}")
_RESHARD_OP = re.compile(
    r"for HLO operation:?\s+%([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]")


@contextlib.contextmanager
def capture_stderr_fd():
    """Capture everything written to the stderr FILE DESCRIPTOR (not
    just ``sys.stderr``) for the duration of the block — C++ XLA logs
    bypass the Python-level stream. Yields an object whose ``.text``
    holds the captured bytes after exit. Anything captured is swallowed
    from the real stderr (including unrelated concurrent writers, e.g.
    logging from other threads), so keep the window tight: one compile.
    """
    class _Cap:
        text = ""

    cap = _Cap()
    sys.stderr.flush()
    saved = os.dup(2)
    tmp = tempfile.TemporaryFile(mode="w+b")
    try:
        os.dup2(tmp.fileno(), 2)
        yield cap
    finally:
        sys.stderr.flush()
        os.dup2(saved, 2)
        os.close(saved)
        tmp.seek(0)
        cap.text = tmp.read().decode("utf-8", "replace")
        tmp.close()


def parse_reshard_warnings(stderr_text: str) -> list[dict]:
    """Structured rows for every involuntary-reshard warning in a
    captured compile stderr: op name/dtype/shape plus the source and
    destination shardings the partitioner could not bridge. Fields
    the vintage's wording omits come back empty rather than missing."""
    rows: list[dict] = []
    for line in stderr_text.splitlines():
        if RESHARD_MARKER not in line:
            continue
        row = {"op": "", "dtype": "", "shape": "",
               "from_sharding": "", "to_sharding": "",
               "raw": line.strip()[:2000]}
        m = _RESHARD_SHARDINGS.search(line)
        if m:
            row["from_sharding"], row["to_sharding"] = m.groups()
        m = _RESHARD_OP.search(line)
        if m:
            # Strip SSA numeric suffixes (%gather.123 → gather) so the
            # fingerprint survives unrelated HLO renumbering.
            row["op"] = re.sub(r"[.\d]+$", "", m.group(1))
            row["dtype"], row["shape"] = m.group(2), m.group(3)
        rows.append(row)
    return rows


def _bytes_of(dtype: str, shape: str) -> int:
    n = 1
    for d in filter(None, shape.split(",")):
        n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _strip_fused_rs_bodies(text: str, names: set[str]) -> str:
    """Remove the bodies of the NAMED %all-reduce-scatter called
    computations so their inner all-reduce/dynamic-slice never reach
    the parser. Only computations whose calling fusion was actually
    COUNTED are stripped — a name-based strip with an uncounted caller
    would make the grad-sync collective vanish from the report
    entirely (and the zero-collective contract tests pass vacuously)."""
    out = []
    for block in re.split(r"\n(?=%|ENTRY)", text):
        m = _RS_COMPUTATION.match(block)
        if m and m.group(1) in names:
            continue
        out.append(block)
    return "\n".join(out)


def parse_replica_groups(text: str) -> list[tuple[int, ...]] | None:
    """Parse an instruction's ``replica_groups=`` annotation (either
    form) into a list of participant-id tuples; None when absent."""
    m = _GROUPS_EXPLICIT.search(text)
    if m:
        groups = []
        for part in re.findall(r"\{([\d, ]*)\}", m.group(1)):
            ids = [int(x) for x in part.replace(" ", "").split(",")
                   if x]
            if ids:
                groups.append(tuple(ids))
        return groups or None
    m = _GROUPS_IOTA.search(text)
    if m:
        out_dims = [int(x) for x in m.group(1).split(",")]
        in_dims = [int(x) for x in m.group(2).split(",")]
        arr = np.arange(int(np.prod(in_dims))).reshape(in_dims)
        if m.group(3):
            arr = arr.transpose([int(x) for x in m.group(3).split(",")])
        arr = arr.reshape(out_dims[0], -1)
        return [tuple(int(x) for x in row) for row in arr]
    return None


def mesh_axis_groupings(mesh) -> list[tuple[str, frozenset]]:
    """Every way the partitioner can group this mesh's devices along
    axis combinations: ``[(label, {frozenset(ids), ...}), ...]`` for
    each non-empty combination of non-trivial axes.

    Participant ids in HLO replica groups are device numbers in the
    program's device assignment; depending on pipeline and mode they
    can be either positions in the mesh's flattened device order or
    PjRT device ids — on the standard identity layouts the two agree,
    and where they differ we emit BOTH groupings so either matches.
    """
    shape = mesh.devices.shape
    names = list(mesh.axis_names)
    axes = [i for i, s in enumerate(shape) if s > 1]
    by_pos = np.arange(mesh.devices.size).reshape(shape)
    by_id = np.vectorize(lambda d: d.id)(mesh.devices).reshape(shape)
    out: list[tuple[str, frozenset]] = []
    for r in range(1, len(axes) + 1):
        for combo in itertools.combinations(axes, r):
            label = "+".join(names[i] for i in combo)
            for ids in (by_pos, by_id):
                moved = np.moveaxis(
                    ids, combo, range(ids.ndim - len(combo), ids.ndim))
                group_sz = int(np.prod([shape[i] for i in combo]))
                grouped = moved.reshape(-1, group_sz)
                key = frozenset(frozenset(int(x) for x in row)
                                for row in grouped)
                out.append((label, key))
    return out


def _axes_label(groups: list[tuple[int, ...]] | None,
                groupings: list[tuple[str, frozenset]]) -> str:
    if groups is None:
        return "unknown"
    if all(len(g) <= 1 for g in groups):
        return "self"  # degenerate: no cross-device traffic
    key = frozenset(frozenset(g) for g in groups)
    for label, candidate in groupings:
        if key == candidate:
            return label
    return "unknown"


def audit_hlo_text(text: str, mesh=None) -> dict:
    """Parse optimized HLO text → per-collective counts and bytes.

    With ``mesh``, each row additionally carries ``axes`` (the mesh
    axis combination its replica groups communicate over) and the
    report gains a ``by_axis`` rollup. The stable consumer surface:
    ``schema``, ``total_collectives``, ``bytes_per_step``, ``by_kind``
    (kind → {count, bytes}), ``by_axis`` (mesh only), ``rows``, and
    ``pallas_calls`` — how many Mosaic (Pallas TPU) kernels the
    program launches, so a run's own event stream says whether it
    measured the flash kernels or the naive attention path.
    """
    groupings = mesh_axis_groupings(mesh) if mesh is not None else None
    pallas_calls = text.count('custom_call_target="tpu_custom_call"')
    rows = []
    counted_rs: set[str] = set()
    # Bodies of called computations, for fused-RS axis attribution:
    # the replica_groups live on the INNER all-reduce, which the strip
    # below removes before the main scan.
    blocks = {m.group(1): b
              for b in re.split(r"\n(?=%|ENTRY)", text)
              for m in [_RS_COMPUTATION.match(b)] if m}
    for m in _FUSED_RS_LINE.finditer(text):
        parts = _TYPE.findall(m.group(1))
        if not parts:
            continue
        total = sum(_bytes_of(dt, sh) for dt, sh in parts)
        big_dt, big_sh = max(parts, key=lambda p: _bytes_of(p[0], p[1]))
        row = {"kind": "reduce-scatter", "dtype": big_dt,
               "shape": big_sh or "scalar",
               "tuple_arity": len(parts), "bytes": total,
               "fused": True}
        if groupings is not None:
            row["axes"] = _axes_label(
                parse_replica_groups(blocks.get(m.group(2), "")),
                groupings)
        rows.append(row)
        counted_rs.add(m.group(2))
    text = _strip_fused_rs_bodies(text, counted_rs)
    for line in text.splitlines():
        m = _OP_LINE.search(line)
        if not m:
            continue
        types, kind = m.group(1), m.group(2)
        parts = _TYPE.findall(types)
        if not parts:
            continue
        total = sum(_bytes_of(dt, sh) for dt, sh in parts)
        big_dt, big_sh = max(
            parts, key=lambda p: _bytes_of(p[0], p[1]))
        row = {"kind": kind, "dtype": big_dt,
               "shape": big_sh or "scalar",
               "tuple_arity": len(parts),
               "bytes": total}
        if groupings is not None:
            row["axes"] = _axes_label(parse_replica_groups(line),
                                      groupings)
        rows.append(row)
    by_kind: dict = defaultdict(lambda: {"count": 0, "bytes": 0})
    by_axis: dict = defaultdict(lambda: {"count": 0, "bytes": 0})
    for r in rows:
        by_kind[r["kind"]]["count"] += 1
        by_kind[r["kind"]]["bytes"] += r["bytes"]
        if "axes" in r:
            by_axis[r["axes"]]["count"] += 1
            by_axis[r["axes"]]["bytes"] += r["bytes"]
    rep = {
        "schema": SCHEMA,
        "total_collectives": len(rows),
        "bytes_per_step": sum(r["bytes"] for r in rows),
        "by_kind": dict(by_kind),
        "pallas_calls": pallas_calls,
        "largest": sorted(rows, key=lambda r: -r["bytes"])[:10],
        # Full row list: contract tests must scan EVERY collective —
        # a pathological row ranked 11th would hide from "largest".
        "rows": rows,
    }
    if groupings is not None:
        rep["by_axis"] = dict(by_axis)
    return rep
