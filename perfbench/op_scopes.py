"""Device time by the program's own ``dtt.*`` scopes.

An ``XLA Ops`` event of a v5e trace is a bare HLO instruction
(``%fusion.591 = ...``): it carries no ``op_name``, and instruction
names are the compiler's, unique only inside one HLO module. The
program writes the other half itself: at warm-up, where a sink records,
``Engine.warmup`` emits one ``program_scopes`` record a compiled
program (``telemetry/op_scopes.py::scope_map``): its module's name and,
scope by scope, the instructions that lie in it. In a traced run the
driver installs its sink before it builds the engine, so the records
are in ``perfbench_out/events.jsonl`` (``obs`` does not carry them).

This joins the two, with ``trace_reduce``'s interval arithmetic: every
``XLA Ops`` event of device 0 inside the traced window is given to the
``XLA Modules`` event that contains it, self time is summed by (module,
instruction) and looked up in that module's record. An instruction of a
module with no record, or one its record does not list, is
``UNSCOPED``. The seconds by scope, ``UNSCOPED`` among them, add up to
device 0's busy seconds of the window.

No record (the parent of the PR that added them; an untraced run, which
has no sink) or no ``XLA Modules`` line (the CPU rehearsal's canned
trace): the table is ``None`` and the readers report nothing.
"""

from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

from perfbench import common, program_spans, trace_reduce

MODULES_LINE = "XLA Modules"
# ``telemetry/op_scopes.py::UNSCOPED``, spelt here: this file also runs
# over a parent commit that has no such module.
UNSCOPED = "_unscoped_"
MIXED = "_mixed_"
NO_MODULE = "_no_module_"
TOP = 40    # operations the log names under their scope


def this_runs_maps() -> dict | None:
    """``{module: {"scope": {instruction: scope}, "mixed": {names}}}``
    from the ``program_scopes`` records of ``perfbench_out/
    events.jsonl``, if this process wrote the file; None where there is
    no such file or it holds no such record."""
    path = os.path.join(common.OUT, "events.jsonl")
    try:
        if os.path.getmtime(path) < program_spans._process_started() - 1.0:
            return None
        with open(path) as f:
            lines = [ln for ln in f if '"program_scopes"' in ln]
    except OSError:
        return None
    maps: dict = {}
    for ln in lines:
        rec = json.loads(ln)
        if rec.get("kind") != "program_scopes":
            continue
        maps[rec["module"]] = {
            "scope": {name: scope for scope, names in rec["scopes"].items()
                      for name in names},
            "mixed": set(rec["mixed"])}
    return maps or None


def _device0(path: str) -> tuple:
    """``(ops [(instruction, start, end)], modules [(module, start,
    end)], window or None)`` of the first device plane of ``path``."""
    from jax.profiler import ProfileData

    planes: dict = {}
    window = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            ops, modules = planes.setdefault(plane.name, ([], []))
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops.extend(
                        (trace_reduce.short_name(ev.name).partition(" ")[0],
                         ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend(
                        (ev.name.partition("(")[0], ev.start_ns,
                         ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW:
                        window = (ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                        break
    # ``/device:CUSTOM:...`` planes carry no operations.
    planes = {name: p for name, p in planes.items() if p[0]}
    if not planes:
        raise ValueError("the trace holds no device plane with an "
                         f"{trace_reduce.OPS_LINE!r} line")
    ops, modules = planes[min(planes)]
    return ops, modules, window


def by_scope(path: str, maps: dict) -> dict | None:
    """The join for the trace at ``path`` and ``maps``
    (``this_runs_maps``'s shape). Seconds of device 0 inside the window:
    ``scope_s`` by scope (``UNSCOPED`` among them; their sum is
    ``busy_s``; a scope that a record lists and the window never ran
    reads 0), ``module_s`` by module, ``mixed_s`` (the part of
    ``scope_s`` spent in fusions whose insides span scopes) and
    ``unlisted_s`` (the part of ``UNSCOPED`` whose module has a record
    that does not list the instruction); ``ops``, the ``TOP`` largest
    ``(seconds, scope, module, instruction, mixed)``; ``launches`` and
    ``ms_per_launch`` by module, over the module events that lie whole
    inside the window. None where the trace has no ``XLA Modules``
    line."""
    ops, modules, window = _device0(path)
    if not modules or not ops:
        return None
    lo, hi = window or (min(s for _n, s, _e in ops),
                        max(e for _n, _s, e in ops))
    modules.sort(key=lambda m: m[1])
    starts = np.array([m[1] for m in modules], float)
    ends = np.array([m[2] for m in modules], float)
    inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
    # The module event that contains an operation's start.
    at = np.searchsorted(starts, [s for _n, s, _e in inside],
                         side="right") - 1
    events = []
    for (name, s, e), i in zip(inside, at):
        module = (modules[i][0] if i >= 0 and s < ends[i]
                  else NO_MODULE)
        events.append(((module, name), max(s, lo), min(e, hi)))
    seconds: dict = {}
    for key, s, e in trace_reduce.self_intervals(events):
        seconds[key] = seconds.get(key, 0.0) + (e - s) / 1e9
    scope_s: dict = {}
    module_s: dict = {}
    mixed_s = unlisted_s = 0.0
    ops = []
    for (module, name), sec in seconds.items():
        module_s[module] = module_s.get(module, 0.0) + sec
        record = maps.get(module)
        scope = record["scope"].get(name) if record else None
        if record and scope is None:
            unlisted_s += sec
        mixed = bool(record) and name in record["mixed"]
        mixed_s += sec * mixed
        scope = scope or UNSCOPED
        scope_s[scope] = scope_s.get(scope, 0.0) + sec
        ops.append((sec, scope, module, name, mixed))
    whole = [(n, e - s) for n, s, e in modules if s >= lo and e <= hi]
    launches: dict = {}
    total: dict = {}
    for n, ns in whole:
        launches[n] = launches.get(n, 0) + 1
        total[n] = total.get(n, 0.0) + ns
    # A scope some program has and the window never ran reads 0.
    for record in maps.values():
        for scope in record["scope"].values():
            scope_s.setdefault(scope, 0.0)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(scope_s.values()),
            "scope_s": scope_s, "module_s": module_s,
            "mixed_s": mixed_s, "unlisted_s": unlisted_s,
            "ops": sorted(ops, reverse=True)[:TOP],
            "launches": launches,
            "ms_per_launch": {n: total[n] / launches[n] / 1e6
                              for n in launches}}


@functools.cache
def _logged(path: str) -> dict | None:
    maps = this_runs_maps()
    if maps is None:
        common.log("op_scopes: no program_scopes record of this run")
        return None
    t0 = time.perf_counter()
    table = by_scope(path, maps)
    if table is None:
        common.log(f"op_scopes: no {MODULES_LINE!r} line in "
                   f"{os.path.basename(path)}")
        return None
    common.log(f"device 0 by dtt.* scope over {table['window_s']:.3f}s "
               f"(busy {table['busy_s']:.5f}s; joined in "
               f"{time.perf_counter() - t0:.1f}s with "
               f"{len(maps)} program_scopes records):")
    for name, seconds in sorted(table["scope_s"].items(),
                                key=lambda kv: -kv[1]):
        common.log(f"  scope {seconds:9.5f}s "
                   f"{100 * seconds / table['window_s']:6.2f}%  {name}")
    common.log(f"  scope {table['mixed_s']:9.5f}s "
               f"{100 * table['mixed_s'] / table['window_s']:6.2f}%  "
               f"{MIXED} (inside the scopes above); "
               f"{table['unlisted_s']:.5f}s of {UNSCOPED} are "
               f"instructions a record does not list")
    for seconds, scope, module, name, mixed in table["ops"]:
        common.log(f"  op {seconds:9.5f}s  {scope:16s} "
                   f"{module.removeprefix('jit_serving_')}:{name}"
                   + (" (mixed)" if mixed else ""))
    for name, seconds in sorted(table["module_s"].items(),
                                key=lambda kv: -kv[1]):
        n = table["launches"].get(name, 0)
        ms = table["ms_per_launch"].get(name)
        common.log(f"  module {seconds:9.5f}s  {name}: {n} whole "
                   f"launches" + (f", {ms:.3f} ms each" if n else ""))
    return table


def table(obs: dict) -> dict | None:
    """This run's join (``by_scope``), logged once; None where the run
    was not traced, wrote no trace or no record, or the trace has no
    ``XLA Modules`` line."""
    if not obs.get("trace"):
        return None
    path = program_spans.this_runs_xplane()
    if path is None:
        return None
    return _logged(path)


def time_share(obs: dict, scopes: tuple) -> float | None:
    """Percent of this run's traced window that device 0 spent in the
    instructions of ``scopes``; None where there is no join, or no
    program of the run has an instruction in any of them."""
    t = table(obs)
    if t is None:
        return None
    found = [t["scope_s"][s] for s in scopes if s in t["scope_s"]]
    if not found:
        return None
    return 100.0 * sum(found) / t["window_s"]


def launch_ms(obs: dict, module: str) -> float | None:
    """Mean device milliseconds of a whole launch of ``module`` in the
    traced window, or None."""
    t = table(obs)
    return t["ms_per_launch"].get(module) if t else None
