#!/usr/bin/env python
"""Perf-ledger regression gate: ``perf_ledger.py --check``.

The repo commits one performance ledger per bench revision at the
root — ``MULTICHIP_r*.json`` (raw dry-run dumps, then planned-mesh
step-time runs) and ``SERVING_r*.json`` (serving storm runs). Since SERVING_r02 every
structured ledger carries a ``compared_to`` block: the predecessor's
headline numbers copied in verbatim, plus the speedup gates computed
against them. Those chains were only ever checked by eyeball. This
tool parses EVERY committed ``*_r*.json`` into one per-family
trajectory and goes red when:

- a family's revisions are not contiguous from r01, a ledger fails to
  parse, or a raw dump is missing its shape (``rc``/``tail``);
- a ``compared_to.entry`` is missing, cross-family, or not an earlier
  revision (SERVING also pins ``revision``/``compared_to.revision``
  strings to the filenames);
- the values a ledger CLAIMS for its predecessor (``tokens_per_s``,
  ``steady_tokens_per_s``, ``ttft_s``/``per_token_latency_s``
  percentiles, ``step_time_ms``, ``tokens_per_sec``) differ from what
  that predecessor actually recorded — the "regresses its own
  recorded gate" case: someone re-ran a bench and edited one file
  without re-deriving the chain;
- a recorded gate (``speedup``, ``realtime_speedup``,
  ``step_time_speedup``) no longer reproduces from the recorded
  numerator/denominator within rounding tolerance.

Deliberately NOT a rule: ``speedup >= 1``. SERVING_r05 honestly
records 0.852 on the saturated drain (prefix sharing is gated on its
5.27x prefill-token reduction, not wall clock) — a naive monotonic
gate would force dishonest ledgers. The gate is INTERNAL CONSISTENCY:
every number a ledger commits must still be derivable from the
ledgers it cites.

Stdlib-only and invoked BY PATH (the tools/lint_local.py discipline
— no package import, no jax): wired into tier-1 via
tests/test_lint_local.py exactly like ``planner --check``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LEDGER_RE = re.compile(r"^([A-Z][A-Z0-9]*)_r(\d+)\.json$")

# Relative tolerance for recomputed gates: recorded speedups are
# rounded to 3-4 significant digits.
GATE_RTOL = 2e-3
# Copied-verbatim predecessor values must match exactly up to float
# round-trip noise.
COPY_RTOL = 1e-6


def _close(a, b, rtol: float) -> bool:
    if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
        return False
    return abs(a - b) <= rtol * max(abs(a), abs(b), 1e-12)


def discover(root: str) -> dict[str, dict[int, str]]:
    """{family: {revision: path}} for every committed ledger."""
    fams: dict[str, dict[int, str]] = {}
    for path in sorted(glob.glob(os.path.join(root, "*_r*.json"))):
        m = LEDGER_RE.match(os.path.basename(path))
        if m:
            fams.setdefault(m.group(1), {})[int(m.group(2))] = path
    return fams


def _load(path: str, problems: list[str]) -> dict | None:
    try:
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
    except (OSError, ValueError) as e:
        problems.append(f"{os.path.basename(path)}: unreadable "
                        f"({type(e).__name__}: {e})")
        return None
    if not isinstance(d, dict):
        problems.append(f"{os.path.basename(path)}: not a JSON object")
        return None
    return d


def _serving_headline(d: dict) -> tuple[float | None, float | None]:
    """(headline tokens/s, steady tokens/s): the saturated drain is
    the headline when measured, else steady — the compared_to
    convention every serving ledger since r02 uses."""
    steady = (d.get("steady") or {}).get("tokens_per_s")
    sat = (d.get("saturated") or {}).get("tokens_per_s")
    return (sat if sat is not None else steady), steady


def _check_copied(name: str, field: str, claimed, actual,
                  ref_name: str, problems: list[str]) -> None:
    if claimed is None or actual is None:
        return
    if isinstance(claimed, dict) and isinstance(actual, dict):
        for k, v in claimed.items():
            _check_copied(name, f"{field}.{k}", v, actual.get(k),
                          ref_name, problems)
        return
    if not _close(claimed, actual, COPY_RTOL):
        problems.append(
            f"{name}: compared_to.{field}={claimed!r} does not match "
            f"{ref_name}'s recorded value {actual!r} — the chain was "
            f"edited without re-deriving it")


def _check_gate(name: str, gate: str, recorded, num, den,
                problems: list[str]) -> float | None:
    if recorded is None:
        return None
    if not isinstance(num, (int, float)) or not den:
        problems.append(f"{name}: gate {gate}={recorded} has no "
                        f"derivable numerator/denominator")
        return None
    derived = num / den
    if not _close(recorded, derived, GATE_RTOL):
        problems.append(
            f"{name}: gate {gate}={recorded} no longer reproduces "
            f"from its recorded inputs ({num}/{den} = {derived:.4f})"
            f" — the ledger regressed its own recorded gate")
    return derived


def _check_chain(family: str, rev: int, d: dict,
                 ledgers: dict[int, dict], problems: list[str]) -> None:
    name = f"{family}_r{rev:02d}.json"
    cmp_ = d.get("compared_to")
    if family == "SERVING" and d.get("revision") != f"r{rev:02d}":
        problems.append(f"{name}: revision={d.get('revision')!r} does "
                        f"not match filename")
    if cmp_ is None:
        return
    entry = cmp_.get("entry")
    m = LEDGER_RE.match(entry or "")
    if not m:
        problems.append(f"{name}: compared_to.entry={entry!r} is not "
                        f"a ledger filename")
        return
    ref_fam, ref_rev = m.group(1), int(m.group(2))
    if ref_fam != family:
        problems.append(f"{name}: compared_to.entry {entry} crosses "
                        f"families")
        return
    if ref_rev >= rev:
        problems.append(f"{name}: compared_to.entry {entry} is not an "
                        f"earlier revision")
        return
    ref = ledgers.get(ref_rev)
    if ref is None:
        problems.append(f"{name}: compared_to.entry {entry} is not "
                        f"committed")
        return

    if family == "SERVING":
        if cmp_.get("revision") != f"r{ref_rev:02d}":
            problems.append(f"{name}: compared_to.revision="
                            f"{cmp_.get('revision')!r} does not match "
                            f"entry {entry}")
        ref_headline, ref_steady = _serving_headline(ref)
        own_headline, own_steady = _serving_headline(d)
        _check_copied(name, "tokens_per_s", cmp_.get("tokens_per_s"),
                      ref_headline, entry, problems)
        _check_copied(name, "steady_tokens_per_s",
                      cmp_.get("steady_tokens_per_s"), ref_steady,
                      entry, problems)
        ref_steady_blk = ref.get("steady") or {}
        _check_copied(name, "ttft_s", cmp_.get("ttft_s"),
                      ref_steady_blk.get("ttft_s"), entry, problems)
        _check_copied(name, "per_token_latency_s",
                      cmp_.get("per_token_latency_s"),
                      ref_steady_blk.get("per_token_latency_s"),
                      entry, problems)
        _check_gate(name, "speedup", cmp_.get("speedup"),
                    own_headline, cmp_.get("tokens_per_s"), problems)
        _check_gate(name, "realtime_speedup",
                    cmp_.get("realtime_speedup"), own_steady,
                    cmp_.get("steady_tokens_per_s",
                             cmp_.get("tokens_per_s")), problems)
    else:  # MULTICHIP-shaped structured ledgers
        _check_copied(name, "step_time_ms", cmp_.get("step_time_ms"),
                      ref.get("step_time_ms"), entry, problems)
        _check_copied(name, "tokens_per_sec",
                      cmp_.get("tokens_per_sec"),
                      ref.get("tokens_per_sec"), entry, problems)
        if isinstance(cmp_.get("mesh"), dict) \
                and isinstance(ref.get("mesh"), dict) \
                and cmp_["mesh"] != ref["mesh"]:
            problems.append(f"{name}: compared_to.mesh {cmp_['mesh']} "
                            f"does not match {entry}'s {ref['mesh']}")
        _check_gate(name, "step_time_speedup",
                    cmp_.get("step_time_speedup"),
                    cmp_.get("step_time_ms"), d.get("step_time_ms"),
                    problems)


def _row(family: str, rev: int, d: dict) -> dict:
    row: dict = {"family": family, "revision": rev,
                 "file": f"{family}_r{rev:02d}.json",
                 "structured": "schema" in d}
    if family == "SERVING":
        headline, steady = _serving_headline(d)
        row.update(tokens_per_s=headline, steady_tokens_per_s=steady)
    elif "schema" in d:
        row.update(step_time_ms=d.get("step_time_ms"),
                   tokens_per_sec=d.get("tokens_per_sec"),
                   mfu=d.get("mfu"))
    else:
        row.update(rc=d.get("rc"))
    cmp_ = d.get("compared_to") or {}
    for gate in ("speedup", "realtime_speedup", "step_time_speedup"):
        if gate in cmp_:
            row[gate] = cmp_[gate]
    return row


def check(root: str) -> tuple[list[dict], list[str]]:
    """(trajectory rows, problems) over every committed ledger."""
    problems: list[str] = []
    trajectory: list[dict] = []
    fams = discover(root)
    if not fams:
        problems.append(f"no *_r*.json ledgers found under {root}")
    for family in sorted(fams):
        revs = sorted(fams[family])
        expected = list(range(1, len(revs) + 1))
        if revs != expected:
            problems.append(f"{family}: revisions {revs} are not "
                            f"contiguous from r01")
        ledgers: dict[int, dict] = {}
        for rev in revs:
            d = _load(fams[family][rev], problems)
            if d is not None:
                ledgers[rev] = d
        for rev in sorted(ledgers):
            d = ledgers[rev]
            name = f"{family}_r{rev:02d}.json"
            if "schema" not in d:
                # Raw dump: shape only.
                if "rc" not in d or "tail" not in d:
                    problems.append(f"{name}: raw ledger missing "
                                    f"rc/tail shape")
            else:
                _check_chain(family, rev, d, ledgers, problems)
            trajectory.append(_row(family, rev, d))
    return trajectory, problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_ledger",
        description="committed perf-ledger trajectory + regression "
                    "gate")
    ap.add_argument("--root", default=REPO,
                    help="directory holding the *_r*.json ledgers")
    ap.add_argument("--check", action="store_true",
                    help="validate chains and gates (the default "
                         "action; flag kept for planner --check "
                         "parity)")
    ap.add_argument("--json", action="store_true",
                    help="print the parsed trajectory as JSON")
    args = ap.parse_args(argv)

    trajectory, problems = check(args.root)
    if args.json:
        print(json.dumps({"trajectory": trajectory,
                          "problems": problems}, indent=1))
    else:
        for row in trajectory:
            gates = {k: row[k] for k in
                     ("speedup", "realtime_speedup",
                      "step_time_speedup") if k in row}
            print(f"[perf_ledger] {row['file']}: "
                  + (f"gates {gates}" if gates else "no chain"))
        for p in problems:
            print(f"[perf_ledger] RED: {p}")
    print(f"[perf_ledger] {len(trajectory)} ledgers checked, "
          f"{len(problems)} problems", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
