"""The two readers that came with ``commanda_ep16.serve_rag``
(``moe.shared_time_share.decode``, ``attn.project_time_share.decode``),
in ``test_op_scope_metrics.py``'s manner: on a trace written for the
test beside the ``program_scopes`` records a run would have written, on
records that lack their scopes, and the entries."""

import json
import os

import pytest

from perfbench import common
from perfbench.tests import test_op_scope_metrics as base
from perfbench.tests.test_op_scope_metrics import (  # noqa: F401
    PREFILL, RESIDENT, TRACED, WINDOW_NS, out, reader)

from distributed_training_tpu.telemetry.op_scopes import SCOPES, scope_of

CELL = "commanda_ep16.serve_rag"
NEW = {"moe.shared_time_share.decode": ("expert layer",
                                        ("dtt.moe.shared",)),
       "attn.project_time_share.decode": ("attention", (
           "dtt.attn.project", "dtt.attn.out"))}
# ``test_op_scope_metrics.OPS`` under a parallel block's scopes: the
# prefill program's ``fusion.1`` (2,000 ns) is the shared product, the
# resident program's ``fusion.1`` (3,000 + 1,000 ns in the window) the
# query projection and its ``fusion.2`` (1,500 ns) the output's.
RECORDS = [
    {"kind": "program_scopes", "program": PREFILL[4:], "module": PREFILL,
     "scopes": {"dtt.moe.shared": ["fusion.1"],
                "dtt.moe.experts": ["copy.3"]},
     "mixed": [], "instructions": 2},
    {"kind": "program_scopes", "program": RESIDENT[4:],
     "module": RESIDENT,
     "scopes": {"dtt.engine": ["while.1"],
                "dtt.attn.project": ["fusion.1"],
                "dtt.attn.out": ["fusion.2"]},
     "mixed": [], "instructions": 3},
]


def test_the_innermost_scope_is_the_shared_experts():
    assert "dtt.moe.shared" in SCOPES
    assert scope_of("jit(f)/dtt.engine/while/body/dtt.moe.experts/"
                    "dtt.moe.shared/dot_general") == "dtt.moe.shared"
    assert scope_of("jit(f)/dtt.engine/while/body/dtt.moe.experts/"
                    "dot_general") == "dtt.moe.experts"


@pytest.mark.parametrize("name,want_ns", [
    ("moe.shared_time_share.decode", 2000),
    ("attn.project_time_share.decode", 4000 + 1500),
    # The shared product's time is NOT in the experts' share.
    ("moe.experts_time_share.decode", 1000)])
def test_a_share_is_its_scopes_seconds_of_the_window(out, name, want_ns):
    base.write_trace(out)
    base.write_records(out, RECORDS)
    assert reader(name).read(TRACED) == pytest.approx(
        100.0 * want_ns / WINDOW_NS)


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("lacks", ["scope", "record", "modules_line",
                                   "traced"])
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(
        out, name, lacks):
    """A program that names no such scope (the older blocks' shared
    expert stays under ``dtt.moe.experts``; the base test's records
    have no projection either), the parent's run with no record, the
    CPU rehearsal's trace, an untraced run: None, never an
    exception."""
    base.write_trace(out, modules=lacks != "modules_line")
    if lacks == "scope":
        base.write_records(out)
    elif lacks != "record":
        base.write_records(out, RECORDS)
    obs = {"trace": None} if lacks == "traced" else TRACED
    assert reader(name).read(obs) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_names_the_reader_and_the_new_cell_alone(name):
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    module = reader(name)
    layer, scopes = NEW[name]
    assert module.SCOPES == scopes and set(scopes) <= set(SCOPES)
    assert entry == {"name": name, "unit": module.UNIT,
                     "better": module.BETTER, "source": module.SOURCE,
                     "layer": module.LAYER, "moves": module.MOVES,
                     "workloads": [CELL]}
    assert module.LAYER == layer
    # The cell reports the end-to-end metric the share should move.
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
