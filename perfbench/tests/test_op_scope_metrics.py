"""``perfbench/op_scopes.py`` and the six readers that came with it, on a
trace written for the test (``telemetry/xplane.py::encode_xspace``): an
``XLA Modules`` line with two modules that both hold a ``fusion.1``,
under different scopes, beside the ``program_scopes`` records a run
would have written; on the trace recorded on a TPU v5e; on hand-made
step records; through the toy closed-loop cell; and the entries."""

import json
import os
import shutil
import time

import pytest

from perfbench import common, op_scopes, program_spans, run
from perfbench.tests.test_trace_reduce import SMALL

from distributed_training_tpu.telemetry import xplane as X

PREFILL, RESIDENT = "jit_serving_prefill_batch", \
    "jit_serving_resident_decode"
SERVING = ["gpt2xl.serve_decode", "joyai_ep4.serve_decode",
           "smallthinker_ep4.serve_long", "dots3_ep8.serve_sparse"]
# name -> (the cells ISSUE 36 names, the scopes it sums)
SHARES = {
    "kv_cache.read_time_share.decode": (SERVING, ("dtt.kv.read",)),
    "attn.core_time_share.decode": (SERVING, ("dtt.attn.core",)),
    "attn.select_time_share.decode": (SERVING[3:], ("dtt.attn.select",)),
    "moe.experts_time_share.decode": (
        SERVING[1:], ("dtt.moe.route", "dtt.moe.experts")),
}
MS = ["engine.prefill_chunk_ms.decode", "engine.decode_iter_ms.decode"]


def op(name, start, end, opcode="fusion"):
    return X.Event(f"%{name} = bf16[8,128]{{1,0:T(8,128)(2,1)}} "
                   f"{opcode}(bf16[8,128]{{1,0}} %p)",
                   start * 1000, (end - start) * 1000)


def module(name, start, end):
    return X.Event(f"{name}(7796946108624892431)", start * 1000,
                   (end - start) * 1000)


# Nanoseconds. The window is [500, 14000): the third launch is cut by
# it, its operation counts up to the window's end and the launch is no
# whole launch.
OPS = [op("fusion.1", 1000, 3000), op("copy.3", 3000, 4000, "copy"),
       op("mystery.9", 4000, 4500),
       op("while.1", 6000, 12000, "while"),
       op("fusion.1", 6500, 9500), op("fusion.2", 9500, 11000),
       op("convert.1", 12200, 12400, "convert"),
       op("fusion.1", 13000, 15000)]
MODULES = [module(PREFILL, 1000, 5000), module(RESIDENT, 6000, 12000),
           module("jit_convert_element_type", 12200, 12400),
           module(RESIDENT, 13000, 15000)]
RECORDS = [
    {"kind": "run_start", "t": 0.0, "step": 0},
    {"kind": "program_scopes", "program": PREFILL[4:], "module": PREFILL,
     "scopes": {"dtt.attn.core": ["fusion.1"], "_unscoped_": ["copy.3"],
                "dtt.head": ["fusion.77"]},
     "mixed": ["fusion.1"], "instructions": 3},
    {"kind": "serving", "op": "idle", "dur_s": 0.1},
    {"kind": "program_scopes", "program": RESIDENT[4:],
     "module": RESIDENT,
     "scopes": {"dtt.engine": ["while.1"], "dtt.kv.read": ["fusion.1"],
                "dtt.moe.experts": ["fusion.2"]},
     "mixed": ["fusion.2"], "instructions": 3},
]
WINDOW_NS = 13500


def write_trace(out, modules=True):
    lanes = [X.Lane("XLA Ops", OPS)]
    if modules:
        lanes.insert(0, X.Lane("XLA Modules", MODULES))
    planes = [X.Plane("/device:TPU:0", lanes),
              X.Plane("/host:CPU", [X.Lane("python3", [
                  X.Event("perfbench.window", 500 * 1000,
                          WINDOW_NS * 1000)])])]
    run_dir = out / "trace" / "cell" / "plugins" / "profile" / "t"
    run_dir.mkdir(parents=True, exist_ok=True)
    path = run_dir / "host.xplane.pb"
    path.write_bytes(X.encode_xspace(planes))
    return str(path)


def write_records(out, records=RECORDS):
    (out / "events.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in records))


@pytest.fixture()
def out(monkeypatch, tmp_path):
    monkeypatch.setattr(common, "OUT", str(tmp_path))
    op_scopes._logged.cache_clear()
    return tmp_path


def reader(name):
    return common.load_file("layer_metrics", name)


TRACED = {"trace": {"window_s": WINDOW_NS / 1e9}}


def test_the_join_gives_exact_seconds_by_scope_and_by_module(out):
    path = write_trace(out)
    write_records(out)
    table = op_scopes.by_scope(path, op_scopes.this_runs_maps())
    ns = lambda d: {k: round(v * 1e9) for k, v in d.items()}  # noqa: E731
    assert table["window_s"] == pytest.approx(WINDOW_NS / 1e9, rel=1e-12)
    # ``fusion.1`` of the prefill program is attention, ``fusion.1`` of
    # the resident program a read of the cache; ``while.1`` keeps its
    # self time; an instruction no record lists and a module with no
    # record are unscoped; ``dtt.head`` is listed and never ran.
    assert ns(table["scope_s"]) == {
        "dtt.attn.core": 2000, "dtt.kv.read": 3000 + 1000,
        "dtt.moe.experts": 1500, "dtt.engine": 6000 - 3000 - 1500,
        "_unscoped_": 1000 + 500 + 200, "dtt.head": 0}
    assert ns(table["module_s"]) == {
        PREFILL: 3500, RESIDENT: 6000 + 1000,
        "jit_convert_element_type": 200}
    assert round(table["mixed_s"] * 1e9) == 2000 + 1500
    assert round(table["unlisted_s"] * 1e9) == 500
    assert table["busy_s"] == pytest.approx(10700e-9, rel=1e-9)
    assert table["busy_s"] == pytest.approx(
        sum(table["module_s"].values()), rel=1e-12)
    assert table["launches"] == {PREFILL: 1, RESIDENT: 1,
                                 "jit_convert_element_type": 1}
    # The largest operations under their scope, the two ``fusion.1``
    # apart.
    assert [(round(s * 1e9), *rest) for s, *rest in table["ops"][:3]] \
        == [(4000, "dtt.kv.read", RESIDENT, "fusion.1", False),
            (2000, "dtt.attn.core", PREFILL, "fusion.1", True),
            (1500, "dtt.moe.experts", RESIDENT, "fusion.2", True)]
    assert table["ms_per_launch"][PREFILL] == pytest.approx(0.004)
    assert table["ms_per_launch"][RESIDENT] == pytest.approx(0.006)


@pytest.mark.parametrize("name,want_ns", [
    ("kv_cache.read_time_share.decode", 4000),
    ("attn.core_time_share.decode", 2000),
    ("moe.experts_time_share.decode", 1500),
    # No program of this run has a selection: not found is not zero.
    ("attn.select_time_share.decode", None)])
def test_a_share_is_its_scopes_seconds_of_the_window(out, name, want_ns):
    write_trace(out)
    write_records(out)
    got = reader(name).read(TRACED)
    if want_ns is None:
        assert got is None
    else:
        assert got == pytest.approx(100.0 * want_ns / WINDOW_NS)
    assert reader(name).SCOPES == SHARES[name][1]


def test_a_scope_that_is_listed_and_never_ran_reads_zero(out):
    write_trace(out)
    records = json.loads(json.dumps(RECORDS))
    records[1]["scopes"]["dtt.attn.select"] = ["sort.23"]
    write_records(out, records)
    assert reader("attn.select_time_share.decode").read(TRACED) == 0.0


@pytest.mark.parametrize("name", sorted(SHARES))
@pytest.mark.parametrize("lacks", ["record", "file", "old_file",
                                   "modules_line", "trace", "traced"])
def test_a_reader_reports_nothing_where_a_half_is_missing(out, name,
                                                          lacks):
    """The parent writes no ``program_scopes`` record; an untraced run
    has no sink, so no file of its own (one an older run left is not
    its); the CPU rehearsal's trace has no ``XLA Modules`` line. None,
    never an exception."""
    if lacks != "trace":
        write_trace(out, modules=lacks != "modules_line")
    if lacks == "record":
        write_records(out, [r for r in RECORDS
                            if r["kind"] != "program_scopes"])
    elif lacks != "file":
        write_records(out)
    if lacks == "old_file":
        old = time.time() - 7 * 86400
        os.utime(out / "events.jsonl", (old, old))
    obs = {} if lacks == "traced" else TRACED
    assert reader(name).read(obs) is None


def test_the_recorded_v5e_trace_adds_up_to_its_busy_seconds(out):
    """``jit_f`` of the trace recorded on a chip, under a record that
    names two of its instructions: the seconds by scope add up to what
    ``trace_reduce.reduce`` calls busy (``test_program_metrics.py``:
    29,087 ns of 17,221,470), operation by operation inside its
    module."""
    maps = {"jit_f": {"scope": {"while": "dtt.engine",
                                "copy-done": "dtt.kv.read"},
                      "mixed": set()}}
    table = op_scopes.by_scope(SMALL, maps)
    assert table["window_s"] == pytest.approx(17221470e-9, rel=1e-12)
    assert table["busy_s"] == pytest.approx(29087e-9, rel=1e-9)
    assert set(table["module_s"]) == {"jit_f"}
    assert table["launches"] == {"jit_f": 4}
    assert table["scope_s"]["dtt.kv.read"] > 0
    assert table["scope_s"]["dtt.engine"] > 0
    assert table["unlisted_s"] == pytest.approx(
        table["scope_s"]["_unscoped_"], rel=1e-12)


def step(op, dur_s, **more):
    return {"op": op, "dur_s": dur_s, "tokens": 0, **more}


def test_a_chunk_and_an_iteration_are_read_from_the_step_records(
        out, capsys):
    steps = [step("prefill", 0.080), step("prefill", 0.084),
             step("decode", 0.200, iters=8), step("decode", 0.052,
                                                  iters=2),
             step("idle", 9.0)]
    obs = {"engine_steps": steps}
    chunk, it = (reader(n).read for n in MS)
    assert chunk(obs) == pytest.approx(82.0)
    assert it(obs) == pytest.approx(25.2)
    assert "on the device" not in capsys.readouterr().err
    # The parent's records carry no ``iters``; no step, no number.
    old = {"engine_steps": [step("decode", 0.2, slot_iters=64)]}
    assert it(old) is None and chunk(old) is None
    assert it({"engine_steps": []}) is None
    # Where the run has a join, the device's clock is logged beside.
    write_trace(out)
    write_records(out)
    assert chunk({**obs, **TRACED}) == pytest.approx(82.0)
    assert it({**obs, **TRACED}) == pytest.approx(25.2)
    err = capsys.readouterr().err
    assert "0.004 ms on the device a launch of " + PREFILL in err
    # 0.006 ms a launch over the records' 5 iterations a launch.
    assert "0.001 ms on the device (0.006 ms a launch of " + RESIDENT \
        in err


@pytest.mark.parametrize("name", sorted(SHARES) + MS)
def test_the_entry_names_the_reader_and_its_cells(name):
    bench = run.load_json(common.ROOT, "BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    mod = reader(name)
    cells = SHARES[name][0] if name in SHARES else SERVING
    assert entry == {"name": name, "unit": mod.UNIT,
                     "better": "lower", "source": mod.SOURCE,
                     "layer": mod.LAYER, "moves": "serve_out_tok_s",
                     "workloads": cells}
    assert (mod.UNIT, mod.SOURCE) == (
        ("%", "device_trace") if name in SHARES
        else ("ms", "program_span"))
    # A layer the benchmark already names, letter for letter.
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] not in SHARES
                              and m["name"] not in MS}


def test_the_six_entries_are_appended_and_nothing_else_moved():
    bench = run.load_json(common.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-6:] == [
        "kv_cache.read_time_share.decode", "attn.core_time_share.decode",
        "attn.select_time_share.decode", "moe.experts_time_share.decode",
        "engine.prefill_chunk_ms.decode", "engine.decode_iter_ms.decode"]
    assert names[-7] == "ops.sparse_prefill_time_share.decode"


def test_the_readers_on_a_real_engines_records(monkeypatch, tmp_path,
                                               capsys):
    """The toy closed-loop cell through the real harness, traced: the
    engine writes its ``program_scopes`` records into this run's
    ``events.jsonl`` (the sink is installed before the engine is
    built), the two readers of step records report, and the four
    shares, whose other half the CPU rehearsal's canned trace lacks,
    leave their metric out without raising."""
    from perfbench.tests import rehearse
    from perfbench.tests.test_rehearsal import TINY

    root = tmp_path / "root"
    shutil.copytree(TINY, root)
    monkeypatch.setattr(common, "OUT", str(tmp_path / "out"))
    op_scopes._logged.cache_clear()
    bench = run.load_json(str(root), "BENCHMARK.json")
    real = {m["name"]: m for m in run.load_json(
        common.ROOT, "BENCHMARK.json")["per_layer"]}
    new = sorted(SHARES) + MS
    bench["per_layer"] += [{**real[n], "workloads": ["tiny.closed"]}
                           for n in new]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rehearse.admit_cpu(monkeypatch.setattr)
    assert run.main(["--workload", "tiny.closed", "--seed", "3000000019",
                     "--seconds", "2", "--trace", "1"],
                    root=str(root)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = {n: line["metrics"][n]["value"] for n in new
           if n in line["metrics"]}
    assert set(got) == set(MS)
    assert got["engine.prefill_chunk_ms.decode"] > 0
    assert got["engine.decode_iter_ms.decode"] > 0
    maps = op_scopes.this_runs_maps()
    assert maps and all(m.startswith("jit_serving_") for m in maps)
    assert any("dtt.kv.read" in set(m["scope"].values())
               for m in maps.values())
    assert program_spans.this_runs_xplane() is None
