"""Each traffic kind end to end at a toy size through the real harness."""

import json
import os

import pytest

from perfbench import run, traffic
from perfbench.tests import rehearse

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny")


@pytest.fixture
def admit_cpu(monkeypatch):
    rehearse.admit_cpu(monkeypatch.setattr)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,metric", [
    ("tiny.train", "train_tok_s_chip"),
    ("tiny.closed", "serve_out_tok_s"),
    ("tiny.open", "ttft_p95_ms"),
])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(admit_cpu, capsys,
                                                workload, metric, trace):
    rc = run.main(["--workload", workload, "--seed", "3000000019",
                   "--seconds", "2", "--trace", str(trace)], root=TINY)
    assert rc == 0
    line = last_line(capsys)
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == want | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    bench = run.load_json(TINY, "BENCHMARK.json")
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"]
                for m in run.metrics_of(bench, group, workload)}
    assert set(line["metrics"]) <= set(declared)
    for name, m in line["metrics"].items():
        assert m["unit"] == declared[name]
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == set(declared)
        assert metric in line["metrics"] and "setup_s" in line["metrics"]


def test_a_run_without_a_tpu_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "tiny.train", "--seed", "1", "--seconds",
                  "1", "--trace", "0"], root=TINY)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def _traffic(name):
    return run.load_json(TINY, "perfbench", "traffic", name + ".json")


def test_the_seed_reproduces_lengths_and_arrivals_exactly():
    t = _traffic("tiny_open")
    a = traffic.open_plan(t, 3000000019, 500, 30.0)
    b = traffic.open_plan(t, 3000000019, 500, 30.0)
    assert a == b
    c = traffic.closed_plan(_traffic("tiny_closed"), 7, 500)
    assert c == traffic.closed_plan(_traffic("tiny_closed"), 7, 500)


def test_every_seed_gets_the_same_sizes_in_another_order():
    t = _traffic("tiny_open")
    a = traffic.open_plan(t, 1, 500, 30.0)["requests"]
    b = traffic.open_plan(t, 2, 500, 30.0)["requests"]

    def sizes(reqs):
        return sorted((len(r["prompt_ids"]), r["max_new_tokens"])
                      for r in reqs)

    def gaps(reqs):
        due = [0.0] + [r["due_s"] for r in reqs]
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))
    assert sizes(a) == sizes(b) and gaps(a) == gaps(b)
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    assert a[0]["prompt_ids"] != b[0]["prompt_ids"]
    t = _traffic("tiny_closed")
    x = traffic.closed_plan(t, 1, 500)["lanes"]
    y = traffic.closed_plan(t, 2, 500)["lanes"]

    def lanes(ls):
        return sorted(tuple((len(r["prompt_ids"]), r["max_new_tokens"])
                            for r in lane) for lane in ls)
    assert lanes(x) == lanes(y)
    for lane in x:
        for r in lane:
            assert len(r["prompt_ids"]) + r["max_new_tokens"] <= \
                t["max_total"]


def test_out_tok_s_counts_between_deliveries_not_clock_edges():
    from perfbench.drivers.serve import out_tok_s

    # Deliveries of 10 tokens every 100 ms; wherever the clock edges
    # fall between two deliveries, the rate is the same.
    recs = [{"times": [0.1 * k + 0.0001 * j for j in range(10)]}
            for k in range(100)]
    rates = {round(out_tok_s(recs, 1.0 + shift, 6.0 + shift, 0.02)["value"],
                   6) for shift in (0.011, 0.033, 0.052, 0.097)}
    assert rates == {100.0}


def test_the_four_chip_path_on_four_virtual_devices():
    """``mesh.fsdp=4`` through the same driver, in a process of its own
    because the device count is fixed when JAX starts."""
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(TINY), "..",
                                      "rehearse.py"),
         "--workload", "tiny.train4", "--seed", "5", "--seconds", "2",
         "--trace", "1"], env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert "parallel.exposed_collective_share.train" in line["metrics"]
