"""One run of one cell of the benchmark.

    python3 -m perfbench.run --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

A new process each run: it loads, warms up, measures for ``--seconds``,
checks the outputs against the plain reference, prints ONE JSON object
as the last line of stdout, and exits. Everything is found by name from
``BENCHMARK.json``: the cell's configuration in
``perfbench/configs/<config>.json``, its traffic in
``perfbench/traffic/<traffic>.json`` (whose ``kind`` picks the driver in
``perfbench/drivers/``), and each per-layer metric's reader in
``perfbench/layer_metrics/<metric>.py``. See ``perfbench/README.md``.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()   # before the heavy imports: they are set-up

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from perfbench import common, yardstick  # noqa: E402


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (known: "
                     f"{[c['name'] for c in bench['workloads']]})")


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


def require_device(chips: int) -> dict:
    """The device as JAX reports it. No accelerator, or another number
    of chips than the cell is defined on, is a failure: there is no
    fallback and no switch that admits a CPU."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SystemExit(f"perfbench: JAX found no TPU ({device}); this "
                         f"benchmark measures a chip and reports "
                         f"nothing without one")
    if device["count"] != chips:
        raise SystemExit(f"perfbench: the cell is defined on {chips} "
                         f"chip(s) and JAX found {device['count']}")
    return device


def setup_jax() -> None:
    """The program's persistent compile cache (one fixed path in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), for every program
    however small, so that only a checkout's first run compiles."""
    import jax

    from distributed_training_tpu.runtime import enable_compile_cache

    common.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None, root: str = common.ROOT) -> int:
    """``root`` holds ``BENCHMARK.json`` and the data files under
    ``perfbench/``; only the CPU rehearsal in ``perfbench/tests/`` passes
    another."""
    ap = argparse.ArgumentParser(prog="python3 -m perfbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    bench = load_json(root, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(root, "perfbench", "configs",
                       cell["config"] + ".json")
    traffic = load_json(root, "perfbench", "traffic",
                        cell["traffic"] + ".json")
    device = require_device(cell["chips"])
    peaks = yardstick.peaks_for(device["kind"])
    setup_jax()

    ctx = common.Context(cell=cell, config=config, traffic=traffic,
                         seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), started=STARTED)
    driver = common.load_file("drivers", traffic["kind"].split("_")[0])
    result = driver.run(ctx)

    # The peak on the chip is live arrays plus program temporaries; the
    # per-layer metrics report the two apart.
    memory = result["memory"]
    common.log(f"memory peaks on the fullest chip: {memory}")
    device["memory_peak_bytes"] = memory["in_use"] + memory["reserved"]
    line = {"correct": bool(result["correct"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": {}, "device": device}
    measured = {**result["end_to_end"], "setup_s": result["setup_s"]}
    common.log(f"end to end: {measured}")
    if not args.trace:
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            line["metrics"][m["name"]] = {"value": measured[m["name"]],
                                          "unit": m["unit"]}
    else:
        trace = result["trace"]
        obs = {**result["obs"], "end_to_end": measured, "trace": trace,
               "peaks": peaks, "chips": cell["chips"],
               "memory": memory}
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = common.load_file("layer_metrics", m["name"]).read(obs)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
