"""Run a cell at another engine geometry than its configuration
freezes. By hand, on the chip:

    python3 perfbench/tests/sweep_engine.py \\
        --engine prefill_slots=4 prefill_chunk=128 -- \\
        --workload joyai_ep4.serve_decode --seed <n> --seconds 30 --trace 0

Copies ``BENCHMARK.json``, ``perfbench/configs`` and ``perfbench/traffic``
into a scratch root under ``perfbench_out/``, overrides the given keys
of ``serving.engine`` in the cell's configuration there, and hands the
rest of the command line to ``perfbench.run`` with that root: the
harness itself, unedited, so a sweep's numbers are read as a cell's are.
"""

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import common, run  # noqa: E402


def scratch_root(workload: str, engine: dict) -> str:
    root = os.path.join(common.OUT, "sweep_engine")
    shutil.rmtree(root, ignore_errors=True)
    for part in ("configs", "traffic"):
        shutil.copytree(os.path.join(common.HERE, part),
                        os.path.join(root, "perfbench", part))
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), root)
    cell = run.find_cell(run.load_json(root, "BENCHMARK.json"), workload)
    path = os.path.join(root, "perfbench", "configs",
                        cell["config"] + ".json")
    body = run.load_json(path)
    body["serving"]["engine"].update(engine)
    with open(path, "w") as f:
        json.dump(body, f, indent=1)
    return root


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", nargs="+", required=True,
                    metavar="key=value")
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    engine = {k: int(v) for k, v in (kv.split("=") for kv in args.engine)}
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    workload = rest[rest.index("--workload") + 1]
    return run.main(rest, root=scratch_root(workload, engine))


if __name__ == "__main__":
    sys.exit(main())
