#!/usr/bin/env python
"""Calibrate the planner's cost model against THIS hardware.

Micro-benchmarks the collectives the planner prices (all-gather,
reduce-scatter, all-reduce, ppermute across message sizes) and matmul
shapes on the current backend, and writes the fingerprinted
calibration table ``conf/calibration/<chip>.json`` the planner's
roofline consumes (``parallel/planner.py`` — measured curves when a
committed table matches the target chip, per-kind nominal constants
otherwise). After writing a table, re-run ``planner --write`` for any
target whose chip it serves: the committed plans record which
calibration scored them, and ``planner --check`` fails on the
mismatch until they are regenerated.

    python benchmarks/calibrate.py                  # this backend
    python benchmarks/calibrate.py --devices 8      # CPU: fake mesh
    python benchmarks/calibrate.py --json -         # print, no write

Off-TPU this measures fake CPU devices (shared-memory collectives) —
an honest calibration OF THE CPU MESH the container's multichip
benches run on, recorded with ``device_kind: cpu``; it never serves a
TPU chip's plans. On a real slice the same command measures the
hardware and writes the chip's table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Micro-benchmark collectives + matmuls and write "
                    "the planner's calibration table")
    ap.add_argument("--devices", type=int, default=8,
                    help="CPU backend: fake-device count for the "
                         "collective mesh (default 8; ignored on "
                         "real accelerators)")
    ap.add_argument("--iters", type=int, default=10,
                    help="timed iterations per point (default 10)")
    ap.add_argument("--sizes", default="",
                    help="comma-separated collective message sizes in "
                         "bytes (default: the ladder in "
                         "calibration/microbench.py)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="table path (default conf/calibration/"
                         "<chip>.json)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump the table doc here ('-' = stdout "
                         "only, no committed write)")
    args = ap.parse_args(argv)

    # Device-less-friendly defaults (bench_multichip discipline): CPU
    # backend with a fake mesh unless a real platform is requested.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count"
                f"={args.devices}").strip()
    from distributed_training_tpu.calibration import (microbench,
                                                      save_table)

    kwargs = dict(iters=args.iters)
    if args.sizes:
        kwargs["sizes"] = tuple(
            int(s) for s in args.sizes.split(",") if s)
    table = microbench.calibrate(**kwargs)
    doc = table.to_doc()

    fitted = doc["fitted"]
    print(f"[calibrate] device_kind={table.device_kind} "
          f"platform={table.platform} n_devices={table.n_devices} "
          f"fingerprint={doc['fingerprint']}", file=sys.stderr)
    for kind, fit in sorted(fitted["collectives"].items()):
        print(f"[calibrate]   {kind:15s} latency "
              f"{fit['latency_s'] * 1e6:8.1f} us   peak "
              f"{fit['peak_bytes_per_s'] / 1e9:6.2f} GB/s",
              file=sys.stderr)
    mm = fitted.get("matmul") or {}
    if mm:
        print(f"[calibrate]   matmul peak "
              f"{mm['peak_flops_per_s'] / 1e12:.4f} TFLOP/s",
              file=sys.stderr)

    if args.json == "-":
        json.dump(doc, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
    path = save_table(table, args.out)
    print(f"[calibrate] wrote {path}", file=sys.stderr)
    print("[calibrate] committed plans scored from an older table "
          "for this chip now FAIL planner --check; re-run planner "
          "--write for affected targets", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
