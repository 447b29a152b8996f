#!/usr/bin/env python
"""MFU sweep harness for the headline bench (dev tool, real chip).

Runs bench.py's *exact* measurement core (imported, not duplicated) at
several batch sizes / model settings in one process and prints a JSON
line per point — the bench config is picked from this evidence.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import bench  # noqa: E402  (repo-root bench.py)


def run_sweep_point(batch: int, timed_steps: int = 10,
                    seq_len: int = bench.SEQ_LEN, **model_kwargs) -> dict:
    """One sweep measurement as a JSON-ready dict. A point that fails
    (a batch that does not fit is an expected outcome of a sweep)
    becomes an ``error`` row naming the config it ran; the matrix
    continues."""
    t0 = time.perf_counter()
    try:
        m = bench.measure(batch, seq_len=seq_len,
                          timed_steps=timed_steps,
                          phase=lambda *a, **k: None, **model_kwargs)
        m["mfu"] = round(m["mfu"], 4)
    except Exception as e:  # noqa: BLE001 — sweeps survive OOM points
        m = {"batch": batch, "seq_len": seq_len,
             "model_kwargs": {**bench.HEADLINE_MODEL_KWARGS,
                              **model_kwargs},
             "error": f"{type(e).__name__}: {e}"[:300]}
    m["point_wall_s"] = round(time.perf_counter() - t0, 1)
    return m


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--timed-steps", type=int, default=10)
    ap.add_argument("--model-kwargs", default="{}",
                    help="JSON kwargs forwarded to build_model")
    args = ap.parse_args()
    model_kwargs = json.loads(args.model_kwargs)
    for b in args.batches:
        # Success rows carry the EFFECTIVE model kwargs (headline
        # defaults merged with ours), recorded by the shared helper.
        print(json.dumps(run_sweep_point(
            b, timed_steps=args.timed_steps, seq_len=args.seq_len,
            **model_kwargs)), flush=True)


if __name__ == "__main__":
    main()
