#!/usr/bin/env python
"""Compile the measurement programs WITHOUT a chip.

Every point below is lowered and compiled with the real TPU compiler
(libtpu) against a device-less v5e topology, with DTT_ASSUME_TPU=1 so
the Pallas flash kernels take their real (Mosaic-compiled) path: a
point whose kernels Mosaic rejects or whose program exceeds HBM fails
HERE, in the CPU sandbox, not in budgeted chip time. It proves a
program compiles, not that it runs.

Prints one JSON line per point: {point, ok, compile_s, temp_gib,
pallas_calls} or {point, ok: false, error}.

    python benchmarks/precompile_points.py
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# (name, batch, seq_len, model_name, model_kwargs[, train_overrides])
# — the configurations bench.measure() and the sweep tools run.
POINTS = [
    ("headline_b32", 32, 1024, "gpt2_125m",
     dict(remat=True, remat_policy="mlp")),
    ("batch48", 48, 1024, "gpt2_125m",
     dict(remat=True, remat_policy="mlp")),
    ("batch16", 16, 1024, "gpt2_125m",
     dict(remat=True, remat_policy="mlp")),
    ("long8k_win", 4, 8192, "gpt2_125m",
     dict(remat=True, remat_policy="mlp", max_seq_len=8192,
          attention_window=1024)),
    ("long8k_full", 4, 8192, "gpt2_125m",
     dict(remat=True, remat_policy="mlp", max_seq_len=8192)),
    ("long16k_win", 2, 16384, "gpt2_125m",
     dict(remat=True, remat_policy="mlp", max_seq_len=16384,
          attention_window=1024)),
    ("slice7b_2l", 1, 2048, "gpt2_125m",
     dict(d_model=4096, n_layers=2, n_heads=32, n_kv_heads=8,
          d_ff=16384, max_seq_len=2048, pos_encoding="rope",
          tie_embeddings=False, remat=True, remat_policy="mlp")),
    # bench_1b_single_chip.py's safety-net config (batch 1, adafactor,
    # full remat).
    ("bench1b_s1024", 1, 1024, "transformer_1b",
     dict(remat=True, remat_policy="full"),
     dict(optimizer="adafactor")),
]


def compile_point(name, batch, seq_len, model_name, model_kwargs,
                  train_overrides=None, topology="v5e:2x2"):
    """Compile one bench-style point via the shared topology-AOT
    builder (audit_collectives.lower_abstract_step — the one
    implementation, so this cannot drift from the audit's)."""
    from audit_collectives import lower_abstract_step

    lowered = lower_abstract_step(
        topology, 1, "ddp", model_name,
        {"dtype": "bfloat16", **model_kwargs},
        batch_size=batch, seq_len=seq_len,
        train_overrides={**dict(optimizer="adamw", learning_rate=6e-4,
                                dtype="bfloat16"),
                         **(train_overrides or {})})
    t0 = time.time()
    compiled = lowered.compile()
    dt = time.time() - t0
    txt = compiled.as_text()
    mem = compiled.memory_analysis()
    return {
        "point": name, "ok": True, "compile_s": round(dt, 1),
        "temp_gib": round(mem.temp_size_in_bytes / 2**30, 2),
        "pallas_calls": len(re.findall(
            r'custom_call_target="tpu_custom_call"', txt)),
    }


def main() -> int:
    # Set only when actually RUNNING the precompile (not at import —
    # an importer, e.g. the test suite, must not inherit a process-
    # wide DTT_ASSUME_TPU and start compiling Pallas kernels for its
    # CPU backend).
    os.environ.setdefault("DTT_ASSUME_TPU", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    failures = 0
    for spec in POINTS:
        try:
            rec = compile_point(*spec)
        except Exception as e:  # noqa: BLE001 — survey every point
            rec = {"point": spec[0], "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:300]}
            failures += 1
        print(json.dumps(rec), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
