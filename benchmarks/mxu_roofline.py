#!/usr/bin/env python
"""Measure the ACHIEVABLE bf16 matmul rate on this device, per shape.

Before attributing a low training MFU to the model program, this
microbench establishes the device's empirical ceiling on the exact
matmul shapes the model runs (qkv/proj, MLP up/down, the vocab head)
plus big square anchors. If even a bare dot_general loop tops out far
below datasheet peak, the gap is the platform's (clock / datasheet
mismatch), not the program's — and "MFU vs achievable" becomes the
honest tuning target.

Prints one JSON line per shape:
  {"m":..,"k":..,"n":..,"tflops":..,"frac_peak":..}
and a final summary line with the best observed rate.

Usage:  python benchmarks/mxu_roofline.py [--cycles 15]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# Model shapes at the headline config (GPT-2 125M, batch 32, S=1024):
# rows = B*S tokens. Plus square anchors to catch shape-specific
# pathologies (a bad result on EVERY shape implicates the platform).
SHAPES = [
    (32768, 768, 2304),    # fused qkv projection
    (32768, 768, 768),     # attention output projection
    (32768, 768, 3072),    # MLP up
    (32768, 3072, 768),    # MLP down
    (2048, 768, 50304),    # xent head chunk
    (8192, 8192, 8192),    # big square anchor
    (4096, 4096, 4096),    # medium square anchor
]


def time_shape(m: int, k: int, n: int, cycles: int) -> tuple[float, bool]:
    """FLOP/s over a jitted scan of matmul cycles (m,k)@(k,n) ->
    (m,n)@(n,k) -> (m,k).  One executable, one dispatch: times the
    MXU, not the dispatch path.  f32 accumulation (preferred_element_type)
    matches the model's einsums; operands stay bf16 like the model's
    activations/weights; both orientations are shapes the model's
    fwd/bwd actually runs (bwd dgrad/wgrad are the transposes).

    Sync discipline: the chain returns a f32 SCALAR (sum of the final
    carry) and we fetch it to host via ``float()``, which cannot
    complete before the compute does.  The fixed per-call overhead (dispatch + 4-byte
    fetch) is then subtracted by differencing two chain lengths, which
    doubles as a timing-sanity check: if tripling the work does not
    grow the wall time, the measurement is flagged unreliable instead
    of reported as a physically impossible rate.

    Returns ``(flops_per_sec, reliable)``.
    """
    import jax
    import jax.numpy as jnp

    kx, kb, kc = jax.random.split(jax.random.PRNGKey(0), 3)
    x0 = jax.random.normal(kx, (m, k), dtype=jnp.bfloat16)
    b = jax.random.normal(kb, (k, n), dtype=jnp.bfloat16)
    c = jax.random.normal(kc, (n, k), dtype=jnp.bfloat16)

    def make_chain(length: int):
        @jax.jit
        def chain(x0, b, c, salt):
            # ``salt`` makes every invocation's inputs distinct, so no
            # layer of the stack (jit, PJRT) can serve a memoized result
            # for a repeated (executable, args) pair.
            def body(x, _):
                y = jax.lax.dot_general(
                    x, b, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.bfloat16)
                z = jax.lax.dot_general(
                    y, c, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.bfloat16)
                return z, None

            x, _ = jax.lax.scan(body, x0 + salt.astype(x0.dtype),
                                None, length=length)
            return jnp.sum(x, dtype=jnp.float32)   # scalar -> host sync

        return chain

    short, long_ = make_chain(cycles), make_chain(3 * cycles)
    salt = iter(range(1, 1000))

    def run(fn) -> float:
        s = jnp.float32(next(salt) * 1e-6)
        t0 = time.perf_counter()
        float(fn(x0, b, c, s))                    # host fetch = real sync
        return time.perf_counter() - t0

    run(short)                                    # compile + warm
    run(long_)
    dt_short = min(run(short) for _ in range(2))
    dt_long = min(run(long_) for _ in range(2))
    extra = dt_long - dt_short                    # 2*cycles of pure work
    reliable = extra > 0.25 * dt_long
    if not reliable:
        # Fall back to the long run's absolute time (still sync'd).
        return (2.0 * m * k * n * 2 * 3 * cycles) / max(dt_long, 1e-9), False
    return (2.0 * m * k * n * 2 * 2 * cycles) / extra, True


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cycles", type=int, default=15)
    args = ap.parse_args()

    import jax

    from distributed_training_tpu.utils.metrics import peak_flops_per_chip

    dev = jax.devices()[0]
    peak = peak_flops_per_chip(dev.device_kind)
    best = 0.0
    for m, k, n in SHAPES:
        try:
            flops, reliable = time_shape(m, k, n, args.cycles)
        except Exception as e:  # noqa: BLE001 — one bad shape != no data
            print(json.dumps({"m": m, "k": k, "n": n,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
            continue
        if reliable:
            best = max(best, flops)
        print(json.dumps({
            "m": m, "k": k, "n": n,
            "tflops": round(flops / 1e12, 1),
            "frac_peak": round(flops / peak, 3),
            "reliable": reliable,
        }), flush=True)
    print(json.dumps({
        "metric": "achievable_bf16_matmul",
        "device_kind": dev.device_kind,
        "best_tflops": round(best / 1e12, 1),
        "datasheet_peak_tflops": round(peak / 1e12, 1),
        "best_frac_peak": round(best / peak, 3),
        # best == 0 means no shape produced a work-scaling wall time;
        # treat every per-shape line above as suspect.
        "all_unreliable": best == 0.0,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
