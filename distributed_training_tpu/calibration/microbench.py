"""Micro-benchmarks that fill a CalibrationTable.

Measures, on the CURRENT jax backend (all visible devices in one 1-D
mesh):

- each collective kind the planner prices (``table.COLLECTIVE_KINDS``)
  across a ladder of message sizes — jitted ``shard_map`` programs so
  the timed op is the same XLA collective a training step runs, not a
  python-dispatch artifact;
- dense matmul across a ladder of square shapes — the achievable-FLOPs
  curve (spec-sheet peak is what marketing measured; the cost model
  wants what THIS chip reaches on XLA-compiled einsums).

Timing discipline: jit + one untimed warmup execution (compile and
first-touch allocation excluded), then ``iters`` back-to-back
dispatches with a single ``block_until_ready`` drain — the
once-per-measurement sync, not per-step (benchmarks/bench_multichip.py
precedent). Each point records seconds/op at the table's accounted-
bytes convention (see ``table.py``).

jax is imported inside functions only: callers (the calibrate CLI)
must be able to pin platform env first.
"""

from __future__ import annotations

import time

from distributed_training_tpu.calibration.table import (
    COLLECTIVE_KINDS, CalibrationTable)

# Message-size ladder (accounted bytes). Spans latency-dominated to
# bandwidth-dominated on every backend we target; float32 elements.
DEFAULT_SIZES = (1 << 16, 1 << 18, 1 << 20, 1 << 22, 1 << 23)

# Square matmul edge sizes; flops = 2 * n^3.
DEFAULT_MATMUL_SIZES = (256, 512, 1024, 2048)


def _timeit(fn, *args, iters: int) -> float:
    import jax
    out = fn(*args)
    jax.block_until_ready(out)  # warmup: compile + allocation
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _collective_fns(mesh, n: int):
    """kind -> (jitted shard_map fn, input builder(accounted_bytes)).

    Input shapes are chosen so the ACCOUNTED bytes of the timed op
    equal the requested x (table.py conventions)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def sm(f, ins, outs):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=ins,
                                 out_specs=outs, check_vma=False))

    def sharded_rows(nbytes):
        rows = max(n, int(nbytes) // 4 // n * n)
        return jax.device_put(
            jnp.zeros((rows,), jnp.float32),
            NamedSharding(mesh, P("x")))

    def replicated_rows(nbytes):
        rows = max(n, int(nbytes) // 4 // n * n)
        return jax.device_put(jnp.zeros((rows,), jnp.float32),
                              NamedSharding(mesh, P()))

    perm = [(i, (i + 1) % n) for i in range(n)]
    return {
        # x = full gathered tensor bytes: input is the sharded tensor
        # whose gather materializes x bytes on every device.
        "all-gather": (
            sm(lambda v: jax.lax.all_gather(v, "x", tiled=True),
               P("x"), P()),
            sharded_rows),
        # x = full reduced+scattered tensor bytes.
        "reduce-scatter": (
            sm(lambda v: jax.lax.psum_scatter(v, "x", tiled=True),
               P(), P("x")),
            replicated_rows),
        # x = 2 * tensor bytes (ring RS+AG phases): time an all-reduce
        # of a FULL x/2-byte replica on every device (in_specs P() —
        # a sharded operand would reduce only 1/n of the tensor and
        # under-price all-reduce by ~n x).
        "all-reduce": (
            sm(lambda v: jax.lax.psum(v, "x"), P(), P()),
            lambda nbytes: replicated_rows(nbytes / 2.0)),
        # x = bytes each device ships per permute: global tensor of
        # n * x bytes, every device rotates its x-byte shard.
        "ppermute": (
            sm(lambda v: jax.lax.ppermute(v, "x", perm),
               P("x"), P("x")),
            lambda nbytes: sharded_rows(nbytes * n)),
    }


def bench_collectives(sizes=DEFAULT_SIZES, iters: int = 10) -> dict:
    """kind -> [[accounted_bytes, seconds], ...] on all devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            "collective calibration needs >= 2 devices (got "
            f"{len(devs)}); on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    mesh = Mesh(np.array(devs), ("x",))
    fns = _collective_fns(mesh, len(devs))
    assert set(fns) == set(COLLECTIVE_KINDS)
    out: dict = {}
    for kind in COLLECTIVE_KINDS:
        fn, build = fns[kind]
        pts = []
        for nbytes in sorted(sizes):
            x = build(nbytes)
            pts.append([float(nbytes),
                        _timeit(fn, x, iters=iters)])
        out[kind] = pts
    return out


def bench_matmul(sizes=DEFAULT_MATMUL_SIZES, iters: int = 10) -> list:
    """[[flops, achieved_flops_per_s], ...] for square f32 matmuls —
    the per-device achievable-compute curve, measured with EVERY
    device computing concurrently (one matmul per device via a
    sharded batch). The cost model divides a step's FLOPs across all
    devices running at once; a solo-device measurement would be
    honest on a real slice (each chip owns its compute) but ~n x
    optimistic on the fake-CPU meshes that share one host's cores."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    f = jax.jit(shard_map(
        lambda m: jnp.einsum("bij,bjk->bik", m, m),
        mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    pts = []
    for edge in sorted(sizes):
        a = jax.device_put(jnp.ones((n, edge, edge), jnp.float32),
                           NamedSharding(mesh, P("x")))
        secs = _timeit(f, a, iters=iters)
        flops = 2.0 * edge ** 3  # per device, all devices concurrent
        pts.append([flops, flops / secs])
    return pts


def calibrate(sizes=DEFAULT_SIZES, matmul_sizes=DEFAULT_MATMUL_SIZES,
              iters: int = 10, note: str = "") -> CalibrationTable:
    """Run the full micro-benchmark suite and assemble the table for
    this backend's device kind."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    return CalibrationTable(
        device_kind=dev.device_kind,
        platform=dev.platform,
        n_devices=len(jax.devices()),
        collectives=bench_collectives(sizes, iters=iters),
        matmul=bench_matmul(matmul_sizes, iters=iters),
        meta={
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "iters": iters,
            "note": note or (
                "measured by benchmarks/calibrate.py; x-axis "
                "conventions in calibration/table.py"),
        })
