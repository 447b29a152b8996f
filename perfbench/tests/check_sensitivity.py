"""What the training cells' ``correct`` can and cannot see. Run by hand:

    python3 perfbench/tests/check_sensitivity.py --workload gpt2s.train1 \\
        --seed 11 [--draws 6] [--cpu]

Builds the cell's trainer as the benchmark does, then measures the
check's three distances (``perfbench/drivers/train.py::Distance``) between
the plain float32 reference at the initial parameters and the program at
parameters that were tampered with: a layer that adds nothing, no
position embeddings, every weight rounded to 8 bits. Prints one JSON
line a case, with whether the traffic file's tolerances would pass it,
and then the untampered distances for ``--draws`` further draws of
weights and rows, which is the room the tolerances have to leave. ``--cpu`` admits a CPU (bfloat16 without the kernels): a
rehearsal of the arithmetic, not the gap on the chip.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import common, run  # noqa: E402


def layer_adds_nothing(params, k):
    """Layer ``k``'s two output projections zeroed: its block leaves the
    residual stream as it found it."""
    attn, mlp = dict(params["attn"]), dict(params["mlp"])
    attn["wo"] = attn["wo"].at[k].set(0)
    mlp["wo"], mlp["bo"] = mlp["wo"].at[k].set(0), mlp["bo"].at[k].set(0)
    return {**params, "attn": attn, "mlp": mlp}


def no_positions(params):
    return {**params, "pos_embed": params["pos_embed"] * 0}


def rounded(params, how):
    """Every matrix rounded to 8 bits and back: ``float8`` keeps the
    four significant bits of e4m3 and any exponent, which is e4m3 under
    the best scale there is (in arithmetic: a v5e has no float8, and its
    compiler makes ``astype(float8).astype(float32)`` a no-op); ``int8``
    is symmetric with one scale for each output column."""
    import jax
    import jax.numpy as jnp

    def one(x):
        if x.ndim < 2:
            return x
        if how == "float8":
            mantissa, exponent = jnp.frexp(x)
            return jnp.ldexp(jnp.round(mantissa * 16) / 16, exponent)
        scale = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0
        return jnp.round(x / jnp.maximum(scale, 1e-30)) * scale
    return jax.tree.map(one, params)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--draws", type=int, default=0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    bench = run.load_json(common.ROOT, "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    config = run.load_json(common.HERE, "configs", cell["config"] + ".json")
    traffic = run.load_json(common.HERE, "traffic",
                            cell["traffic"] + ".json")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        run.require_device(cell["chips"])
        run.setup_jax()

    import jax

    from perfbench.drivers import train

    ctx = common.Context(cell=cell, config=config, traffic=traffic,
                         seed=args.seed, seconds=0.0, trace=False,
                         started=time.perf_counter())
    trainer, _loader, dataset = train.build(ctx)
    check_rows = traffic["check_rows"]
    params, shardings, rows = train.initial(trainer, dataset, check_rows)
    distance = train.Distance(ctx, trainer.model, shardings)
    rng = trainer.step_rng
    want = distance.reference(params, rows)

    def report(case, found):
        verdict = train.within(found, traffic)
        print(json.dumps({
            "workload": args.workload, "case": case, **found,
            **{k: v for k, v in verdict.items() if k.endswith("passes")},
            "correct": verdict["ok"]}), flush=True)

    last = config["n_layer"] - 1
    cases = [
        ("as committed", lambda p: p),
        ("layer 0 adds nothing", lambda p: layer_adds_nothing(p, 0)),
        (f"layer {last // 2} adds nothing",
         lambda p: layer_adds_nothing(p, last // 2)),
        (f"layer {last} adds nothing",
         lambda p: layer_adds_nothing(p, last)),
        ("no position embeddings", no_positions),
        ("weights rounded to float8 e4m3", lambda p: rounded(p, "float8")),
        ("weights rounded to int8", lambda p: rounded(p, "int8")),
    ]
    for name, tamper in cases:
        tampered = jax.jit(tamper, out_shardings=shardings)(params)
        report(name, distance(tampered, rows, rng, want))
        del tampered
    del params, want
    for draw in range(1, args.draws + 1):
        params, _, rows = train.initial(trainer, dataset, check_rows, draw)
        report(f"as committed, draw {draw}", distance(
            params, rows, rng, distance.reference(params, rows)))
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
