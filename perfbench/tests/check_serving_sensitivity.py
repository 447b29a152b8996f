"""What the serving check of the latent-attention expert cell
(``joyai_ep4.serve_decode``) can and cannot see. Run by hand, on the chip:

    python3 perfbench/tests/check_serving_sensitivity.py --seed 11 \\
        [--seconds 6] [--cases committed rope_off ...]

Each case is a process of its own that runs the cell as the benchmark
does, ``perfbench/drivers/serve.py::run`` unedited: the engine at the
configuration's geometry behind its server, under the traffic file's 32
clients, and at the end the harness's own ``check_against_reference`` on
what was streamed. The PROGRAM is tampered with, the reference and the
weights it is made from never: a patch at trace time (RoPE the identity,
the routed sum or the shared expert dropped, weights rounded to 8 bits
where they are used)
or, for one layer's experts, a tampered copy of the parameters handed to
the engine alone. Prints one JSON line a case: the harness's worst logit
gap and whether the traffic file's ``logit_tolerance`` passes it, and
beside it the quantiles of the same gaps over the same tokens (a second
pass of the reference; its worst must equal the harness's), which is
what a second number of the check would read (PERF.md section 7 row 11).

``--cases flips`` is another kind of case: how often the bfloat16
program's router and the float32 reference's pick another set of experts
for the same token in the model's full forward, and the gap that is left
when the program is handed the reference's picks.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import common, run  # noqa: E402

CELL = "joyai_ep4.serve_decode"
CASES = ("committed", "rope_off", "routed_experts_out",
         "shared_expert_out", "one_layers_experts_out", "experts_int8", "all_int8",
         "all_float8", "flips")


def round_8bit(x, how):
    """``x`` rounded to 8 bits and back. ``int8``: symmetric, one scale
    an output column; ``float8``: the four significant bits of e4m3 at
    any exponent (in arithmetic: a v5e has no float8 and its compiler
    makes the cast a no-op)."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    if how == "float8":
        mantissa, exponent = jnp.frexp(x32)
        return jnp.ldexp(jnp.round(mantissa * 16) / 16,
                         exponent).astype(x.dtype)
    scale = jnp.max(jnp.abs(x32), axis=-2, keepdims=True) / 127.0
    return (jnp.round(x32 / jnp.maximum(scale, 1e-30)) * scale
            ).astype(x.dtype)


def tamper(case: str, config: dict, setattr_=setattr) -> None:
    """Patch the program for ``case`` through ``setattr_(object, name,
    value)``. Nothing here touches the reference, nor the parameters
    the harness makes it from."""
    from distributed_training_tpu.models import latent_moe
    from distributed_training_tpu.serving import blocks, engine

    kw = config["program"]["kwargs"]
    if case == "rope_off":
        setattr_(latent_moe, "rope_interleaved",
                 lambda x, positions, theta: x)
    elif case == "routed_experts_out":
        route = latent_moe.route

        def no_weight(h, m, c):
            idx, g = route(h, m, c)
            return idx, g * 0
        setattr_(latent_moe, "route", no_weight)
    elif case == "shared_expert_out":
        mlp = latent_moe.gated_mlp
        width = kw.get("n_shared_experts", 1) * kw["moe_d_ff"]

        def dense_only(h, m, w=latent_moe._cast):
            return mlp(h, m, w) * (m["wg"].shape[-1] != width)
        setattr_(latent_moe, "gated_mlp", dense_only)
    elif case == "one_layers_experts_out":
        # The engine alone gets the copy: the harness keeps its own.
        real = engine.Engine

        def engine_with_a_layer_emptied(model, params, cfg):
            moe = dict(params["moe"])
            moe["mlp"] = {**moe["mlp"],
                          "wd": moe["mlp"]["wd"].at[1].set(0)}
            return real(model, {**params, "moe": moe}, cfg)
        setattr_(engine, "Engine", engine_with_a_layer_emptied)
    elif case in ("experts_int8", "all_int8", "all_float8"):
        weight = blocks.weight
        held = kw["n_routed_experts"] // kw["ep_size"]

        def rounded(leaf, dt):
            x = weight(leaf, dt)
            an_expert = (x.ndim == 3 and x.shape[0] == held
                         and kw["moe_d_ff"] in x.shape[1:])
            if x.ndim < 2 or (case == "experts_int8" and not an_expert):
                return x
            return round_8bit(x, "float8" if case == "all_float8"
                              else "int8")
        setattr_(blocks, "weight", rounded)
    elif case != "committed":
        raise SystemExit(f"no case {case!r}")


def with_quantiles(harness_check):
    """The harness's check, then the same gaps again for their
    quantiles."""
    def check(ctx, model, params, records, plan_prompts):
        import jax
        import jax.numpy as jnp
        import numpy as np

        out = harness_check(ctx, model, params, records, plan_prompts)
        ref = common.load_reference(ctx.config)
        forward = jax.jit(lambda p, ids: ref.logits(
            p, ids, ctx.config["n_head"]))
        ref_params = jax.jit(ref.from_program)(params)
        by_id = {r["id"]: r for r in records}
        gaps = []
        for rid in out["requests"]:
            prompt, toks = plan_prompts[rid], by_id[rid]["tokens"]
            seq = prompt + toks
            ids = np.zeros(ctx.config["n_positions"], np.int32)
            ids[:len(seq) - 1] = seq[:-1]
            rows = np.asarray(forward(ref_params, jnp.asarray(ids)))[
                len(prompt) - 1:len(seq) - 1]
            gaps += [float(r.max() - r[t]) for r, t in zip(rows, toks)]
        g = np.asarray(gaps)
        assert abs(g.max() - out["worst_logit_gap"]) < 1e-6, (
            g.max(), out["worst_logit_gap"])
        out["gap_quantiles"] = {
            "p50": float(np.percentile(g, 50)),
            "p90": float(np.percentile(g, 90)),
            "p99": float(np.percentile(g, 99)),
            "tokens_over_0.1": int((g > 0.1).sum())}
        return out
    return check


def one_case(case: str, seed: int, seconds: float,
             root: str = common.ROOT, cell: str = CELL,
             setattr_=setattr) -> int:
    """``root`` and ``cell`` are the CPU rehearsal's (a toy cell of the
    same kind); the chip's are the committed cell."""
    bench = run.load_json(root, "BENCHMARK.json")
    cell = run.find_cell(bench, cell)
    config = run.load_json(root, "perfbench", "configs",
                           cell["config"] + ".json")
    traffic = run.load_json(root, "perfbench", "traffic",
                            cell["traffic"] + ".json")
    run.require_device(cell["chips"])
    run.setup_jax()
    if case == "flips":
        return flips(config, seed)

    from perfbench.drivers import serve

    tamper(case, config, setattr_)
    setattr_(serve, "check_against_reference",
             with_quantiles(serve.check_against_reference))
    ctx = common.Context(cell=cell, config=config, traffic=traffic,
                         seed=seed, seconds=seconds, trace=False,
                         started=time.perf_counter())
    result = serve.run(ctx)
    check = result["obs"]["check"]
    print(json.dumps({
        "case": case, "seed": seed, "tokens": check["tokens"],
        "worst_logit_gap": check["worst_logit_gap"],
        **check["gap_quantiles"],
        "argmax_equal": check["argmax_equal"] / check["tokens"],
        "tolerance": check["tolerance"], "harness_ok": check["ok"],
        "failed": result["failed"],
        "serve_out_tok_s": result["end_to_end"]["serve_out_tok_s"]}),
        flush=True)
    return 0


def flips(config: dict, seed: int, tokens: int = 1216) -> int:
    """Teacher-forced full forward of ``tokens`` random ids on both
    sides: the share of tokens for which the program's router and the
    reference's pick another set, an expert layer; the worst gap of the
    program's own argmax under the reference, on its own picks and
    handed the reference's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.models import build_model, latent_moe

    prog = config["program"]
    model = build_model(prog["build_model"], dtype="bfloat16",
                        **prog["kwargs"])
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)))(
            jax.random.PRNGKey(seed))
    ref = common.load_reference(config)
    ref_params = jax.jit(ref.from_program)(params)
    n_head, k = config["n_head"], model.cfg.moe_top_k
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, prog["token_vocab"], tokens), jnp.int32)

    def ref_forward(rp):
        picks = []
        with jax.default_matmul_precision("highest"):
            x = rp["embed"][ids]
            for p in rp["layers"]:
                x = x + ref.attention(ref.rms(x, p["ln_1"]), p, n_head)
                h = ref.rms(x, p["ln_2"])
                if "w_r" in p:
                    s = jax.nn.sigmoid(h @ p["w_r"])
                    picks.append(jnp.argsort(-(s + p["b_r"]), -1)[:, :k])
                    x = x + ref.experts(h, p)
                else:
                    x = x + ref.gated(h, p["w_gate"], p["w_up"],
                                      p["w_down"])
            return ref.rms(x, rp["norm"]) @ rp["head"], jnp.stack(picks)

    want, ref_picks = jax.jit(ref_forward)(ref_params)
    want = np.asarray(want)
    seen, forced = [], iter(())
    route = latent_moe.route

    def spy(h, m, c):
        idx, g = route(h, m, c)
        seen.append(idx)
        give = next(forced, None)
        if give is None:
            return idx, g
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(h.astype(jnp.float32)
                               @ m["router"].astype(jnp.float32))
        g = jnp.take_along_axis(s, give, -1)
        return give, c.routed_scaling_factor * g / g.sum(-1, keepdims=True)

    # The layers are scanned: unroll them by hand so each call is seen.
    def program(params, picks=None):
        nonlocal forced
        seen.clear()
        forced = iter(picks) if picks is not None else iter(())
        c = model.cfg
        dt = jnp.dtype(c.dtype)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)[None]
        x = params["tok_embed"][ids][None].astype(dt)
        for stack in model.runs(params):
            for i in range(stack["ln1"].shape[0]):
                layer = jax.tree.map(lambda a: a[i], stack)
                h = latent_moe.rms_norm(x, layer["ln1"], c.rms_norm_eps)
                attn = latent_moe.expanded_attention(
                    *latent_moe.project(h, layer["attn"], pos, c),
                    layer["attn"], c)
                x = x + jnp.einsum("...hk,hkd->...d", attn,
                                   layer["attn"]["wo"].astype(dt))
                h = latent_moe.rms_norm(x, layer["ln2"], c.rms_norm_eps)
                x = x + model.feed_forward(layer, h)[0]
        x = latent_moe.rms_norm(x, params["final_norm"], c.rms_norm_eps)
        lg = jnp.einsum("...d,dv->...v", x, params["lm_head"].astype(dt))
        return lg[0].astype(jnp.float32), jnp.stack(seen)

    latent_moe.route = spy
    try:
        free, picks = jax.jit(program)(params)
        held, _ = jax.jit(program)(params, list(ref_picks))
    finally:
        latent_moe.route = route
    picks, ref_picks = np.asarray(picks), np.asarray(ref_picks)
    other = np.array([[set(a) != set(b) for a, b in zip(pl, rl)]
                      for pl, rl in zip(picks, ref_picks)])

    def worst(lg):
        tok = np.asarray(lg).argmax(-1)
        return float((want.max(-1) - want[np.arange(len(tok)), tok]).max())
    print(json.dumps({
        "case": "flips", "seed": seed, "tokens": int(ids.shape[0]),
        "other_set_share_by_expert_layer": [
            round(float(o.mean()), 4) for o in other],
        "tokens_with_another_set_in_some_layer": float(
            other.any(0).mean()),
        "worst_logit_gap_own_picks": worst(free),
        "worst_logit_gap_with_the_references_picks": worst(held)}),
        flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--cases", nargs="*", default=list(CASES),
                    choices=CASES)
    ap.add_argument("--one", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one:
        return one_case(args.one, args.seed, args.seconds)
    # A process a case, one after another: a chip belongs to one
    # process, and this one never touches JAX.
    worst = 0
    for case in args.cases:
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--seed",
             str(args.seed), "--seconds", str(args.seconds), "--one",
             case]).returncode
        if rc:
            print(json.dumps({"case": case, "seed": args.seed,
                              "exit": rc}), flush=True)
        worst = max(worst, rc)
    return worst


if __name__ == "__main__":
    sys.exit(main())
