"""What the serving check of the two-latent, learned-selection expert
cell (``dots3_ep8.serve_sparse``) can and cannot see. Run by hand, on
the chip:

    python3 perfbench/tests/check_sparse_sensitivity.py --seed 11 \\
        [--seconds 6] [--cases committed selection_off ...]

``check_serving_sensitivity.py``'s machinery (a process a case, the
cell under its own load through the unedited driver, the harness's own
check and the quantiles of the same gaps) with this cell's cases. The
PROGRAM is tampered with, the reference and the weights it is made from
never:

- ``selection_off``: a full layer's queries attend every earlier
  position (the call made without its selection: dense latent
  attention over the gathered table, a block of queries at a time);
- ``topk_half``: the selection keeps half of ``index_topk``;
- ``window_off``: a window layer's queries see every row of the ring
  (the mask ``rows behind the query < window`` dropped: what 'the
  window ignored' can mean over a ring, which holds no more);
- ``gate_off``: every head's gate 1;
- ``rescale_off``: the two latents not rescaled after their norms;
- ``index_rope_off``: no RoPE on the indexer's queries and keys;
- ``all_int8`` / ``all_float8``: every matrix rounded to 8 bits where
  it is used (``float8`` is the nearest precision below the cell's
  bfloat16).

``--cases selection_flips`` is another kind of case: how many of the
positions the bfloat16 program's indexer keeps for a query the float32
reference's does not, in the first full layer (whose input is the
embedding) over one long random sequence.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import common, run  # noqa: E402
from perfbench.tests import check_serving_sensitivity as sens  # noqa: E402

CELL = "dots3_ep8.serve_sparse"
CASES = ("committed", "selection_off", "topk_half", "window_off",
         "gate_off", "rescale_off", "index_rope_off", "all_int8",
         "all_float8", "selection_flips")


def tamper(case: str, config: dict, setattr_=setattr) -> None:
    """Patch the program for ``case`` through ``setattr_(object, name,
    value)``."""
    from distributed_training_tpu.models import (latent_moe,
                                                 sparse_latent_moe)
    from distributed_training_tpu.ops import paged_attention
    from distributed_training_tpu.serving import blocks

    kw = config["program"]["kwargs"]
    if case in ("selection_off", "topk_half"):
        attend = paged_attention.latent_attention_chunk

        def another_selection(*args, select=None, **more):
            if select is not None:
                select = None if case == "selection_off" else \
                    select._replace(topk=kw["index_topk"] // 2)
            return attend(*args, select=select, **more)
        setattr_(paged_attention, "latent_attention_chunk",
                 another_selection)
    elif case == "window_off":
        visible = paged_attention._visible
        setattr_(paged_attention, "_visible",
                 lambda q_pos, slot_pos, window, ring_slots: visible(
                     q_pos, slot_pos,
                     window if ring_slots is None else ring_slots,
                     ring_slots))
    elif case == "gate_off":
        gate = sparse_latent_moe.head_gate
        setattr_(sparse_latent_moe, "head_gate",
                 lambda h, a, w=None: 0 * gate(h, a) + 1)
    elif case == "rescale_off":
        setattr_(latent_moe, "_scaled", lambda x, scale: x)
    elif case == "index_rope_off":
        # The name ``index_project`` rotates by: the attention's own
        # RoPE is ``latent_moe``'s.
        setattr_(sparse_latent_moe, "rope_interleaved",
                 lambda x, positions, theta: x)
    elif case in ("all_int8", "all_float8"):
        weight = blocks.weight

        def rounded(leaf, dt):
            x = weight(leaf, dt)
            return x if x.ndim < 2 else sens.round_8bit(
                x, case.split("_")[1])
        setattr_(blocks, "weight", rounded)
    elif case != "committed":
        raise SystemExit(f"no case {case!r}")


def selection_flips(config: dict, seed: int, tokens: int = 8192) -> int:
    """One random sequence of ``tokens`` ids through the first full
    layer's indexer on both sides: of the positions the program keeps
    for a query past ``index_topk`` (bfloat16 weights, operands and
    keys, float32 accumulation), the share the reference (float32 of
    the same weights, the key rounded to the cache's dtype) does not
    keep."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_tpu.models import (build_model,
                                                 sparse_latent_moe)
    from distributed_training_tpu.models.experts import rms_norm
    from distributed_training_tpu.models.latent_moe import query_latent
    from distributed_training_tpu.ops import paged_attention as pa

    prog = config["program"]
    model = build_model(prog["build_model"], dtype="bfloat16",
                        **prog["kwargs"])
    c = model.cfg
    tokens = min(tokens, c.max_seq_len)
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)))(
            jax.random.PRNGKey(seed))
    ref = common.load_reference(config)
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, prog["token_vocab"], tokens), jnp.int32)
    layer = jax.tree.map(lambda a: a[0], params["runs"][0])
    pos = jnp.arange(tokens, dtype=jnp.int32)

    @jax.jit
    def program(params, layer):
        x = params["tok_embed"][ids][None].astype(jnp.bfloat16)
        h = rms_norm(x, layer["ln1"], c.rms_norm_eps)
        c_q = query_latent(h, layer["attn"], c.dims(False))
        q, k, w = sparse_latent_moe.index_project(
            h, c_q, layer["index"], pos[None], c)
        back = pos[:, None] - pos[None, :]

        def block(rows):
            sel = pa.Selection(q[:, rows], w[:, rows], None, 0)
            return pa.select_topk(pa.index_scores(sel, k),
                                  (back[rows] >= 0)[None],
                                  min(c.index_topk, tokens))[2][0]
        return ref.in_blocks(block, tokens, 64)

    @jax.jit
    def reference(params, layer):
        with jax.default_matmul_precision("highest"):
            h = ref.rms(ref.f32(params["tok_embed"][ids]),
                        ref.f32(layer["ln1"]))
            c_q = ref.latents(h, layer["attn"])[0]
            return ref.selection(h, c_q, layer["index"], pos)

    mine = np.asarray(program(params, layer))
    theirs = np.asarray(reference(params, layer))
    bound = np.arange(tokens) >= c.index_topk
    kept = mine[bound].sum(-1)
    lost = (mine[bound] & ~theirs[bound]).sum(-1)
    print(json.dumps({
        "case": "selection_flips", "seed": seed, "tokens": tokens,
        "queries_past_topk": int(bound.sum()),
        "kept_a_query": float(kept.mean()) if bound.any() else 0.0,
        "kept_that_the_reference_drops_mean": float(lost.mean())
        if bound.any() else 0.0,
        "kept_that_the_reference_drops_max": int(lost.max())
        if bound.any() else 0,
        "share": float(lost.sum() / max(1, kept.sum()))}), flush=True)
    return 0


def one_case(case: str, seed: int, seconds: float, setattr_=setattr,
             **where) -> int:
    """``sens.one_case`` with this cell's cases (``where``: the CPU
    rehearsal's ``root`` and ``cell``)."""
    if case == "selection_flips":
        root = where.get("root", common.ROOT)
        cell = run.find_cell(run.load_json(root, "BENCHMARK.json"),
                             where.get("cell", CELL))
        run.require_device(cell["chips"])
        run.setup_jax()
        return selection_flips(
            run.load_json(root, "perfbench", "configs",
                          cell["config"] + ".json"), seed)
    setattr_(sens, "tamper", tamper)
    return sens.one_case(case, seed, seconds, setattr_=setattr_,
                         **{"cell": CELL, **where})


def main(argv=None) -> int:
    import argparse
    import json
    import subprocess

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--cases", nargs="*", default=list(CASES),
                    choices=CASES)
    ap.add_argument("--one", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one:
        return one_case(args.one, args.seed, args.seconds)
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
