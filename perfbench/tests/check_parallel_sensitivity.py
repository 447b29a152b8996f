"""What the serving check of the parallel-block expert cell
(``commanda_ep16.serve_rag``) can and cannot see. Run by hand, on the
chip:

    python3 perfbench/tests/check_parallel_sensitivity.py --seed 11 \\
        [--seconds 6] [--cases committed serial_block ...]

``check_serving_sensitivity.py``'s machinery (a process a case, the
cell under its own load through the unedited driver, the harness's own
check and the quantiles of the same gaps) with this cell's cases. The
PROGRAM is tampered with, the reference and the weights it is made from
never:

- ``all_float8`` / ``all_int8``: every matrix rounded to 8 bits where it
  is used (``float8`` is the nearest precision below the cell's
  bfloat16);
- ``rope_off_sliding`` / ``rope_on_full``: no layer rotates / every
  layer does;
- ``serial_block``: the experts (and the router) read a second norm of
  ``x + a W_o`` and not the block's one normed input;
- ``shared_summed``: the four shared experts' outputs summed, not
  averaged;
- ``rms_norm``: RMSNorm (no mean subtracted) in place of LayerNorm,
  every layer and the final norm;
- ``ring_one_page_off``: a window layer's attention reads every ring one
  page (16 rows) round from where the scatter wrote it (the table handed
  to ``paged_attention_chunk`` rolled by a page: the in-kernel masks of
  the flash and the ragged form see the same wrong ring).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench.tests import check_serving_sensitivity as sens  # noqa: E402

CELL = "commanda_ep16.serve_rag"
CASES = ("committed", "all_float8", "all_int8", "rope_off_sliding",
         "rope_on_full", "serial_block", "shared_summed", "rms_norm",
         "ring_one_page_off")


def tamper(case: str, config: dict, setattr_=setattr) -> None:
    """Patch the program for ``case`` through ``setattr_(object, name,
    value)``."""
    import jax.numpy as jnp

    from distributed_training_tpu.models import (experts, parallel_moe,
                                                 window_moe)
    from distributed_training_tpu.serving import blocks

    del config
    if case in ("all_float8", "all_int8"):
        weight = blocks.weight

        def rounded(leaf, dt):
            x = weight(leaf, dt)
            return x if x.ndim < 2 else sens.round_8bit(
                x, case.split("_")[1])
        setattr_(blocks, "weight", rounded)
    elif case in ("rope_off_sliding", "rope_on_full"):
        project = window_moe.project
        setattr_(window_moe, "project",
                 lambda h, a, positions, rope, c, w: project(
                     h, a, positions, case == "rope_on_full", c, w))
    elif case == "serial_block":
        def finish(self, layer, x, attn, valid):
            b = self.block
            attn, _parallel = attn
            x = x + jnp.einsum("...hk,hkd->...d", attn,
                               b._w(layer["attn"]["wo"], x.dtype))
            y, counts = parallel_moe.experts(
                parallel_moe.norm(x, layer["ln1"], b.cfg), layer["mlp"],
                b.cfg, valid, b._w)
            return x + y, counts
        setattr_(parallel_moe._ParallelRun, "finish", finish)
    elif case == "shared_summed":
        setattr_(parallel_moe, "shared_mean",
                 lambda x, m, w, c: experts.gated_mlp(x, m, w,
                                                      c.expert_act))
    elif case == "rms_norm":
        setattr_(parallel_moe, "norm",
                 lambda x, scale, c: experts.rms_norm(x, scale,
                                                      c.layer_norm_eps))
    elif case == "ring_one_page_off":
        attend = window_moe._Run.attend_chunk

        def off_by_a_page(self, layer, q, kp, vp, page_rows, q_pos):
            if self.window:
                page_rows = jnp.roll(page_rows, 1, axis=-1)
            return attend(self, layer, q, kp, vp, page_rows, q_pos)
        setattr_(window_moe._Run, "attend_chunk", off_by_a_page)
    elif case != "committed":
        raise SystemExit(f"no case {case!r}")


def one_case(case: str, seed: int, seconds: float, setattr_=setattr,
             **where) -> int:
    """``sens.one_case`` with this cell's cases (``where``: the CPU
    rehearsal's ``root`` and ``cell``)."""
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
