"""What the serving check of the window-and-global expert cell
(``smallthinker_ep4.serve_long``) can and cannot see. Run by hand, on
the chip:

    python3 perfbench/tests/check_window_sensitivity.py --seed 11 \\
        [--seconds 6] [--cases committed window_mask_off ...]

``check_serving_sensitivity.py``'s machinery (a process a case, the
cell under its own load through the unedited driver, the harness's own
check and the quantiles of the same gaps) with this cell's cases. The
PROGRAM is tampered with, the reference and the weights it is made from
never:

- ``window_mask_off``: a window layer's queries see every row of the
  ring (the mask ``rows behind the query < window`` dropped);
- ``rope_on_global`` / ``rope_off_window``: every layer rotates / none;
- ``ring_one_page_wrong``: the mask takes every ring slot for one page
  (16 rows) later than where the scatter wrote it;
- ``one_layers_experts_out``: the held experts of layer 2 (a window
  layer) contribute nothing, in the engine's copy of the parameters;
- ``experts_int8`` / ``all_int8`` / ``all_float8``: weights rounded to
  8 bits where they are used (the held experts' alone, or every
  matrix; ``float8`` is the nearest precision below the cell's
  bfloat16).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench.tests import check_serving_sensitivity as sens  # noqa: E402

CELL = "smallthinker_ep4.serve_long"
CASES = ("committed", "window_mask_off", "rope_on_global",
         "rope_off_window", "ring_one_page_wrong",
         "one_layers_experts_out", "experts_int8", "all_int8",
         "all_float8")


def tamper(case: str, config: dict, setattr_=setattr) -> None:
    """Patch the program for ``case`` through ``setattr_(object, name,
    value)``."""
    from distributed_training_tpu.models import window_moe
    from distributed_training_tpu.ops import paged_attention
    from distributed_training_tpu.serving import blocks, engine

    kw = config["program"]["kwargs"]
    if case == "window_mask_off":
        visible = paged_attention._visible
        setattr_(paged_attention, "_visible",
                 lambda q_pos, slot_pos, window, ring_slots: visible(
                     q_pos, slot_pos,
                     window if ring_slots is None else ring_slots,
                     ring_slots))
    elif case == "ring_one_page_wrong":
        visible = paged_attention._visible
        page = config["serving"]["engine"]["page_size"]
        setattr_(paged_attention, "_visible",
                 lambda q_pos, slot_pos, window, ring_slots: visible(
                     q_pos, slot_pos if ring_slots is None
                     else (slot_pos + page) % ring_slots, window,
                     ring_slots))
    elif case in ("rope_on_global", "rope_off_window"):
        project = window_moe.project
        setattr_(window_moe, "project",
                 lambda h, a, positions, rope, c, w: project(
                     h, a, positions, case == "rope_on_global", c, w))
    elif case == "one_layers_experts_out":
        # The engine alone gets the copy: the harness keeps its own.
        real = engine.Engine

        def engine_with_a_layer_emptied(model, params, cfg):
            runs = list(params["runs"])
            mlp = runs[1]["mlp"]
            runs[1] = {**runs[1], "mlp": {
                **mlp, "wd": mlp["wd"].at[1].set(0)}}
            return real(model, {**params, "runs": tuple(runs)}, cfg)
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
            return sens.round_8bit(x, case.split("_")[1])
        setattr_(blocks, "weight", rounded)
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
