"""What a decode iteration costs the engine: `dur_s` of the window's
`decode` step records over their `iters`, the loop iterations the retired
launch ran (`serving/engine.py::Engine._run_decode_resident`, `1` on the
one-token and speculative cadences). None where the records carry no
`iters` (the parent). Beside it the log has the device's own milliseconds
a launch of `jit_serving_resident_decode` in the traced window over the
records' mean iterations a launch (`perfbench/op_scopes.py`), where the
program writes `program_scopes` records: the two clocks should agree
within a few percent."""

from perfbench import common, op_scopes

LAYER = "engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_out_tok_s"

MODULE = "jit_serving_resident_decode"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and s.get("iters")]
    if not steps:
        return None
    iters = sum(s["iters"] for s in steps)
    value = 1e3 * sum(s["dur_s"] for s in steps) / iters
    device = op_scopes.launch_ms(obs, MODULE)
    if device is not None:
        per_launch = iters / len(steps)
        common.log(f"a decode iteration: {value:.3f} ms by {len(steps)} "
                   f"step records of {per_launch:.2f} iterations, "
                   f"{device / per_launch:.3f} ms on the device "
                   f"({device:.3f} ms a launch of {MODULE})")
    return value
