"""What a prompt chunk costs the engine: the mean `dur_s` of the window's
`prefill` step records (a record's `dur_s` runs from the retire before to
its own, so with the device never idle and every launch run ahead it is
the launch's device time). Beside it the log has the device's own
milliseconds a launch of `jit_serving_prefill_batch` in the traced window
(`perfbench/op_scopes.py`), where the program writes `program_scopes`
records: the two clocks should agree within a few percent."""

from perfbench import common, op_scopes

LAYER = "engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_out_tok_s"

MODULE = "jit_serving_prefill_batch"


def read(obs):
    steps = [s for s in obs["engine_steps"] if s["op"] == "prefill"]
    if not steps:
        return None
    value = 1e3 * sum(s["dur_s"] for s in steps) / len(steps)
    device = op_scopes.launch_ms(obs, MODULE)
    if device is not None:
        common.log(f"a prompt chunk: {value:.3f} ms by {len(steps)} step "
                   f"records, {device:.3f} ms on the device a launch of "
                   f"{MODULE}")
    return value
