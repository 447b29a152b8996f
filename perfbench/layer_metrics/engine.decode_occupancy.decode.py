"""Sequences in flight over `max_batch`, mean over the decode steps of the
window."""

LAYER = "engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"] if s["op"] == "decode"]
    if not steps:
        return None
    return 100.0 * sum(s["in_flight"] for s in steps) / (
        len(steps) * obs["max_batch"])
