"""The share of the engine's stepping time that went to prompts: `dur_s`
of the window's prefill steps over `dur_s` of all its steps that
launched. Output tokens come from the rest."""

LAYER = "engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"] if s["op"] != "idle"]
    if not steps:
        return None
    return 100.0 * sum(s["dur_s"] for s in steps
                       if s["op"] == "prefill") / sum(
        s["dur_s"] for s in steps)
