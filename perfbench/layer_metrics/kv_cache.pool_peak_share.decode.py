"""Most pages in use over pages usable (`pages_used` / `pages_total` of the
step records) in the window."""

LAYER = "KV cache"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = obs["engine_steps"]
    if not steps:
        return None
    return 100.0 * max(s["pages_used"] / s["pages_total"] for s in steps)
