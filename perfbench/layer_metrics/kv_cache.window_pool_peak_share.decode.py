"""Most pages of the WINDOW layers' pool in use over its pages usable
(`pages_used_window` / `pages_total_window` of the step records) in the
window: rings a slot, so it reads how many slots held a whole ring.
None where the records do not tell the pools apart (a model without
window layers)."""

LAYER = "KV cache"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s.get("pages_total_window")]
    if not steps:
        return None
    return 100.0 * max(s["pages_used_window"] / s["pages_total_window"]
                       for s in steps)
