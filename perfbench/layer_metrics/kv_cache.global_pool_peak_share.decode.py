"""Most pages of the GLOBAL layers' pool in use over its pages usable
(`pages_used_global` / `pages_total_global` of the step records) in the
window: tables that grow with their sequences, so it reads how near the
traffic came to the worst case the pool is sized for. None where the
records do not tell the pools apart (a model without window layers)."""

LAYER = "KV cache"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s.get("pages_total_global")]
    if not steps:
        return None
    return 100.0 * max(s["pages_used_global"] / s["pages_total_global"]
                       for s in steps)
