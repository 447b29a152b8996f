"""How much of the decode traffic the window acts on: over the window's
decode step records, `window_bound_iters` (slot-iterations whose
sequence was longer than the attention window, counted in the program
and fetched with the tokens) over `slot_iters`. None where the records
carry no such counter (a model without window layers, or a program that
does not count)."""

LAYER = "KV cache"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and "window_bound_iters" in s
             and s.get("slot_iters")]
    if not steps:
        return None
    return (100.0 * sum(s["window_bound_iters"] for s in steps)
            / sum(s["slot_iters"] for s in steps))
