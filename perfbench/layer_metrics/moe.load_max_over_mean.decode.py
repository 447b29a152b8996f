"""How unevenly the router loads the experts held here: over the
window's decode steps, `moe_load_max` (the largest count on one held
expert, an expert-layer call, summed in the program) over the mean
count a held expert a call, `moe_picks_held / moe_expert_calls`, taken
over the same calls. 1 is an even load; the slowest expert sets the
time of a grouped product. None where the step records carry no expert
counters (a model without experts, or a program that does not count)."""

LAYER = "expert layer"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and s.get("moe_picks_held")]
    if not steps:
        return None
    mean = sum(s["moe_picks_held"] * s["moe_layer_calls"]
               / s["moe_expert_calls"] for s in steps)
    return sum(s["moe_load_max"] for s in steps) / mean
