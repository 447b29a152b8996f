"""Tokens a held expert sees in one expert-layer call of a decode
iteration: over the window's decode steps, `moe_picks_held` /
`moe_expert_calls` (held experts x expert-layer calls). It says how near the cell's
load is to the deployment's (4 chips x 8 slots x 8 picks over 256
experts: 1 a held expert a call). None where the step records carry no
expert counters."""

LAYER = "expert layer"
UNIT = "tokens/expert"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and s.get("moe_expert_calls")]
    if not steps:
        return None
    return sum(s["moe_picks_held"] for s in steps) / sum(
        s["moe_expert_calls"] for s in steps)
