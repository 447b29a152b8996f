"""Rows of expert products a prompt chunk computes for each pick that
lands on a held expert: over the window's prefill step records that
carry the expert counters, `moe_rows_computed` / `moe_picks_held`. A
prefill launch fetches its counts only where it ends some prompt
(`serving/engine.py::Engine._launch(fetch=...)`), so these are the
records of such launches. The dense form computes every held expert over
every row (10 to 32 rows a pick at the four expert cells' chunks); the
grouped form the rows of the tiles its kernel visits
(`models/experts.py::expert_layer`). None where the records carry no
`moe_rows_computed` (a program without it) or no expert picks."""

LAYER = "expert layer"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "prefill" and s.get("moe_rows_computed")
             and s.get("moe_picks_held")]
    if not steps:
        return None
    return sum(s["moe_rows_computed"] for s in steps) / sum(
        s["moe_picks_held"] for s in steps)
