"""Device-to-host hand-overs (`host_syncs` of the step records) per output
token streamed in the window. The output tokens are counted on the
client's side: a prefill step's `tokens` are prompt tokens, and the first
token it hands each completed prompt is in no record."""

LAYER = "engine scheduler"
UNIT = "syncs/token"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    lo, hi = obs["window"]
    out = sum(1 for r in obs["requests"] for t in r["times"]
              if lo <= t <= hi)
    if not obs["engine_steps"] or not out:
        return None
    return sum(s["host_syncs"] for s in obs["engine_steps"]) / out
