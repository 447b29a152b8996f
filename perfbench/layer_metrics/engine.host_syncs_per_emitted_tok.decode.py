"""Device-to-host hand-overs per token the engine emitted in the window,
all from its step records: `host_syncs` over the decode steps' `tokens`
plus the prefill steps' `first_tokens` (a prefill step's `tokens` are
prompt tokens). None where no record carries `first_tokens` or
`slot_iters`, the marks of a program that counts emitted tokens."""

LAYER = "engine scheduler"
UNIT = "syncs/token"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = obs["engine_steps"]
    if not any("first_tokens" in s or "slot_iters" in s for s in steps):
        return None
    emitted = (sum(s["tokens"] for s in steps if s["op"] == "decode")
               + sum(s.get("first_tokens", 0) for s in steps))
    if not emitted:
        return None
    return sum(s["host_syncs"] for s in steps) / emitted
