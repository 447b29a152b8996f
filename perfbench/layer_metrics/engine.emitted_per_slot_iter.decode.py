"""Tokens a slot emits per decode iteration it is live in: the decode step
records' `tokens` over their `slot_iters`, both counted where the burst is
unpacked (`serving/engine.py::Engine._run_decode*`). At most `spec_k`; 1.0
exactly with `spec_k` 1. It replaces the derivation in
`engine.spec_accepted_mean.decode`, which reads `in_flight` when the
record is written, not when the burst was packed."""

LAYER = "engine scheduler"
UNIT = "tokens/iteration"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and s.get("slot_iters")]
    if not steps:
        return None
    return (sum(s["tokens"] for s in steps)
            / sum(s["slot_iters"] for s in steps))
