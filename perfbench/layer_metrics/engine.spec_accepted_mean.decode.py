"""Tokens a slot emits per speculative chunk of `spec_k`. Host-driven
cadence: `spec_accepted_mean` of the step records. Device-resident
cadence, which does not report it: a decode step's `tokens` over its
loop iterations (`resident_steps_per_launch`) times the sequences in
flight, which counts a slot that stopped early in the burst as running,
so it reads a little low."""

LAYER = "engine scheduler"
UNIT = "tokens/launch"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"] if s["op"] == "decode"]
    vals = [s["spec_accepted_mean"] for s in steps
            if "spec_accepted_mean" in s]
    if vals:
        return sum(vals) / len(vals)
    chunks = sum(s["resident_steps_per_launch"] * s["in_flight"]
                 for s in steps if "resident_steps_per_launch" in s)
    if not chunks:
        return None
    return sum(s["tokens"] for s in steps
               if "resident_steps_per_launch" in s) / chunks
