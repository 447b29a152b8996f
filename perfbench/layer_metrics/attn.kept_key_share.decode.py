"""What share of a full layer's cache is read after the selection: over
the window's decode step records, `index_keys_kept` (positions attended
after the selection) over `index_keys_scored` (visible positions whose
index key was scored), both summed in the program over full layers,
slots and iterations. 1 while no sequence is longer than `index_topk`.
None where the records carry no such counters."""

LAYER = "attention"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and s.get("index_keys_scored")]
    if not steps:
        return None
    return sum(s["index_keys_kept"] for s in steps) / sum(
        s["index_keys_scored"] for s in steps)
