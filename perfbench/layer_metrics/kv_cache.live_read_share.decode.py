"""What share of its tables a decode iteration reads: over the window's
decode step records, `kv_pages_walked` (the pages the ragged form's
kernel walked: of every live slot, iteration and layer the pages that
hold a position its query sees) over `kv_pages_tabled` (the pages the
gather form copies for the same calls: every slot's whole table row or
ring, a layer), both counted by the engine at a launch's retire from
the positions it holds. 1 where every table is full and every slot
live; how often the mechanism saves, and how much. None where the
records carry no such counters (a program that reads no layer in the
ragged form, or one from before it)."""

LAYER = "KV cache"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and s.get("kv_pages_tabled")]
    if not steps:
        return None
    return sum(s["kv_pages_walked"] for s in steps) / sum(
        s["kv_pages_tabled"] for s in steps)
