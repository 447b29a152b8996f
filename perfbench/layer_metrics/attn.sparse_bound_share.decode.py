"""How much of the decode traffic the learned selection acts on: over
the window's decode step records, `sparse_bound_iters` (slot-iterations
whose sequence was longer than the `index_topk` positions the selection
keeps, counted in the program and fetched with the tokens) over
`slot_iters`. None where the records carry no such counter (a model
without an indexer, or a program that does not count)."""

LAYER = "attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    steps = [s for s in obs["engine_steps"]
             if s["op"] == "decode" and "sparse_bound_iters" in s
             and s.get("slot_iters")]
    if not steps:
        return None
    return (100.0 * sum(s["sparse_bound_iters"] for s in steps)
            / sum(s["slot_iters"] for s in steps))
