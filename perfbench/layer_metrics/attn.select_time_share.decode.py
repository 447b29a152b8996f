"""Share of the traced window that device 0 spends choosing what a query
attends: the operations under `jax.named_scope("dtt.attn.select")` (the
indexer's scores over the index keys, the exact top-k's sorts, the
selection's mask and its `cumsum`; `ops/paged_attention.py::
latent_attention_chunk`'s `choose`), found through the `program_scopes`
records the engine writes at warm-up (`perfbench/op_scopes.py`). None
where no program of the run has such an operation (an engine without a
selection; the parent, which writes no record), the trace has no `XLA
Modules` line, or the run was not traced."""

from perfbench import op_scopes

LAYER = "attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

SCOPES = ("dtt.attn.select",)


def read(obs):
    return op_scopes.time_share(obs, SCOPES)
