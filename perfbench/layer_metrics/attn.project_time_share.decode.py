"""Share of the traced window that device 0 spends in attention's
projections: the operations under `jax.named_scope("dtt.attn.project")`
(the layer's norm, the query, key and value products, RoPE;
`serving/engine.py::_scan_layers` around the block's `project`) and
`"dtt.attn.out"` (the output projection and the residual; the blocks'
`finish`). Where the query heads are many times the residual's width
(128 heads of 128 over 4,096: `command-a-plus-ep16`) the two are 4096 <->
16,384 products and a share of their own beside the attention itself
(`attn.core_time_share.decode`). Found through the `program_scopes`
records the engine writes at warm-up (`perfbench/op_scopes.py`). None
where the program writes no such record, the trace has no `XLA Modules`
line, or the run was not traced."""

from perfbench import op_scopes

LAYER = "attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

SCOPES = ("dtt.attn.project", "dtt.attn.out")


def read(obs):
    return op_scopes.time_share(obs, SCOPES)
