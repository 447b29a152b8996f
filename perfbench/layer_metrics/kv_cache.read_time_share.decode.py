"""Share of the traced window that device 0 spends moving cached rows to
where attention contracts them: the operations the program puts under
`jax.named_scope("dtt.kv.read")` (page-table lookups and their index
arithmetic, gathers of pages or rows, the layer's slice out of the
carried pool, the re-laying copies; `serving/kv_cache.py::PoolLayer`,
`ops/paged_attention.py`), found through the `program_scopes` records the
engine writes at warm-up (`perfbench/op_scopes.py`). None where the
program writes no such record (the parent), the trace has no `XLA
Modules` line, or the run was not traced."""

from perfbench import op_scopes

LAYER = "KV cache"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

SCOPES = ("dtt.kv.read",)


def read(obs):
    return op_scopes.time_share(obs, SCOPES)
