"""Share of the traced window that device 0 spends in the shared experts:
the operations under `jax.named_scope("dtt.moe.shared")` (the one gated
product over the shared experts stacked on the hidden axis and its scale
by one over their number; `models/parallel_moe.py::shared_mean`, inside
`models/experts.py::expert_layer`'s `dtt.moe.experts`: the innermost scope
an instruction lies in is its scope, so this time is NOT in
`moe.experts_time_share.decode` for a model that names it), found through
the `program_scopes` records the engine writes at warm-up
(`perfbench/op_scopes.py`). None where no program of the run has such an
operation (a model whose shared expert stays under `dtt.moe.experts`; the
parent, whose vocabulary lacks the scope), the trace has no `XLA Modules`
line, or the run was not traced."""

from perfbench import op_scopes

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

SCOPES = ("dtt.moe.shared",)


def read(obs):
    return op_scopes.time_share(obs, SCOPES)
