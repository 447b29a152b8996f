"""Share of the traced window that device 0 spends in the expert layers:
the operations under `jax.named_scope("dtt.moe.route")` (router product,
top-k, gates, the counters of what was chosen) and `"dtt.moe.experts"`
(the held experts' products, the shared expert, the combine, with the
layer's norm and residual; `models/experts.py::expert_layer`, the blocks'
`finish`), found through the `program_scopes` records the engine writes
at warm-up (`perfbench/op_scopes.py`). None where no program of the run
has such an operation (a model without experts; the parent, which writes
no record), the trace has no `XLA Modules` line, or the run was not
traced."""

from perfbench import op_scopes

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

SCOPES = ("dtt.moe.route", "dtt.moe.experts")


def read(obs):
    return op_scopes.time_share(obs, SCOPES)
