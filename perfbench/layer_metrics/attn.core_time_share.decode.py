"""Share of the traced window that device 0 spends in attention itself:
the operations under `jax.named_scope("dtt.attn.core")` (logits, mask,
softmax, weighted sum, the latent up-projections in either form, the two
Pallas kernels `dtt_paged_prefill` and `dtt_sparse_prefill`;
`serving/engine.py::_scan_layers` around the block's `attend_chunk`, less
what `ops/paged_attention.py` names `dtt.kv.read` and `dtt.attn.select`),
found through the `program_scopes` records the engine writes at warm-up
(`perfbench/op_scopes.py`). None where the program writes no such record
(the parent), the trace has no `XLA Modules` line, or the run was not
traced."""

from perfbench import op_scopes

LAYER = "attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

SCOPES = ("dtt.attn.core",)


def read(obs):
    return op_scopes.time_share(obs, SCOPES)
