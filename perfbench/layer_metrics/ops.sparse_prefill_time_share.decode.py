"""Share of the traced window that device 0 spends in the kernel of a
prompt chunk's attention under a learned selection: self time of the
`tpu_custom_call`s that `ops/paged_attention.py::_sparse_flash_attention`
names `dtt_sparse_prefill` (the masked form: a chunk's queries against
the sequence's table read once, the selection a mask, softmax kept
online). None where no operation bears the name (a program from before
the kernel, or an engine with no selection or whose shapes keep the
gather form): a kernel that cannot be found is not a kernel that took no
time."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

PATTERN = re.compile(
    r"^dtt_sparse_prefill\.\d+ custom-call:tpu_custom_call$")


def read(obs):
    t = obs["trace"]
    found = [s for name, s in t["op_self_s"].items()
             if PATTERN.match(name)]
    if not found:
        return None
    return 100.0 * sum(found) / t["window_s"]
