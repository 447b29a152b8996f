"""Share of the traced window that device 0 spends in the kernel of a
decode iteration's paged attention: self time of the `tpu_custom_call`s
that `ops/paged_attention.py::_ragged_attention` names
`dtt_paged_decode` (the ragged form: a sequence's live pages walked
where they lie in the carried pool, a DMA a page, softmax kept online).
None where no operation bears the name (a program from before the
kernel, or an engine whose shapes keep another form): a kernel that
cannot be found is not a kernel that took no time."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"

PATTERN = re.compile(
    r"^dtt_paged_decode\.\d+ custom-call:tpu_custom_call$")


def read(obs):
    t = obs["trace"]
    found = [s for name, s in t["op_self_s"].items()
             if PATTERN.match(name)]
    if not found:
        return None
    return 100.0 * sum(found) / t["window_s"]
