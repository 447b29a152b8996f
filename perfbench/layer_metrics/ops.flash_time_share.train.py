"""Share of the traced window that device 0 spends in the flash attention
kernels: self time of the `tpu_custom_call`s that `ops/flash_attention.py`
names (`dtt_flash_fwd`, `dtt_flash_bwd_fused`, `dtt_flash_bwd_dq`,
`dtt_flash_bwd_dkv`). A forward kernel that remat runs again in the
backward counts as forward. None where no operation bears such a name: a
kernel that cannot be found is not a kernel that took no time."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tok_s_chip"

PATTERN = re.compile(
    r"^dtt_flash_(fwd|bwd_\w+)\.\d+ custom-call:tpu_custom_call$")


def read(obs):
    t = obs["trace"]
    found = [s for name, s in t["op_self_s"].items()
             if PATTERN.match(name)]
    if not found:
        return None
    return 100.0 * sum(found) / t["window_s"]
