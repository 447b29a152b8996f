"""Model FLOP/s utilization: tokens/s/chip times the forward-and-backward
FLOPs a token (perfbench/yardstick.py; recompute not counted) over the
chip's bf16 peak (perfbench/peaks.json)."""

LAYER = "trainer step"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_tok_s_chip"


def read(obs):
    return 100.0 * (obs["end_to_end"]["train_tok_s_chip"]
                    * obs["flops_per_token"]
                    / obs["peaks"]["bf16_flops_per_s"])
