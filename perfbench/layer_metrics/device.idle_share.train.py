"""Share of the traced window in which no operation ran on the device, mean
over the chips."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tok_s_chip"


def read(obs):
    return 100.0 * obs["trace"]["idle_share"]
