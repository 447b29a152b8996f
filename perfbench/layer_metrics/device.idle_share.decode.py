"""Share of the traced window in which no operation ran on the device."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"


def read(obs):
    return 100.0 * obs["trace"]["idle_share"]
