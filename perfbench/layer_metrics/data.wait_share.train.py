"""Share of the window the loop spent waiting in the loader's `next`."""

LAYER = "input pipeline"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "train_tok_s_chip"


def read(obs):
    return 100.0 * obs["train_wait_s"] / obs["train_elapsed_s"]
