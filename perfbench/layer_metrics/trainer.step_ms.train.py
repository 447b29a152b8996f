"""Mean time of a step: the window between two step ends over the steps in
it."""

LAYER = "trainer step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "train_tok_s_chip"


def read(obs):
    return 1e3 * obs["train_elapsed_s"] / obs["train_steps"]
