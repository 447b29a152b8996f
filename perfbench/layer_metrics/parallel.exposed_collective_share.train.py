"""Share of the traced window that device 0's core spends in collective
operations: the self time of the synchronous ones and of the `-done`
halves of the asynchronous ones, which last as long as the core waits."""

LAYER = "collectives"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tok_s_chip"


def read(obs):
    t = obs["trace"]
    return 100.0 * t["exposed_collective_s"] / t["window_s"]
