"""Peak bytes of live arrays on the fullest chip (weights, optimizer
state, KV pool, batches): `peak_bytes_in_use` of `memory_stats()`, in GB
of 1e9 bytes, read before the reference check. On this libtpu it leaves
out what the loaded programs hold for their temporaries, which is
`device.reserved_hbm_gb.train`."""

LAYER = "device"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_tok_s_chip"


def read(obs):
    return obs["memory"]["in_use"] / 1e9
