"""Peak bytes the loaded programs reserved for their temporaries on the
fullest chip (activations of a training step, the engine programs'
re-laid-out copy of the KV pool): `peak_bytes_reserved` of
`memory_stats()`, in GB of 1e9 bytes, read before the reference check.
Beside `device.peak_hbm_gb.decode`, never inside it."""

LAYER = "device"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    return obs["memory"]["reserved"] / 1e9
