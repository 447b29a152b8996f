"""What the host costs a launch: over the window's step records that
launched (`op` other than `idle`), the mean of `dur_s` less
`phase_s["fetch"]`, the part of a step in which the host waits for the
device. It bounds the cell once the device is fast. None where the
records carry no `phase_s` (`serving/engine.py::Engine._step` writes it)."""

LAYER = "engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_out_tok_s"


def read(obs):
    host = [s["dur_s"] - s["phase_s"]["fetch"]
            for s in obs["engine_steps"]
            if s["op"] != "idle" and "phase_s" in s]
    return 1e3 * sum(host) / len(host) if host else None
