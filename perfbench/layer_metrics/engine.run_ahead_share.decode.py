"""How often the engine's run-ahead engages: of the window's step records
that launched (`op` other than `idle`), the share whose launch was
dispatched while the launch before it was still un-retired (`ran_ahead`
1, written in `serving/engine.py::Engine._retire`). At 100% the device
always has the next launch queued when one ends, and the host's
bookkeeping of a launch runs beside the next launch; at 0% every launch
waits for the fetch and the emit of the one before. None where the
records carry no `ran_ahead` (a program that retires every launch in the
step that dispatched it says nothing)."""

LAYER = "engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tok_s"


def read(obs):
    ran = [s["ran_ahead"] for s in obs["engine_steps"]
           if s["op"] != "idle" and "ran_ahead" in s]
    return 100.0 * sum(ran) / len(ran) if ran else None
