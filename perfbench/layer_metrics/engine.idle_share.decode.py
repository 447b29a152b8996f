"""Share of the traced window in which device 0 is idle while the host is
inside `Engine.step`: the `serving.step` annotation and the five phases
nested in it. With `server.idle_share.decode` and the idle that no
`serving.*` annotation covers it makes `device.idle_share.decode`."""

from perfbench import program_spans

LAYER = "engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"


def read(obs):
    return program_spans.idle_share(obs, program_spans.ENGINE_SPANS)
