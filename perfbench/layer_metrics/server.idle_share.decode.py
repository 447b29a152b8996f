"""Share of the traced window in which device 0 is idle while the host is
in the server's loop outside `Engine.step`: the `serving.control`,
`serving.mailbox`, `serving.dispatch` and `serving.idle_sleep` annotations
of `ServingServer._engine_loop_inner`."""

from perfbench import program_spans

LAYER = "server"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tok_s"


def read(obs):
    return program_spans.idle_share(obs, program_spans.SERVER_SPANS)
