"""Median wait in the server's mailbox: arrival (stamped by the HTTP
thread) to `Engine.submit` on the engine thread, the `submitted` event of
a `serving_trace` record's `spans`. `server.queue_wait_p50_ms.decode`
less this is the wait in the engine's own queue, over the same requests
(those that ended in or after the window). None where no record has
the event (`serving/engine.py::Engine._mark_admitted` writes it)."""

from perfbench import yardstick

LAYER = "server"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_out_tok_s"


def read(obs):
    waits = [1e3 * s["t"] for r in obs["serving_traces"]
             for s in r.get("spans") or [] if s.get("ev") == "submitted"]
    return yardstick.percentile(waits, 50) if waits else None
