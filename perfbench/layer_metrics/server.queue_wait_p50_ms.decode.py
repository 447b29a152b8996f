"""Median arrival-to-admission wait (`queue_wait_s` of the `serving_trace`
records) of the requests that ended after the window opened."""

from perfbench import yardstick

LAYER = "server"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_out_tok_s"


def read(obs):
    waits = [1e3 * r["queue_wait_s"] for r in obs["serving_traces"]
             if r.get("queue_wait_s") is not None]
    return yardstick.percentile(waits, 50) if waits else None
