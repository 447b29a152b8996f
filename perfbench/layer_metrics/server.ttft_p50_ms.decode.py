"""Median time from a request's send to its first streamed token, on the
client's clock, over the requests sent in the window."""

from perfbench import yardstick

LAYER = "server"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "serve_out_tok_s"


def read(obs):
    lo, hi = obs["window"]
    ttft = [1e3 * (r["times"][0] - r["sent"]) for r in obs["requests"]
            if r["times"] and lo <= r["sent"] <= hi]
    return yardstick.percentile(ttft, 50) if ttft else None
