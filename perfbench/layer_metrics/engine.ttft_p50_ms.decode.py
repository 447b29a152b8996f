"""Median arrival-to-first-token on the engine's own clock (`ttft_s` of
the `serving_trace` records, `serving/engine.py::Engine._emit_trace`):
the engine's share of what `server.ttft_p50_ms.decode` reads at the
client. A record is written when its request ends, so the median is over
the requests that ended in or after the window, not those that arrived in
it: in a saturated closed loop whose requests last longer than the window
these are mostly the requests of the load's start (PERF.md section 6,
PR 25), and the client's median, over the requests served in the window,
need not agree with it."""

from perfbench import yardstick

LAYER = "engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_out_tok_s"


def read(obs):
    ttft = [1e3 * r["ttft_s"] for r in obs["serving_traces"]
            if r.get("ttft_s") is not None]
    return yardstick.percentile(ttft, 50) if ttft else None
