"""The one general traffic generator: a traffic file's parameters and
``--seed`` in, a plan of requests out.

Every seed gets the SAME multiset of lengths and arrival gaps (drawn
from the file's ``set_seed``) in another order, so that runs with
different seeds do the same amount of work; what the seed changes is the
order, the token ids and the weights. Nothing is drawn from the clock.
"""

from __future__ import annotations

import math

import numpy as np


def draw_lengths(rng, n: int, spec: dict) -> np.ndarray:
    """``n`` whole lengths from ``spec``: ``lognormal`` (median, sigma)
    or ``fixed`` (value), clipped to [min, max]."""
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def draw_gaps(rng, n: int, rate: float, spec: dict) -> np.ndarray:
    """``n`` inter-arrival gaps with mean ``1 / rate``: ``poisson``
    (exponential gaps)."""
    if spec["dist"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    raise ValueError(f"unknown arrival distribution {spec['dist']!r}")


def _sizes(base, n: int, t: dict):
    prompt = draw_lengths(base, n, t["prompt"])
    output = draw_lengths(base, n, t["output"])
    return prompt, np.minimum(output, t["max_total"] - prompt)


def _request(rng, rid: str, prompt_len: int, out_len: int,
             vocab: int) -> dict:
    return {"id": rid, "max_new_tokens": int(out_len),
            "prompt_ids": rng.integers(0, vocab, int(prompt_len)).tolist()}


def closed_plan(t: dict, seed: int, vocab: int) -> dict:
    """``clients`` lanes of ``requests_per_client`` requests each. The
    first request of a lane asks for a fixed fraction of its output
    budget, so that the slots do not all finish together. The seed
    deals the lanes to the clients in another order."""
    base = np.random.default_rng(t["set_seed"])
    c, r = t["clients"], t["requests_per_client"]
    prompt, output = _sizes(base, c * r, t)
    prompt, output = prompt.reshape(c, r), output.reshape(c, r)
    f = t["first_fraction"]
    frac = base.uniform(f["min"], f["max"], c)
    output[:, 0] = np.maximum(2, np.ceil(output[:, 0] * frac)).astype(int)
    rng = np.random.default_rng(seed)
    order = rng.permutation(c)
    lanes = [[_request(rng, f"c{k}r{j}", prompt[lane, j], output[lane, j],
                       vocab) for j in range(r)]
             for k, lane in enumerate(order)]
    return {"loop": "closed", "lanes": lanes}


def open_plan(t: dict, seed: int, vocab: int, horizon_s: float) -> dict:
    """Arrivals at ``rate_per_s`` for ``horizon_s`` seconds: a fixed
    multiset of gaps and of lengths, each dealt in an order of the
    seed's. ``due_s`` counts from the start of the load."""
    base = np.random.default_rng(t["set_seed"])
    n = int(math.ceil(t["rate_per_s"] * horizon_s))
    gaps = draw_gaps(base, n, t["rate_per_s"], t["arrivals"])
    prompt, output = _sizes(base, n, t)
    rng = np.random.default_rng(seed)
    due = np.cumsum(gaps[rng.permutation(n)])
    sizes = rng.permutation(n)
    reqs = []
    for i in range(n):
        req = _request(rng, f"o{i}", prompt[sizes[i]], output[sizes[i]],
                       vocab)
        req["due_s"] = float(due[i])
        reqs.append(req)
    return {"loop": "open", "requests": reqs}
