"""The benchmark's own arithmetic: peaks, FLOPs per token, quantiles.

Copies, not imports: ``Transformer.flops_per_token`` and
``utils/metrics.TPU_PEAK_FLOPS`` stay where they are in the program, and
a PR that changes them cannot move a number reported here.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The row of ``peaks.json`` for this ``device_kind``. A device
    that is not in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json "
            f"(known: {sorted(table)}); add a row with its source")
    return table[device_kind]


def gpt2_param_count(n_embd: int, n_layer: int, rows: int,
                     n_positions: int) -> int:
    """Parameters the program holds for a GPT-2 shape: tied embedding
    of ``rows`` rows, learned positions, per layer four D x D attention
    matrices, the 4x MLP with biases, two LayerNorms; a final
    LayerNorm."""
    d = n_embd
    per_layer = 4 * d * d + (8 * d * d + 4 * d + d) + 4 * d
    return rows * d + n_positions * d + n_layer * per_layer + 2 * d


def train_flops_per_token(n_embd: int, n_layer: int, rows: int,
                          n_positions: int, seq_len: int) -> float:
    """Forward plus backward FLOPs a token: 6 N for the matrix products
    plus the causal attention term 12 L D S/2 (PaLM appendix B).
    Recomputed operations (remat) are not counted."""
    n = gpt2_param_count(n_embd, n_layer, rows, n_positions)
    return 6.0 * n + 12.0 * n_layer * n_embd * seq_len * 0.5


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default), on a plain list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
