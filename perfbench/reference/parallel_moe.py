"""Plain reference for the Command A+ layout (``cohere2_moe``): a
parallel block (attention and the expert layer read one LayerNorm of
the residual and are both added to it), sliding-window RoPE layers and
full-attention layers with no positions, grouped-query attention,
sigmoid-scored top-k experts held in part beside shared experts whose
outputs are averaged, a tied head. Forward pass and next-token loss in
float32.

Written from the published configuration
(huggingface.co/CohereLabs/command-a-plus-05-2026, config.json) and its
description, with no kernels, no cache, no ring, no batching and nothing
imported from the program under test. Every matrix product runs under
``jax.default_matmul_precision("highest")`` so a TPU does not quietly
compute it in bfloat16.

Decoder layer ``l`` on ``x`` (S, D), with ``ln`` = LayerNorm (mean
subtracted, scale only, ``LAYER_NORM_EPS``), no biases, no norm on
queries or keys:

    h = ln(x)
    q, k, v = h W_q, h W_k, h W_v                H / Hkv heads of HEAD_DIM
    q, k = rope(q), rope(k)                      where LAYER_TYPES[l] is
                                                 "sliding_attention"; else
                                                 no positions at all
    p = softmax(q . k / sqrt(HEAD_DIM) + mask)   query head i uses kv head
                                                 i // (H / Hkv); mask: key j
                                                 <= query i, and on sliding
                                                 layers also i - j < WINDOW
    s = sigmoid(h W_r)                           all experts' scores
    chosen = the TOP_K largest of s;  g_e = s_e / sum of the chosen s
    x = x + concat_h(p v) W_o
          + sum_{e chosen and held} g_e W_down[e](silu(h W_gate[e]) * (h W_up[e]))
          + 1/N sum_{j < N} S_down[j](silu(h S_gate[j]) * (h S_up[j]))

where the last line's three terms all read the SAME ``h`` and ``N`` =
the shared experts, whose outputs are averaged and the mean added (not
averaged with the routed sum). After the last layer ``LOGIT_SCALE *
ln(x) E^T`` with ``E`` the embedding's rows (tied).

``rope`` turns the ADJACENT pair ``(x[2i], x[2i+1])`` by ``pos *
ROPE_THETA^(-2i/d)`` (``rope_gptj``), over all of a head (``rotary_pct``
1).

Departures from the published description, each the configuration
file's (``assumed``, ``reduced``): one expert's width is
``intermediate_size``; the router reads ``h``; the window counts the
query itself; the vision tower is not here (text only).

**Experts and rows held.** The reference is given the same share of
each layer as the program: the routed experts' arrays' leading axis is
the experts held, global experts ``EP_RANK * held ... (EP_RANK + 1) *
held - 1``; it scores all of them (the router is whole), normalises the
gates over all chosen, and leaves out what the absent experts would
add, as the program does. The shared experts are whole on every chip.
The embedding has the rows the program holds.

Memory, at the published widths and 8,192 positions on one 16 GB chip
beside the program's own weights: ``from_program`` keeps every matrix in
the dtype it is stored in (the program's bfloat16: a second copy of the
weights, not a float32 one of twice the size) and ``wide`` makes a
matrix float32 where it is used, which is exact. Attention runs a kv
head with its sixteen query heads at a time and of those ``Q_BLOCK``
queries at a time, their projection and the output projection inside
the block (all heads' queries are 16,384 floats a position, a kv head's
2,048; a block's float32 scores are ``16 x Q_BLOCK x S``); the experts
run one at a time, routed and shared alike (a shared expert is then a
matrix of the routed experts' size, not four times it), and the head
``V_BLOCK`` rows of the embedding at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# The configuration's numbers the harness does not hand over
# (``logits`` gets ``n_head`` and nothing else). Held to
# ``perfbench/configs/command-a-plus-ep16.json`` by
# ``tests/test_parallel_moe.py``; the tiny-size tests set others.
N_KV_HEAD = 8
HEAD_DIM = 128
WINDOW = 4096
LAYER_TYPES = ("sliding_attention",) * 3 + ("full_attention",)
ROPE_THETA = 50000.0
LAYER_NORM_EPS = 1e-5
NUM_EXPERTS_PER_TOK = 8
NUM_SHARED_EXPERTS = 4
LOGIT_SCALE = 1.0
EP_RANK = 0
Q_BLOCK = 256
V_BLOCK = 4096


def wide(a):
    return a.astype(jnp.float32)


def ln(x, scale):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LAYER_NORM_EPS) * scale


def rope(x, pos):
    """``x`` (S, ..., d) at positions ``pos`` (S,): the pair ``(x[2i],
    x[2i+1])`` turned by ``pos * ROPE_THETA^(-2i/d)``."""
    d = x.shape[-1]
    inv = ROPE_THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * inv
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     -1).reshape(x.shape)


def attention(h, p, n_head, windowed, rotated):
    """A kv head and the ``n_head / N_KV_HEAD`` query heads that use it
    at a time (query head ``n * group + g`` uses kv head ``n``), their
    part of the output projection added up over the kv heads."""
    S, D = h.shape
    group = n_head // N_KV_HEAD
    pos = jnp.arange(S)
    # Q_BLOCK queries at a time; the last block is padded with zero
    # rows, whose outputs are cut off again.
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    padded = jnp.pad(h, ((0, nb * qb - S), (0, 0)))

    def kv_head(y, w):
        w_q, w_k, w_v, w_o = (wide(a) for a in w)
        k, v = h @ w_k, h @ w_v
        if rotated:
            k = rope(k, pos)

        def block(i):
            rows = i * qb + jnp.arange(qb)
            q = (padded[rows] @ w_q).reshape(qb, group, HEAD_DIM)
            if rotated:
                q = rope(q, rows)
            back = rows[:, None] - pos[None, :]   # keys behind the query
            seen = back >= 0
            if windowed:
                seen &= back < WINDOW
            scores = jnp.einsum("qgd,kd->gqk", q, k) / math.sqrt(HEAD_DIM)
            scores = jnp.where(seen, scores, -jnp.inf)
            out = jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, -1), v)
            return out.reshape(qb, group * HEAD_DIM) @ w_o

        return y + jax.lax.map(block, jnp.arange(nb)).reshape(
            nb * qb, D)[:S], None

    def by_kv_head(w, width):
        return w.reshape(D, N_KV_HEAD, width).transpose(1, 0, 2)

    y, _ = jax.lax.scan(kv_head, jnp.zeros_like(h), (
        by_kv_head(p["w_q"], group * HEAD_DIM),
        by_kv_head(p["w_k"], HEAD_DIM), by_kv_head(p["w_v"], HEAD_DIM),
        p["w_o"].reshape(N_KV_HEAD, group * HEAD_DIM, D)))
    return y


def gates(s):
    """(S, all experts) sigmoid scores -> each chosen expert's score
    over the sum of the chosen scores, 0 elsewhere."""
    order = jnp.argsort(-s, axis=-1)[:, :NUM_EXPERTS_PER_TOK]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    g = jnp.where(chosen, s, 0.0)
    return g / g.sum(-1, keepdims=True)


def gated_sum(h, w_gate, w_up, w_down, weight):
    """``sum_e weight[e] W_down[e](silu(h W_gate[e]) * (h W_up[e]))``,
    an expert at a time; ``weight`` (experts, S)."""
    def one(y, e):
        g, u, d, w_e = e
        out = (jax.nn.silu(h @ wide(g)) * (h @ wide(u))) @ wide(d)
        return y + w_e[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w_gate, w_up, w_down, weight))
    return y


def experts(h, p):
    """The held experts' part of the routed sum plus the mean of the
    shared experts, both of ``h``."""
    held = p["e_gate"].shape[0]
    g = jax.lax.dynamic_slice_in_dim(
        gates(jax.nn.sigmoid(h @ wide(p["w_r"]))), EP_RANK * held, held, 1)
    routed = gated_sum(h, p["e_gate"], p["e_up"], p["e_down"], g.T)
    n = p["s_gate"].shape[0]
    mean = jnp.full((n, h.shape[0]), 1.0 / n, jnp.float32)
    return routed + gated_sum(h, p["s_gate"], p["s_up"], p["s_down"],
                              mean)


def block(x, p, n_head, windowed, rotated):
    h = ln(x, p["ln"])
    return x + attention(h, p, n_head, windowed, rotated) + experts(h, p)


def head(x, embed):
    """``x E^T``, ``V_BLOCK`` rows of ``E`` at a time where they divide
    its rows, written into the one (S, V) result."""
    S, V = x.shape[0], embed.shape[0]
    vb = V_BLOCK if V % V_BLOCK == 0 else V

    def some_rows(i, out):
        rows = jax.lax.dynamic_slice_in_dim(embed, i * vb, vb)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ wide(rows).T, i * vb, 1)

    return jax.lax.fori_loop(0, V // vb, some_rows,
                             jnp.zeros((S, V), jnp.float32))


def logits(params, tokens, n_head):
    """``tokens`` (S,) int -> logits (S, V) float32. ``params["layers"]``
    is a list, one dict a layer, in the order of ``LAYER_TYPES``."""
    with jax.default_matmul_precision("highest"):
        x = wide(params["embed"][tokens])
        for p, kind in zip(params["layers"], LAYER_TYPES, strict=True):
            sliding = kind == "sliding_attention"
            x = block(x, p, n_head, windowed=sliding, rotated=sliding)
        return LOGIT_SCALE * head(ln(x, params["norm"]), params["embed"])


def loss(params, rows, n_head):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1)."""
    def one(row):
        logp = jax.nn.log_softmax(logits(params, row[:-1], n_head), -1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
    return jnp.mean(jax.lax.map(one, rows))


def from_program(p):
    """The program's parameter tree (``ParallelMoE.init``) as the
    reference's: relabelled, heads folded into widths, the stacked runs
    of layers cut into a list, the shared experts (stacked on the
    hidden axis there) cut apart into ``NUM_SHARED_EXPERTS`` experts of
    their own. Every matrix keeps the dtype it is stored in (see Memory
    above); the norms' scales are float32."""
    n = NUM_SHARED_EXPERTS

    def layer(run, i):
        a, m = run["attn"], run["mlp"]
        s = m["shared"]
        fold = lambda w: w[i].reshape(w.shape[1], -1)  # noqa: E731
        d = s["wg"].shape[1]
        return {
            "ln": wide(run["ln1"][i]),
            "w_q": fold(a["wq"]), "w_k": fold(a["wk"]),
            "w_v": fold(a["wv"]),
            "w_o": a["wo"][i].reshape(-1, a["wo"].shape[-1]),
            "w_r": m["router"][i],
            "e_gate": m["wg"][i], "e_up": m["wu"][i],
            "e_down": m["wd"][i],
            "s_gate": s["wg"][i].reshape(d, n, -1).transpose(1, 0, 2),
            "s_up": s["wu"][i].reshape(d, n, -1).transpose(1, 0, 2),
            "s_down": s["wd"][i].reshape(n, -1, d)}

    return {"embed": p["tok_embed"], "norm": wide(p["final_norm"]),
            "layers": [layer(run, i) for run in p["runs"]
                       for i in range(run["ln1"].shape[0])]}
