"""Plain reference for the SmallThinker layout: window and global
attention layers mixed, NoPE and RoPE layers mixed, grouped-query
attention, a softmax router that reads the attention's input, ReGLU
experts held in part. Forward pass and next-token loss in float32.

Written from the published configuration
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct, config.json)
and its description, with no kernels, no cache, no ring, no batching
and nothing imported from the program under test. Every matrix product
runs under ``jax.default_matmul_precision("highest")`` so a TPU does not
quietly compute it in bfloat16.

Decoder layer ``l`` on ``x`` (S, D), with ``rms`` = RMSNorm (scale
only), no biases:

    h = rms(x)
    r = h W_r                                    all experts' logits
    q, k, v = h W_q, h W_k, h W_v                H / Hkv heads of HEAD_DIM
    q, k = rope(q), rope(k)                      where ROPE_LAYOUT[l]; else
                                                 no positions at all
    p = softmax(q . k / sqrt(HEAD_DIM) + mask)   query head i uses kv head
                                                 i // (H / Hkv); mask: key j
                                                 <= query i, and where
                                                 WINDOW_LAYOUT[l] also
                                                 i - j < WINDOW
    x = x + concat_h(p v) W_o
    h2 = rms(x)
    chosen = the TOP_K largest of r;  g = softmax(r over chosen)
    x = x + sum_{e chosen and held} g_e W_down[e](relu(h2 W_gate[e]) * (h2 W_up[e]))

``rope`` turns the pair ``(x[i], x[i + d/2])`` by ``pos * ROPE_THETA^(-2i/d)``.

Departures from the published description, each the configuration
file's (``assumed``, ``reduced``): the router's input is the
attention's normed input (``described_as``: "router placed before
attention"); the gate's activation is ReLU ("sparse ReGLU"); the window
counts the query itself; "secondary experts" are not in the published
``config`` and are not here.

**Experts and rows held.** The reference is given the same share of
each layer as the program: the expert arrays' leading axis is the
experts held, global experts ``EP_RANK * held ... (EP_RANK + 1) * held
- 1``; it routes over all of them (the router is whole) and leaves out
what the absent experts would add, as the program does. The embedding
and the head have the rows the program holds.

Memory, at the published widths and 16,384 positions on one 16 GB chip
beside the program's own weights: attention runs over ``Q_BLOCK``
queries at a time under a dense mask, the held experts one at a time,
and the held experts' arrays stay in the dtype they come in and are
widened to float32 one expert at a time inside that loop, which is
exact.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# The configuration's numbers the harness does not hand over
# (``logits`` gets ``n_head`` and nothing else). Held to
# ``perfbench/configs/smallthinker-21b-ep4.json`` by
# ``tests/test_window_moe.py``; the tiny-size tests set others.
N_KV_HEAD = 4
HEAD_DIM = 128
WINDOW = 4096
WINDOW_LAYOUT = (0, 1, 1, 1) * 3
ROPE_LAYOUT = (0, 1, 1, 1) * 3
ROPE_THETA = 1500000.0
RMS_NORM_EPS = 1e-6
NUM_EXPERTS_PER_TOK = 6
EP_RANK = 0
Q_BLOCK = 256


def rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                        + RMS_NORM_EPS) * scale


def rope(x, pos):
    """``x`` (S, heads, d) at positions ``pos`` (S,): the pair
    ``(x[i], x[i + d/2])`` turned by ``pos * ROPE_THETA^(-2i/d)``."""
    d = x.shape[-1]
    inv = ROPE_THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def attention(h, p, n_head, windowed, rotated):
    S = h.shape[0]
    group = n_head // N_KV_HEAD
    pos = jnp.arange(S)
    q = (h @ p["w_q"]).reshape(S, n_head, HEAD_DIM)
    k = (h @ p["w_k"]).reshape(S, N_KV_HEAD, HEAD_DIM)
    v = (h @ p["w_v"]).reshape(S, N_KV_HEAD, HEAD_DIM)
    if rotated:
        q, k = rope(q, pos), rope(k, pos)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)

    # Q_BLOCK queries at a time; the last block is padded with zero
    # queries, whose rows are cut off again.
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    q = jnp.pad(q, ((0, nb * qb - S), (0, 0), (0, 0)))

    def block(i):
        rows = i * qb + jnp.arange(qb)
        back = rows[:, None] - pos[None, :]       # keys behind the query
        seen = back >= 0
        if windowed:
            seen &= back < WINDOW
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) \
            / math.sqrt(HEAD_DIM)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    att = jax.lax.map(block, jnp.arange(nb)).reshape(
        nb * qb, n_head * HEAD_DIM)[:S]
    return att @ p["w_o"]


def gates(r):
    """(S, all experts): the softmax over the chosen experts' logits for
    those, 0 elsewhere."""
    order = jnp.argsort(-r, axis=-1)[:, :NUM_EXPERTS_PER_TOK]
    chosen = jnp.zeros(r.shape, bool).at[
        jnp.arange(r.shape[0])[:, None], order].set(True)
    return jax.nn.softmax(jnp.where(chosen, r, -jnp.inf), axis=-1)


def experts(h, r, p):
    held = p["e_gate"].shape[0]
    g = jax.lax.dynamic_slice_in_dim(gates(r), EP_RANK * held, held, 1)

    def one(y, e):
        w_gate, w_up, w_down, g_e = e
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        out = (jax.nn.relu(h @ f32(w_gate)) * (h @ f32(w_up))) \
            @ f32(w_down)
        return y + g_e[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["e_gate"], p["e_up"], p["e_down"], g.T))
    return y


def block(x, p, n_head, windowed, rotated):
    h = rms(x, p["ln_1"])
    r = h @ p["w_r"]
    x = x + attention(h, p, n_head, windowed, rotated)
    return x + experts(rms(x, p["ln_2"]), r, p)


def logits(params, tokens, n_head):
    """``tokens`` (S,) int -> logits (S, V) float32. ``params["layers"]``
    is a list, one dict a layer, in the order of the two layouts."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for p, windowed, rotated in zip(params["layers"], WINDOW_LAYOUT,
                                        ROPE_LAYOUT, strict=True):
            x = block(x, p, n_head, windowed, rotated)
        return rms(x, params["norm"]) @ params["head"]


def loss(params, rows, n_head):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1)."""
    def one(row):
        logp = jax.nn.log_softmax(logits(params, row[:-1], n_head), -1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
    return jnp.mean(jax.lax.map(one, rows))


def from_program(p):
    """The program's parameter tree (``WindowMoE.init``) as the
    reference's: relabelled, heads folded into widths, the stacked runs
    of layers cut into a list, everything float32 but the held experts'
    arrays, which keep their dtype (see Memory above)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731

    def layer(run, i):
        a, m = run["attn"], run["mlp"]
        fold = lambda w: f32(w[i]).reshape(w.shape[1], -1)  # noqa: E731
        return {
            "ln_1": f32(run["ln1"][i]), "ln_2": f32(run["ln2"][i]),
            "w_q": fold(a["wq"]), "w_k": fold(a["wk"]),
            "w_v": fold(a["wv"]),
            "w_o": f32(a["wo"][i]).reshape(-1, a["wo"].shape[-1]),
            "w_r": f32(m["router"][i]),
            "e_gate": m["wg"][i], "e_up": m["wu"][i],
            "e_down": m["wd"][i]}

    return {"embed": f32(p["tok_embed"]), "head": f32(p["lm_head"]),
            "norm": f32(p["final_norm"]),
            "layers": [layer(run, i) for run in p["runs"]
                       for i in range(run["ln1"].shape[0])]}
