"""Plain reference for the dots3-note layout: multi-head latent attention
of two kinds in one model (full layers under a learned top-k selection
of positions, window layers with a latent of their own width), a
headwise output gate, a sigmoid router over experts held in part with a
shared expert. Forward pass and next-token loss in float32.

Written from the published configuration
(huggingface.co/dots-studio/dots3-note-prev, config.json) and its
description, with no kernels, no cache, no ring, no absorption, no
batching and nothing imported from the program under test. Every matrix
product runs under ``jax.default_matmul_precision("highest")`` so a TPU
does not quietly compute it in bfloat16.

Decoder layer ``l`` on ``x`` (S, D), ``rms`` = RMSNorm (scale only), no
biases but the indexer's LayerNorm; a FULL layer (``LAYER_TYPES[l] ==
"full_attention"``) with the sizes below, a WINDOW layer
(``"sliding_attention"``) with the ``SWA_`` ones:

    h = rms(x)
    c_q = s_q rms(h W_dq)                 s_q = sqrt(D / q_lora_rank)
    [c_kv ; k_r] = h W_dkv ;  c_kv = s_kv rms(c_kv)      s_kv likewise
    q_i = c_q W_uq,i                      n_head heads of QK_NOPE + QK_ROPE,
                                          the last QK_ROPE under rope
    k_i,s = [c_kv,s W_uk,i ; rope(k_r,s)] ;  v_i,s = c_kv,s W_uv,i
    a_t,i = softmax over s in S_t of q_t,i . k_i,s / sqrt(QK_NOPE + QK_ROPE)
    g_t = sigmoid(h_t W_g)                one gate a head
    x = x + concat_i(g_t,i sum_s a_t,i,s v_i,s) W_o

``S_t`` of a window layer is ``{s : 0 <= t - s < WINDOW}``. Of a full
layer it is the learned selection:

    qI_j = c_q W_iq,j                     J index heads of d, the first
    kI_s = layernorm(h_s W_ik)            QK_ROPE of each under rope
    w = h W_iw                            (J,)
    I(t, s) = sum_j w_t,j relu(qI_t,j . kI_s)       for s <= t
    S_t = the INDEX_TOPK positions s <= t of largest I(t, s), all of
          them while t < INDEX_TOPK

made here as a dense (S, S) mask from the full score matrix, a row's
scores sorted whole; of equal scores the lower position goes first (all
relu's at zero give exact ties at 0: rare with 64 index heads, common
with the tests' few). ``rope`` turns the pair ``(x[2i], x[2i + 1])`` by ``pos * theta^(-2i
/ d)``.

Feed-forward: layers below FIRST_K_DENSE ``W_down(silu(W_gate h2) *
(W_up h2))``; the others

    s = sigmoid(h2 W_r)                   all experts
    chosen = the TOP_K largest of s + b ;  g_e = SCALING * s_e / sum_chosen s
    x = x + sum_{e chosen and held} g_e E_e(h2) + E_shared(h2)

Departures from the published recipe, each the configuration file's
(``assumed``): the index key is cached in bfloat16, not FP8 after a
Hadamard rotation, so the reference rounds ``kI`` to INDEX_KEY_DTYPE
before it widens it again (that is the cache's stated precision; None
in the float32 tests); the positive scales of ``I`` are left out (they
do not change the order); the rescale is ``s_q``, ``s_kv`` above,
after the norms, and the indexer reads the rescaled ``c_q``; the gate
reads the layer's normed input; the window counts the query.

**Experts and rows held.** The expert arrays' second axis is the experts
held, global experts ``EP_RANK * held ... (EP_RANK + 1) * held - 1``;
the router is whole, and what the absent experts would add is left out,
as in the program. Embedding and head have the rows the program holds.

Memory, at the published widths and 16,384 positions on one 16 GB chip
beside the program's own 8.2 GB of weights: ``from_program`` hands the
program's arrays on as they are and widens none; every matrix is widened
to float32 where it is used, a layer at a time, which is exact; the
held experts one at a time; attention HEAD_BLOCK heads and Q_BLOCK
queries at a time with that block of heads' keys and values expanded
for all positions, each block of heads gated and sent through its rows
of ``W_o`` at once (no (S, heads x v) array is held); the feed-forward
ROW_BLOCK rows at a time. The harness makes the reference's parameters
by ``jax.jit(from_program)(params)``, which COPIES every array handed
on: the reference's weights lie a second time beside the program's, in
the program's dtype, and that is what bounds the configuration's size
(``perfbench/configs/dots3-note-ep8.json``, ``reduced_why``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# The configuration's numbers the harness does not hand over
# (``logits`` gets ``n_head`` and nothing else; ranks, widths and the
# indexer's heads are read off the arrays). Held to
# ``perfbench/configs/dots3-note-ep8.json`` by
# ``tests/test_zsparse_latent_moe.py``; the tiny-size tests set others.
LAYER_TYPES = ("full_attention", "full_attention", "sliding_attention",
               "sliding_attention", "sliding_attention")
FIRST_K_DENSE = 1
QK_NOPE = 128
QK_ROPE = 64
ROPE_THETA = 80000000.0
INDEX_TOPK = 2048
SWA_N_HEAD = 64
SWA_QK_NOPE = 192
SWA_QK_ROPE = 64
SWA_ROPE_THETA = 50000.0
WINDOW = 513
RESCALE = True
RMS_NORM_EPS = 1e-5
NUM_EXPERTS_PER_TOK = 8
ROUTED_SCALING = 1.0
EP_RANK = 0
INDEX_KEY_DTYPE = jnp.bfloat16
Q_BLOCK = 256
HEAD_BLOCK = 8
ROW_BLOCK = 2048


def f32(a):
    return a.astype(jnp.float32)


def rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                        + RMS_NORM_EPS) * scale


def layernorm(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + bias


def rope(x, pos, theta):
    """``x`` (S, ..., d) at positions ``pos`` (S,): the pair ``(x[2i],
    x[2i + 1])`` turned by ``pos * theta^(-2i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * inv
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     -1).reshape(x.shape)


def in_blocks(fn, n: int, block: int):
    """``fn(rows)`` for ``rows`` = ``block`` indices at a time over
    ``0 .. n - 1`` (the last block's surplus clipped to ``n - 1`` and
    cut off again), the results laid end to end."""
    block = min(block, n)
    nb = -(-n // block)
    out = jax.lax.map(
        lambda i: fn(jnp.minimum(i * block + jnp.arange(block), n - 1)),
        jnp.arange(nb))
    return out.reshape((nb * block,) + out.shape[2:])[:n]


def index_rope(x, pos):
    """The indexer's queries or key with the first QK_ROPE of the last
    axis under rope."""
    return jnp.concatenate([rope(x[..., :QK_ROPE], pos, ROPE_THETA),
                            x[..., QK_ROPE:]], -1)


def head_gate(h, a):
    """(S, heads): one gate a head, of the layer's normed input."""
    return jax.nn.sigmoid(h @ f32(a["wg"]))


def selection(h, c_q, ix, pos):
    """The full layer's ``S_t`` as a mask (S, S): see the module's
    text. The indexer's queries are made Q_BLOCK rows at a time."""
    S = h.shape[0]
    wq = f32(ix["wq"])
    J, d = wq.shape[1:]
    ki = index_rope(layernorm(h @ f32(ix["wk"]),
                              f32(ix["k_norm"]["scale"]),
                              f32(ix["k_norm"]["bias"])), pos)
    if INDEX_KEY_DTYPE is not None:
        ki = f32(ki.astype(INDEX_KEY_DTYPE))
    w = h @ f32(ix["ww"])
    k = min(INDEX_TOPK, S)

    def block(rows):
        qi = index_rope((c_q[rows] @ wq.reshape(wq.shape[0], -1)
                         ).reshape(-1, J, d), pos[rows])
        scores = jnp.einsum("qjk,qj->qk", jax.nn.relu(
            jnp.einsum("qjd,kd->qjk", qi, ki)), w[rows])
        seen = pos[None, :] <= rows[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        order = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
        chosen = jnp.zeros(scores.shape, bool).at[
            jnp.arange(rows.shape[0])[:, None], order].set(True)
        return chosen & seen

    return in_blocks(block, S, Q_BLOCK)


def latents(h, a):
    """``c_q`` (S, q rank), ``c_kv`` (S, kv rank) after norm and
    rescale, ``k_r`` (S, rope) before rope."""
    D = h.shape[-1]
    w_dq, w_dkv = f32(a["wdq"]), f32(a["wdkv"])
    rank = a["kv_norm"].shape[-1]
    s_q = math.sqrt(D / w_dq.shape[-1]) if RESCALE else 1.0
    s_kv = math.sqrt(D / rank) if RESCALE else 1.0
    c_q = s_q * rms(h @ w_dq, f32(a["q_norm"]))
    ckv = h @ w_dkv
    return c_q, s_kv * rms(ckv[:, :rank], f32(a["kv_norm"])), \
        ckv[:, rank:]


def attend(h, c_q, c_kv, k_r, a, mask, pos, n_head, nope, theta):
    """(S, D): every head's softmax over the positions ``mask (S, S)``
    marks, its gate and its rows of ``W_o``, HEAD_BLOCK heads at a time
    (their keys and values expanded for all positions) and summed."""
    S = c_q.shape[0]
    w_uq, w_ukv, w_o = f32(a["wuq"]), f32(a["wukv"]), f32(a["wo"])
    k_rope = rope(k_r, pos, theta)
    gate = head_gate(h, a)                                 # (S, heads)
    hb = min(HEAD_BLOCK, n_head)
    scale = 1.0 / math.sqrt(w_uq.shape[-1])

    def heads(total, g):
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, g * hb, hb, axis)
        q = jnp.einsum("sr,rhk->shk", c_q, cut(w_uq, 1))
        q = jnp.concatenate([q[..., :nope],
                             rope(q[..., nope:], pos, theta)], -1)
        kv = jnp.einsum("sr,rhk->shk", c_kv, cut(w_ukv, 1))
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope[:, None, :], (S, hb, k_rope.shape[-1]))], -1)
        v = kv[..., nope:]

        def block(rows):
            scores = jnp.einsum("qhd,khd->hqk", q[rows], k) * scale
            scores = jnp.where(mask[rows][None], scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, -1), v)

        out = in_blocks(block, S, Q_BLOCK) * cut(gate, 1)[..., None]
        return total + jnp.einsum("shv,hvd->sd", out, cut(w_o, 0)), None

    total, _ = jax.lax.scan(heads, jnp.zeros_like(h),
                            jnp.arange(n_head // hb))
    return total


def attention_layer(h, p, pos, n_head, windowed):
    a = p["attn"]
    c_q, c_kv, k_r = latents(h, a)
    back = pos[:, None] - pos[None, :]
    if windowed:
        mask = (back >= 0) & (back < WINDOW)
        return attend(h, c_q, c_kv, k_r, a, mask, pos, SWA_N_HEAD,
                      SWA_QK_NOPE, SWA_ROPE_THETA)
    mask = selection(h, c_q, p["index"], pos)
    return attend(h, c_q, c_kv, k_r, a, mask, pos, n_head, QK_NOPE,
                  ROPE_THETA)


def gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ f32(w_gate)) * (h @ f32(w_up))) @ f32(w_down)


def gates(s, bias):
    """(S, all experts): the chosen experts' scores over their sum
    times ROUTED_SCALING for those, 0 elsewhere; chosen are the
    NUM_EXPERTS_PER_TOK largest of ``s + bias``."""
    order = jnp.argsort(-(s + bias), axis=-1)[:, :NUM_EXPERTS_PER_TOK]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    picked = jnp.where(chosen, s, 0.0)
    return ROUTED_SCALING * picked / picked.sum(-1, keepdims=True)


def experts(h, m, i):
    """Layer ``i`` of the stacked run ``m``: this rank's experts' part of
    the routed sum and the shared expert."""
    held = m["wg"].shape[1]
    s = jax.nn.sigmoid(h @ f32(m["router"][i]))
    g = jax.lax.dynamic_slice_in_dim(
        gates(s, f32(m["router_bias"][i])), EP_RANK * held, held, 1)

    def one(y, e):
        out = gated(h, m["wg"][i, e], m["wu"][i, e], m["wd"][i, e])
        return y + jnp.take(g, e, axis=1)[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    sh = m["shared"]
    return y + gated(h, sh["wg"][i], sh["wu"][i], sh["wd"][i])


def feed_forward(h, run, i, dense):
    m = run["mlp"]
    if not dense:
        return experts(h, m, i)
    return in_blocks(
        lambda rows: gated(h[rows], m["wg"][i], m["wu"][i], m["wd"][i]),
        h.shape[0], ROW_BLOCK)


def logits(params, tokens, n_head):
    """``tokens`` (S,) int -> logits (S, V) float32. ``params["runs"]``
    are the program's stacked runs of like layers, in layer order."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = f32(params["embed"][tokens])
        number = 0
        for run in params["runs"]:
            for i in range(run["ln1"].shape[0]):
                p = jax.tree.map(lambda leaf: leaf[i], {
                    k: run[k] for k in ("ln1", "ln2", "attn", "index")
                    if k in run})
                windowed = LAYER_TYPES[number] == "sliding_attention"
                x = x + attention_layer(rms(x, f32(p["ln1"])), p, pos,
                                        n_head, windowed)
                x = x + feed_forward(rms(x, f32(p["ln2"])), run, i,
                                     number < FIRST_K_DENSE)
                number += 1
        assert number == len(LAYER_TYPES), (number, LAYER_TYPES)
        return rms(x, f32(params["norm"])) @ f32(params["head"])


def loss(params, rows, n_head):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1)."""
    def one(row):
        logp = jax.nn.log_softmax(logits(params, row[:-1], n_head), -1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
    return jnp.mean(jax.lax.map(one, rows))


def from_program(p):
    """The program's parameter tree (``SparseLatentMoE.init``) as the
    reference's: relabelled at the top and otherwise handed on as it
    is, every array in the dtype it comes in (see Memory above);
    ``logits`` widens each where it uses it."""
    return {"embed": p["tok_embed"], "head": p["lm_head"],
            "norm": p["final_norm"], "runs": tuple(p["runs"])}
