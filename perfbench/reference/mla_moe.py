"""Plain reference for the DeepSeek-V3 layout: latent attention, a
sigmoid router with a selection bias, experts held in part, a shared
expert. Forward pass and next-token loss in float32.

Written from the published description (DeepSeek-V3 technical report,
arXiv:2412.19437, sections 2.1.1 and 2.1.2; the Hugging Face
``DeepseekV3ForCausalLM`` layout that ``joyai_llm_flash`` keeps), with
no kernels, no cache, no absorbed products, no batching and nothing
imported from the program under test. Every matrix product runs under
``jax.default_matmul_precision("highest")`` so a TPU does not quietly
compute it in bfloat16.

A decoder layer on ``x`` (S, D), with ``rms`` = RMSNorm (scale only):

    h = rms(x)
    c_q = rms(h W_dq);  q = c_q W_uq          -> heads of [nope | rope]
    [c_kv | k_r] = h W_dkv;  c_kv = rms(c_kv)
    [k_nope | v] = c_kv W_ukv                 -> heads of [nope | v]
    q_r, k_r = rope(q_r), rope(k_r)           k_r is one key for all heads
    p = softmax(([q_nope | q_r] . [k_nope | k_r]) / sqrt(nope + rope)), causal
    x = x + concat_h(p v) W_o
    x = x + ffn(rms(x))

``rope`` rotates the pairs ``(2i, 2i + 1)`` by ``pos * theta^(-2i/d)``.
``ffn`` is ``(silu(h W_gate) * (h W_up)) W_down`` in a dense layer, and
in an expert layer

    s = sigmoid(h W_r)                                  all experts
    chosen = the TOP_K largest of s + b                 b chooses only
    g_e = ROUTED_SCALING * s_e / sum_{chosen} s         for e chosen, else 0
    y = sum_{e held} g_e E_e(h) + E_shared(h)

**Experts held.** The reference is given the same share of each expert
layer as the program: the expert arrays' leading axis is the experts
held, global experts ``EP_RANK * held ... (EP_RANK + 1) * held - 1``.
It routes over all of them (the router is whole) and leaves out what
the absent experts would add, as the program does. With every expert
held it is the whole layer.

Memory, at the published widths on one 16 GB chip beside the program's
own weights: attention runs over ``Q_BLOCK`` queries at a time, the
held experts one at a time, and the held experts' arrays stay in the
dtype they come in and are widened to float32 one expert at a time
inside that loop, which is exact.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# The configuration's numbers the harness does not hand over
# (``logits`` gets ``n_head`` and nothing else). Held to
# ``perfbench/configs/joyai-llm-flash-ep4.json`` by
# ``tests/test_latent_moe.py``; the tiny-size tests set others.
QK_NOPE_HEAD_DIM = 128
QK_ROPE_HEAD_DIM = 64
V_HEAD_DIM = 128
ROPE_THETA = 32000000.0
RMS_NORM_EPS = 1e-6
NUM_EXPERTS_PER_TOK = 8
ROUTED_SCALING_FACTOR = 2.5
EP_RANK = 0
Q_BLOCK = 512


def rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                        + RMS_NORM_EPS) * scale


def rope(x, pos):
    """``x`` (S, ..., d) at positions ``pos`` (S,): each pair
    ``(x[2i], x[2i+1])`` turned by ``pos * ROPE_THETA^(-2i/d)``, as one
    complex product."""
    d = x.shape[-1]
    inv = ROPE_THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) \
        * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def attention(h, p, n_head):
    S = h.shape[0]
    nope, rd, vd = QK_NOPE_HEAD_DIM, QK_ROPE_HEAD_DIM, V_HEAD_DIM
    pos = jnp.arange(S)
    q = (rms(h @ p["w_dq"], p["q_norm"]) @ p["w_uq"]).reshape(
        S, n_head, nope + rd)
    ckv = h @ p["w_dkv"]
    rank = ckv.shape[-1] - rd
    kv = (rms(ckv[:, :rank], p["kv_norm"]) @ p["w_ukv"]).reshape(
        S, n_head, nope + vd)
    k_r = rope(ckv[:, rank:], pos)                        # (S, rd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (S, n_head, rd))],
        -1)
    v = kv[..., nope:]

    # Q_BLOCK queries at a time; the last block is padded with zero
    # queries, whose rows are cut off again.
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    q = jnp.pad(q, ((0, nb * qb - S), (0, 0), (0, 0)))

    def block(i):
        rows = i * qb + jnp.arange(qb)
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) \
            / math.sqrt(nope + rd)
        scores = jnp.where((pos[None, :] <= rows[:, None])[None], scores,
                           -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    att = jax.lax.map(block, jnp.arange(nb)).reshape(
        nb * qb, n_head * vd)[:S]
    return att @ p["w_o"]


def gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def gates(h, p):
    """(S, all experts): ``g_e`` for the chosen experts of each token,
    0 elsewhere."""
    s = jax.nn.sigmoid(h @ p["w_r"])
    order = jnp.argsort(-(s + p["b_r"]), axis=-1)[:, :NUM_EXPERTS_PER_TOK]
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], order].set(1.0)
    picked = s * chosen
    return ROUTED_SCALING_FACTOR * picked / picked.sum(-1, keepdims=True)


def experts(h, p):
    held = p["e_gate"].shape[0]
    g = jax.lax.dynamic_slice_in_dim(gates(h, p), EP_RANK * held, held, 1)

    def one(y, e):
        w_gate, w_up, w_down, g_e = e
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        return y + g_e[:, None] * gated(h, f32(w_gate), f32(w_up),
                                        f32(w_down)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["e_gate"], p["e_up"], p["e_down"], g.T))
    return y + gated(h, p["s_gate"], p["s_up"], p["s_down"])


def block(x, p, n_head):
    x = x + attention(rms(x, p["ln_1"]), p, n_head)
    h = rms(x, p["ln_2"])
    if "w_r" in p:
        return x + experts(h, p)
    return x + gated(h, p["w_gate"], p["w_up"], p["w_down"])


def logits(params, tokens, n_head):
    """``tokens`` (S,) int -> logits (S, V) float32. ``params["layers"]``
    is a list, one dict a layer: the layers are of two kinds."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for p in params["layers"]:
            x = block(x, p, n_head)
        return rms(x, params["norm"]) @ params["head"]


def loss(params, rows, n_head):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1)."""
    def one(row):
        logp = jax.nn.log_softmax(logits(params, row[:-1], n_head), -1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
    return jnp.mean(jax.lax.map(one, rows))


def from_program(p):
    """The program's parameter tree (``LatentMoE.init``) as the
    reference's: relabelled, heads folded into widths, the stacked runs
    of layers cut into a list, everything float32 but the held experts'
    arrays, which keep their dtype (see Memory above)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731

    def layer(run, i):
        a, m = run["attn"], run["mlp"]
        fold = lambda w: f32(w[i]).reshape(w.shape[1], -1)  # noqa: E731
        out = {
            "ln_1": f32(run["ln1"][i]), "ln_2": f32(run["ln2"][i]),
            "w_dq": f32(a["wdq"][i]), "q_norm": f32(a["q_norm"][i]),
            "w_uq": fold(a["wuq"]), "w_dkv": f32(a["wdkv"][i]),
            "kv_norm": f32(a["kv_norm"][i]), "w_ukv": fold(a["wukv"]),
            "w_o": f32(a["wo"][i]).reshape(-1, a["wo"].shape[-1]),
        }
        if "router" not in m:
            return {**out, "w_gate": f32(m["wg"][i]),
                    "w_up": f32(m["wu"][i]), "w_down": f32(m["wd"][i])}
        s = m["shared"]
        return {**out, "w_r": f32(m["router"][i]),
                "b_r": f32(m["router_bias"][i]),
                "e_gate": m["wg"][i], "e_up": m["wu"][i],
                "e_down": m["wd"][i], "s_gate": f32(s["wg"][i]),
                "s_up": f32(s["wu"][i]), "s_down": f32(s["wd"][i])}

    runs = [p[k] for k in ("dense", "moe") if k in p]
    return {"embed": f32(p["tok_embed"]), "head": f32(p["lm_head"]),
            "norm": f32(p["final_norm"]),
            "layers": [layer(run, i) for run in runs
                       for i in range(run["ln1"].shape[0])]}
