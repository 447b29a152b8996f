"""Plain GPT-2 reference: forward pass and next-token loss in float32.

Written from the GPT-2 description (Radford et al. 2019; the Hugging
Face ``GPT2LMHeadModel`` layout), with no kernels, no cache, no batching
tricks and nothing imported from the program under test. Every matrix
product runs under ``jax.default_matmul_precision("highest")`` so a TPU
does not quietly compute it in bfloat16.

A decoder layer is

    x = x + proj(attention(ln_1(x)))       causal, softmax(q k^T / sqrt(d))
    x = x + fc_out(gelu_new(fc_in(ln_2(x))))

after ``wte[token] + wpe[position]``, and the logits are
``ln_f(x) @ wte^T`` (tied embeddings). LayerNorm has scale and bias,
epsilon 1e-5; ``gelu_new`` is the tanh approximation.

Departure from the published model, noted once: the program's block
(``models/transformer.py``) has no bias on the four attention
projections, so ``from_program`` supplies zeros for them. The
reference itself carries the biases, as GPT-2 does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def layer_norm(x, scale, bias, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def block(x, p, n_head):
    """One decoder layer on ``x`` (S, D); ``p`` holds that layer's
    arrays."""
    S, D = x.shape
    hd = D // n_head
    h = layer_norm(x, p["ln_1_g"], p["ln_1_b"])
    q = (h @ p["w_q"] + p["b_q"]).reshape(S, n_head, hd)
    k = (h @ p["w_k"] + p["b_k"]).reshape(S, n_head, hd)
    v = (h @ p["w_v"] + p["b_v"]).reshape(S, n_head, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(S, D) @ p["w_proj"] + p["b_proj"]
    h = layer_norm(x, p["ln_2_g"], p["ln_2_b"])
    h = gelu_new(h @ p["w_fc"] + p["b_fc"])
    return x + h @ p["w_fc_out"] + p["b_fc_out"]


def logits(params, tokens, n_head):
    """``tokens`` (S,) int -> logits (S, V) float32. ``params["layers"]``
    holds each layer array stacked on a leading layer axis; the loop
    over layers is a ``lax.scan`` only so that 48 layers compile as
    one, and each layer is a ``jax.checkpoint`` only so that the
    gradient of 48 layers fits a chip (it changes no number)."""
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[0]
        x = params["wte"][tokens] + params["wpe"][:S]

        @jax.checkpoint
        def step(x, layer):
            return block(x, layer, n_head), None

        x, _ = jax.lax.scan(step, x, params["layers"])
        x = layer_norm(x, params["ln_f_g"], params["ln_f_b"])
        return x @ params["wte"].T


def loss(params, rows, n_head):
    """Mean next-token cross-entropy over ``rows`` (B, S + 1): each row
    feeds its first S tokens and is scored on its last S. One row at
    a time, each recomputed in the backward pass, for memory alone."""
    @jax.checkpoint
    def one(row):
        lg = logits(params, row[:-1], n_head)
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
    return jnp.mean(jax.lax.map(one, rows))


def from_program(p):
    """The program's parameter tree (``Transformer.init``) as the
    reference's, in float32. Pure relabelling and reshaping: heads are
    folded back into the width, and the attention biases the program
    lacks are zeros."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    a, m = p["attn"], p["mlp"]
    L, D = p["ln1"]["scale"].shape
    zeros = jnp.zeros((L, D), jnp.float32)
    return {
        "wte": f32(p["tok_embed"]), "wpe": f32(p["pos_embed"]),
        "ln_f_g": f32(p["final_norm"]["scale"]),
        "ln_f_b": f32(p["final_norm"]["bias"]),
        "layers": {
            "ln_1_g": f32(p["ln1"]["scale"]),
            "ln_1_b": f32(p["ln1"]["bias"]),
            "ln_2_g": f32(p["ln2"]["scale"]),
            "ln_2_b": f32(p["ln2"]["bias"]),
            "w_q": f32(a["wq"]).reshape(L, D, D), "b_q": zeros,
            "w_k": f32(a["wk"]).reshape(L, D, D), "b_k": zeros,
            "w_v": f32(a["wv"]).reshape(L, D, D), "b_v": zeros,
            "w_proj": f32(a["wo"]).reshape(L, D, D), "b_proj": zeros,
            "w_fc": f32(m["wi"]), "b_fc": f32(m["bi"]),
            "w_fc_out": f32(m["wo"]), "b_fc_out": f32(m["bo"]),
        },
    }
