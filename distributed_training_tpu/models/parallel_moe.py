"""Decoder whose attention and expert layer read ONE normed input and
are both added to the residual (a parallel block), over window RoPE
layers and global NoPE layers, sigmoid-routed experts held in part and
several shared experts averaged, with a tied head.

The Command A+ layout (huggingface.co/CohereLabs/command-a-plus-05-2026,
``model_type`` ``cohere2_moe``), which the benchmark's
``command-a-plus-ep16`` configuration publishes its sizes in. Layer
``l`` on the residual stream ``x``, no bias anywhere, no norm on
queries or keys:

    h   = LayerNorm(x; g)            mean subtracted, scale only,
                                     layer_norm_eps, float32 inside
    q, k, v = h W_q, h W_k, h W_v    n_heads / n_kv_heads heads of head_dim
    q, k = RoPE(q, k; rope_theta)    where rope_layout[l], ADJACENT pairs
                                     (x[2i], x[2i+1]); else no positions
    a   = softmax(q k^T / sqrt(head_dim) + mask) v      GQA; mask j <= i
                                     and, where window_layout[l], i - j < window
    s   = sigmoid(h W_r)             float32, all n_routed_experts, of
                                     the norm's float32 output (not of h
                                     rounded to the compute dtype)
    e   = top_k(s);  p = s[e] / sum(s[e])       no bias, no factor
    y_r = sum_k p_k W_d[e_k] (silu(W_g[e_k] h) * W_u[e_k] h)
    y_s = 1/n sum_j W_d^j (silu(W_g^j h) * W_u^j h)     n_shared_experts,
                                     their outputs averaged
    x'  = x + a W_o + y_r + y_s      ONE norm: attention and experts read
                                     the same h

then a LayerNorm and ``logits = logit_scale * x E^T`` with ``E`` the
embedding (tied). Local layers come first in the published period
(S S S F).

What this model shares with ``models/window_moe.py`` it takes from it
and does not write again: the configuration's fields and checks, the
runs of like layers, ``project`` (told to pair adjacent elements:
``rope_pairs``), the cache description and the run views of
``WindowBlock`` (rings for window layers beside tables), and ``apply``'s
scan over runs. What differs is here: the layer (``ParallelMoE.layer``
for the full forward; ``_ParallelRun.project`` / ``finish`` behind
``serving/blocks.py``'s interface), the head, and the parameters. The
experts are ``models/experts.py::expert_layer`` (``router_score =
"sigmoid"`` with no ``router_bias`` and no scaling factor), and the
shared experts its ``shared=`` hook: ``shared_mean``, ONE gated product
over the experts stacked on the hidden axis, times ``1 / n`` — the same
mathematics as n products averaged, one read of the weights.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from distributed_training_tpu.models import window_moe
from distributed_training_tpu.models.base import normal_init
from distributed_training_tpu.models.experts import (
    _cast, expert_layer, gated_mlp, router_logits)


@dataclass
class ParallelMoEConfig(window_moe.WindowMoEConfig):
    vocab_size: int = 32768       # rows of the tied embedding held
    d_model: int = 4096
    n_layers: int = 4
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    moe_d_ff: int = 4096
    n_routed_experts: int = 128
    moe_top_k: int = 8
    n_shared_experts: int = 4     # averaged, beside the routed sum
    window_layout: tuple = (1, 1, 1, 0)
    rope_layout: tuple = (1, 1, 1, 0)
    rope_theta: float = 5e4
    layer_norm_eps: float = 1e-5  # (``rms_norm_eps`` is not read)
    logit_scale: float = 1.0
    qk_std: float = 0.0234375
    max_seq_len: int = 8192

    router_score = "sigmoid"      # no ``router_bias``, no factor
    expert_act = "silu"
    rope_pairs = "adjacent"

    def __post_init__(self):
        super().__post_init__()
        if self.n_shared_experts < 1:
            raise ValueError("n_shared_experts must be at least 1")


def norm(x, scale, c: ParallelMoEConfig):
    """LayerNorm with a scale only: mean subtracted, float32 inside."""
    from distributed_training_tpu.serving.blocks import layer_norm

    return layer_norm(x, scale, eps=c.layer_norm_eps)


def normed_and_routed(x, layer, c: ParallelMoEConfig, w=_cast):
    """The block's one normed input ``h`` in ``x``'s dtype and the
    router's logits, float32, of the norm's float32 output BEFORE it is
    rounded to that dtype. Of the rounded ``h`` they would be the same
    function of the token alone in the first layer (``x`` is the
    embedding's row there), so a token whose eighth and ninth scores lie
    within a bfloat16 rounding of each other would take another expert
    than the published float32 router at EVERY occurrence, and a text
    that repeats it drifts (PERF.md section 6, PR 38: a worst logit gap
    of 1.04 on a sound program)."""
    h32 = norm(x.astype(jnp.float32), layer["ln1"], c)
    with jax.named_scope("dtt.moe.route"):
        logits = router_logits(h32, w(layer["mlp"]["router"],
                                      jnp.float32))
    return h32.astype(x.dtype), logits


def shared_mean(x, m, w, c: ParallelMoEConfig):
    """The mean of the ``c.n_shared_experts`` shared experts' outputs on
    ``x (T, D)``: ``m`` holds them stacked on the hidden axis (``wg``,
    ``wu`` ``(D, n F)``, ``wd`` ``(n F, D)``), so their SUM is one gated
    product and the mean that over ``n``."""
    with jax.named_scope("dtt.moe.shared"):
        return (gated_mlp(x, m, w, c.expert_act)
                * jnp.asarray(1.0 / c.n_shared_experts, x.dtype))


def experts(h, m, c: ParallelMoEConfig, valid=None, w=_cast,
            logits=None):
    """``y_r + y_s`` of the normed input ``h`` and ``COUNTERS``."""
    return expert_layer(
        h, m, c, valid, w, logits=logits,
        shared=lambda x, shared, w: shared_mean(x, shared, w, c))


class ParallelMoE(window_moe.WindowMoE):
    """Functional model: ``init`` and the layer are its own; ``apply``,
    ``loss`` and ``generate`` are ``WindowMoE``'s."""

    def init(self, rng: jax.Array):
        """Normal(0, 0.02) leaves (output projections over ``sqrt(2
        n_layers)``, norms ones) but ``W_q`` and ``W_k``, which take
        ``cfg.qk_std`` (``WindowMoE.init`` says why). The router at
        0.02 gives logits of std ``0.02 sqrt(d_model)`` on a normed
        input: sigmoid scores that differ enough for the top-k to be
        decided."""
        c = self.cfg
        pdt = jnp.dtype(c.param_dtype)
        std = 0.02
        out_std = std / (2 * c.n_layers) ** 0.5
        D, H, Hkv, hd, F = (c.d_model, c.n_heads, c.n_kv_heads,
                            c.head_dim, c.moe_d_ff)
        E, Fs = c.experts_held, c.n_shared_experts * c.moe_d_ff

        def run(key, L):
            k = jax.random.split(key, 11)
            return {
                "ln1": jnp.ones((L, D), pdt),
                "attn": {
                    "wq": normal_init(k[0], (L, D, H, hd), c.qk_std, pdt),
                    "wk": normal_init(k[1], (L, D, Hkv, hd), c.qk_std,
                                      pdt),
                    "wv": normal_init(k[2], (L, D, Hkv, hd), std, pdt),
                    "wo": normal_init(k[3], (L, H, hd, D), out_std, pdt),
                },
                "mlp": {
                    "router": normal_init(
                        k[4], (L, D, c.n_routed_experts), std, pdt),
                    "wg": normal_init(k[5], (L, E, D, F), std, pdt),
                    "wu": normal_init(k[6], (L, E, D, F), std, pdt),
                    "wd": normal_init(k[7], (L, E, F, D), out_std, pdt),
                    "shared": {
                        "wg": normal_init(k[8], (L, D, Fs), std, pdt),
                        "wu": normal_init(k[9], (L, D, Fs), std, pdt),
                        "wd": normal_init(k[10], (L, Fs, D), out_std,
                                          pdt),
                    },
                },
            }

        keys = jax.random.split(rng, 1 + len(c.runs))
        return {
            "tok_embed": normal_init(keys[0], (c.vocab_size, D), std,
                                     pdt),
            "final_norm": jnp.ones((D,), pdt),
            "runs": tuple(run(k, n) for k, (_lo, n, _w, _r)
                          in zip(keys[1:], c.runs)),
        }

    def layer(self, layer, x, positions, rope: bool, attend):
        c = self.cfg
        h, r = normed_and_routed(x, layer, c)
        attn = attend(*window_moe.project(h, layer["attn"], positions,
                                          rope, c))
        return (x + jnp.einsum("...hk,hkd->...d", attn,
                               layer["attn"]["wo"].astype(x.dtype))
                + experts(h, layer["mlp"], c, logits=r)[0])

    def head(self, params, x, w=_cast):
        c = self.cfg
        x = norm(x, params["final_norm"], c)
        logits = jnp.einsum("...d,vd->...v", x,
                            w(params["tok_embed"], x.dtype)
                            ).astype(jnp.float32)
        return logits if c.logit_scale == 1 else logits * c.logit_scale

    def serving_block(self):
        return ParallelBlock(self)


class ParallelBlock(window_moe.WindowBlock):
    """This model behind ``serving/blocks.py``'s interface:
    ``WindowBlock``'s cache description, embedding and runs, with the
    run views below."""

    def run_view(self, window: bool, rope: bool):
        return _ParallelRun(self, window, rope)


class _ParallelRun(window_moe._Run):
    """A run of parallel layers. ``project`` norms ONCE and hands ``h``
    on beside the queries and the router's logits (``q = (heads,
    (logits, h))``: ``_Run.attend_chunk`` passes the second member
    through as it passes the logits of ``WindowBlock``), and ``finish``
    adds the attention's output, the routed sum and the shared mean to
    ``x`` with no second norm."""

    def project(self, layer, x, positions):
        b = self.block
        h, logits = normed_and_routed(x, layer, b.cfg, b._w)
        q, k, v = window_moe.project(h, layer["attn"], positions,
                                     self.rope, b.cfg, b._w)
        return (q, (logits, h)), k, v

    def finish(self, layer, x, attn, valid):
        b = self.block
        attn, (logits, h) = attn
        with jax.named_scope("dtt.attn.out"):
            x = x + jnp.einsum("...hk,hkd->...d", attn,
                               b._w(layer["attn"]["wo"], x.dtype))
        with jax.named_scope("dtt.moe.experts"):
            y, counts = experts(h, layer["mlp"], b.cfg, valid, b._w,
                                logits)
            return x + y, counts


def build_parallel_moe(loss: str = "auto", dtype: str = "bfloat16",
                       **kwargs) -> ParallelMoE:
    """Registry entrypoint (``build_model("parallel_moe", ...)``)."""
    if loss not in ("auto", "xent"):
        raise ValueError(f"parallel_moe has one loss (xent), got {loss!r}")
    kwargs.setdefault("dtype", dtype)
    return ParallelMoE(ParallelMoEConfig(**kwargs))
