"""Latent-attention decoder with sigmoid-routed experts, held in part.

The DeepSeek-V3 layout (arXiv:2412.19437), which the benchmark's
``joyai-llm-flash-ep4`` configuration publishes its sizes in. With
``h = RMSNorm(x)`` (scale only), no biases, no position embedding:

    x = x + Attn(RMSNorm(x));  x = x + FFN(RMSNorm(x))

**Attention (every layer)** is multi-head latent attention:

    c_q = RMSNorm(W_dq h)                 q = W_uq c_q   (H heads of nope + rope)
    [c_kv ; k_r] = W_dkv h                c_kv = RMSNorm(c_kv)
    [k_nope ; v] = W_ukv c_kv             (H heads of nope + v)
    q_rope, k_rope = RoPE(q_rope), RoPE(k_r)   one rotary key for all heads,
                                               pairs interleaved (2i, 2i+1)
    scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)
    o = W_o concat_h(softmax(scores) v)

What a token leaves behind is ``c_kv`` after its norm and ``k_rope``
after RoPE: ``kv_lora_rank + qk_rope_head_dim`` values a layer, which
is what the serving cache holds (``LatentBlock.cache``). Attention over
that cache runs in the absorbed form (``W_uk`` folded into the query,
``W_uv`` applied to the weighted latent rows) or, where queries are
many, expands the gathered rows: ``ops/paged_attention.py::
latent_attention_chunk`` takes the form from the shapes.

**Feed-forward**: the first ``n_dense_layers`` layers a gated SiLU MLP
``W_down(silu(W_gate h) * W_up h)``; the others experts:

    s = sigmoid(W_r h)                    float32, all n_routed_experts
    chosen = top_k(s + b)                 b chooses only (noaux_tc)
    g_i = scaling * s_i / sum_chosen s_j
    y = sum_{i chosen and held} g_i E_i(h) + E_shared(h)

**Experts held.** The layer routes over all ``n_routed_experts`` and
holds ``n_routed_experts / ep_size`` of them, the contiguous block of
rank ``ep_rank``: it computes its own experts' part of ``y`` (and the
shared expert, which every rank computes alike) and nothing for the
experts it lacks. ``ep_size=1`` is the whole layer. The gate weights
are normalised over all chosen experts, held or not, so the parts of
all ranks add up to the uncut layer. No token is dropped: each held
expert runs over the tokens that picked it, one grouped product for all
of them (``models/experts.py``), and its output is weighted by the
token's gate for it.

The multi-token-prediction module of the published family is not here.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from distributed_training_tpu.models.base import ApplyLM, normal_init
from distributed_training_tpu.models.experts import (  # noqa: F401
    COUNTERS, _cast, expert_layer, ffn_scope, gated_mlp, rms_norm, route)

@dataclass
class LatentMoEConfig:
    vocab_size: int = 129280
    d_model: int = 2048
    n_layers: int = 5
    n_dense_layers: int = 1
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 7168
    moe_d_ff: int = 768
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    moe_top_k: int = 8
    routed_scaling_factor: float = 2.5
    ep_size: int = 1              # chips a layer's experts lie on
    ep_rank: int = 0              # which of them this is
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: str = "bfloat16"       # compute dtype
    param_dtype: str = "float32"

    # How ``models/experts.py::expert_layer`` scores and activates.
    router_score = "sigmoid"
    expert_act = "silu"
    # What ``project`` multiplies the two latents by after their norms:
    # nothing here (``models/sparse_latent_moe.py`` rescales).
    q_scale = 1.0
    kv_scale = 1.0

    def __post_init__(self):
        if not 0 < self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"n_dense_layers ({self.n_dense_layers}) must be in "
                f"[1, n_layers={self.n_layers}]")
        if self.n_routed_experts % self.ep_size:
            raise ValueError(
                f"{self.n_routed_experts} experts do not divide over "
                f"ep_size={self.ep_size}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} not in "
                             f"[0, {self.ep_size})")
        if self.moe_top_k > self.n_routed_experts:
            raise ValueError("moe_top_k exceeds n_routed_experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.ep_size

    @property
    def expert_offset(self) -> int:
        return self.ep_rank * self.experts_held

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def rope_interleaved(x, positions, theta):
    """RoPE on the last axis of ``x (*positions.shape, ..., d)``, pairs
    ``(2i, 2i+1)`` rotated by ``positions * theta^(-2i/d)``, layout
    kept."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs
    angles = angles.reshape(positions.shape
                            + (1,) * (x.ndim - positions.ndim - 1)
                            + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _scaled(x, scale: float):
    return x if scale == 1.0 else x * jnp.asarray(scale, x.dtype)


def query_latent(h, a, c, w=_cast):
    """The query's latent ``c_q (..., q_lora_rank)`` of the normed
    input ``h``: after its norm, times ``c.q_scale``."""
    dt = h.dtype
    return _scaled(
        rms_norm(jnp.einsum("...d,dr->...r", h, w(a["wdq"], dt)),
                 a["q_norm"], c.rms_norm_eps), c.q_scale)


def project(h, a, positions, c, w=_cast, c_q=None):
    """The normed input ``h (..., D)`` at ``positions (...)`` ->
    ``q_nope (..., H, nope)``, ``q_rope (..., H, rope)`` after RoPE,
    and what the token leaves behind: ``c_kv (..., rank)`` after its
    norm (times ``c.kv_scale``), ``k_rope (..., rope)`` after RoPE.
    ``c``: the sizes (``qk_nope_head_dim``, ``kv_lora_rank``,
    ``rope_theta``, ``rms_norm_eps``, the two scales), a
    ``LatentMoEConfig`` or one kind of layer of a model with several
    (``models/sparse_latent_moe.py``); ``c_q``: ``query_latent``'s,
    where the caller has made it for a second reader."""
    dt = h.dtype
    if c_q is None:
        c_q = query_latent(h, a, c, w)
    q = jnp.einsum("...r,rhk->...hk", c_q, w(a["wuq"], dt))
    q_nope = q[..., :c.qk_nope_head_dim]
    q_rope = rope_interleaved(q[..., c.qk_nope_head_dim:], positions,
                              c.rope_theta)
    ckv = jnp.einsum("...d,dr->...r", h, w(a["wdkv"], dt))
    c_kv = _scaled(rms_norm(ckv[..., :c.kv_lora_rank], a["kv_norm"],
                            c.rms_norm_eps), c.kv_scale)
    k_rope = rope_interleaved(ckv[..., c.kv_lora_rank:], positions,
                              c.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def expanded_attention(q_nope, q_rope, c_kv, k_rope, a,
                       c: LatentMoEConfig, w=_cast):
    """Causal attention of ``(B, S)`` tokens over themselves with the
    keys and values expanded from the latent rows: ``(B, S, H, v)``."""
    from distributed_training_tpu.ops.attention import (
        dot_product_attention)

    dt = q_nope.dtype
    kv = jnp.einsum("...r,rhk->...hk", c_kv, w(a["wukv"], dt))
    k = jnp.concatenate(
        [kv[..., :c.qk_nope_head_dim],
         jnp.broadcast_to(k_rope[..., None, :],
                          kv.shape[:-1] + (c.qk_rope_head_dim,))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # Naive: the flash kernels take one width for q, k and v.
    return dot_product_attention(q, k, kv[..., c.qk_nope_head_dim:],
                                 causal=True, impl="naive")


class LatentMoE(ApplyLM):
    """Functional model: ``init``, ``apply`` (the full forward),
    ``loss`` and ``generate`` by it (``ApplyLM``), and
    ``serving_block`` for the engine."""

    def __init__(self, cfg: LatentMoEConfig):
        self.cfg = cfg

    def init(self, rng: jax.Array):
        c = self.cfg
        pdt = jnp.dtype(c.param_dtype)
        std = 0.02
        out_std = std / (2 * c.n_layers) ** 0.5
        D, H = c.d_model, c.n_heads

        def run(key, L, mlp):
            """A run of ``L`` like layers, stacked."""
            k = iter(jax.random.split(key, 8))
            return {
                "ln1": jnp.ones((L, D), pdt),
                "ln2": jnp.ones((L, D), pdt),
                "attn": {
                    "wdq": normal_init(next(k), (L, D, c.q_lora_rank),
                                       std, pdt),
                    "q_norm": jnp.ones((L, c.q_lora_rank), pdt),
                    "wuq": normal_init(
                        next(k), (L, c.q_lora_rank, H, c.qk_head_dim),
                        std, pdt),
                    "wdkv": normal_init(
                        next(k),
                        (L, D, c.kv_lora_rank + c.qk_rope_head_dim),
                        std, pdt),
                    "kv_norm": jnp.ones((L, c.kv_lora_rank), pdt),
                    "wukv": normal_init(
                        next(k), (L, c.kv_lora_rank, H,
                                  c.qk_nope_head_dim + c.v_head_dim),
                        std, pdt),
                    "wo": normal_init(next(k), (L, H, c.v_head_dim, D),
                                      out_std, pdt),
                },
                "mlp": mlp(next(k), L),
            }

        def gated(key, lead, F):
            k = jax.random.split(key, 3)
            return {"wg": normal_init(k[0], lead + (D, F), std, pdt),
                    "wu": normal_init(k[1], lead + (D, F), std, pdt),
                    "wd": normal_init(k[2], lead + (F, D), out_std,
                                      pdt)}

        def experts(key, L):
            k = jax.random.split(key, 4)
            return {
                "router": normal_init(k[0], (L, D, c.n_routed_experts),
                                      std, pdt),
                "router_bias": normal_init(
                    k[1], (L, c.n_routed_experts), std, pdt),
                **gated(k[2], (L, c.experts_held), c.moe_d_ff),
                "shared": gated(k[3], (L,),
                                c.n_shared_experts * c.moe_d_ff),
            }

        keys = jax.random.split(rng, 4)
        params = {
            "tok_embed": normal_init(keys[0], (c.vocab_size, D), std,
                                     pdt),
            "lm_head": normal_init(keys[1], (D, c.vocab_size), std, pdt),
            "final_norm": jnp.ones((D,), pdt),
            "dense": run(keys[2], c.n_dense_layers,
                         lambda k, L: gated(k, (L,), c.d_ff)),
        }
        if c.n_layers > c.n_dense_layers:
            params["moe"] = run(keys[3], c.n_layers - c.n_dense_layers,
                                experts)
        return params

    def runs(self, params):
        """The stacked runs of like layers, in layer order."""
        return tuple(params[k] for k in ("dense", "moe") if k in params)

    def feed_forward(self, layer, h, valid=None, w=_cast):
        """``FFN(h)`` of a layer of either kind, and its counts."""
        if "router" in layer["mlp"]:
            # ``route`` and ``gated_mlp`` by this module's names: the
            # benchmark's controls patch them here
            # (perfbench/tests/check_serving_sensitivity.py).
            return expert_layer(h, layer["mlp"], self.cfg, valid, w,
                                route=route, shared=gated_mlp)
        return (gated_mlp(h, layer["mlp"], w),
                jnp.zeros((len(COUNTERS),), jnp.int32))

    def apply(self, params, tokens: jax.Array, rng=None,
              train: bool = False) -> jax.Array:
        """tokens (B, S) -> logits (B, S, V) float32."""
        del rng, train
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        x = params["tok_embed"][tokens].astype(dt)

        def body(x, layer):
            h = rms_norm(x, layer["ln1"], c.rms_norm_eps)
            attn = expanded_attention(
                *project(h, layer["attn"], positions, c),
                layer["attn"], c)
            x = x + jnp.einsum("...hk,hkd->...d", attn,
                               layer["attn"]["wo"].astype(dt))
            h = rms_norm(x, layer["ln2"], c.rms_norm_eps)
            return x + self.feed_forward(layer, h)[0], None

        for run in self.runs(params):
            x, _ = jax.lax.scan(body, x, run)
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        return jnp.einsum("...d,dv->...v", x,
                          params["lm_head"].astype(dt)
                          ).astype(jnp.float32)

    def serving_block(self):
        return LatentBlock(self)


class LatentBlock:
    """This model behind ``serving/blocks.py``'s interface. The pool's
    ``k_pages`` hold the latent rows (``kv_lora_rank`` wide), its
    ``v_pages`` the rotary keys, one row a token a layer each."""

    counters = COUNTERS

    def __init__(self, model: LatentMoE):
        from distributed_training_tpu.serving.blocks import weight

        c = model.cfg
        self.model = model
        self.cfg = c
        self._w = weight              # int8 leaves dequantised at compute
        self.cache = dict(n_layers=c.n_layers, n_kv_heads=1,
                          head_dim=c.kv_lora_rank,
                          v_head_dim=c.qk_rope_head_dim, kind="latent")

    def embed(self, params, tokens, positions):
        del positions
        return params["tok_embed"][tokens].astype(
            jnp.dtype(self.cfg.dtype))

    def segments(self, params):
        return self.model.runs(params)

    def at(self, layer):
        del layer
        return self

    def project(self, layer, x, positions):
        c = self.cfg
        h = rms_norm(x, layer["ln1"], c.rms_norm_eps)
        q_nope, q_rope, c_kv, k_rope = project(
            h, layer["attn"], positions, c, self._w)
        return ((q_nope, q_rope), c_kv[..., None, :],
                k_rope[..., None, :])

    def _uk_uv(self, layer, dt):
        wukv = self._w(layer["attn"]["wukv"], dt)
        n = self.cfg.qk_nope_head_dim
        return wukv[..., :n], wukv[..., n:]

    def attend_chunk(self, layer, q, kp, vp, page_rows, q_pos):
        from distributed_training_tpu.ops.paged_attention import (
            latent_attention_chunk)

        w_uk, w_uv = self._uk_uv(layer, q[0].dtype)
        return latent_attention_chunk(q[0], q[1], kp, vp, page_rows,
                                      q_pos, w_uk, w_uv)

    def finish(self, layer, x, attn, valid):
        c = self.cfg
        with jax.named_scope("dtt.attn.out"):
            x = x + jnp.einsum("...hk,hkd->...d", attn,
                               self._w(layer["attn"]["wo"], x.dtype))
        with jax.named_scope(ffn_scope(layer)):
            h = rms_norm(x, layer["ln2"], c.rms_norm_eps)
            y, counts = self.model.feed_forward(layer, h, valid,
                                                self._w)
            return x + y, counts

    def logits(self, params, x):
        x = rms_norm(x, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,dv->...v", x,
                          self._w(params["lm_head"], x.dtype)
                          ).astype(jnp.float32)


def build_latent_moe(loss: str = "auto", dtype: str = "bfloat16",
                     **kwargs) -> LatentMoE:
    """Registry entrypoint (``build_model("latent_moe", ...)``)."""
    if loss not in ("auto", "xent"):
        raise ValueError(f"latent_moe has one loss (xent), got {loss!r}")
    kwargs.setdefault("dtype", dtype)
    return LatentMoE(LatentMoEConfig(**kwargs))
