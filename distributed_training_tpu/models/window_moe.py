"""Decoder that mixes window and global attention layers, NoPE and RoPE
layers, over softmax-routed ReGLU experts held in part.

The SmallThinker layout (huggingface.co/PowerInfer/SmallThinker-21BA3B-
Instruct), which the benchmark's ``smallthinker-21b-ep4`` configuration
publishes its sizes in. Layer ``l`` on the residual stream ``x``, with
``RMSNorm`` scale only and no biases anywhere:

    h   = RMSNorm(x; g1)
    r   = h W_r                      float32, all n_routed_experts: the
                                     router reads the ATTENTION's input
    q, k, v = h W_q, h W_k, h W_v    n_heads / n_kv_heads heads of head_dim
    q, k = RoPE(q, k; rope_theta)    where rope_layout[l], halves paired;
                                     else no positions at all (NoPE)
    a   = softmax(q k^T / sqrt(head_dim) + mask) v      GQA; mask j <= i
                                     and, where window_layout[l], i - j < window
    x1  = x + a W_o
    h2  = RMSNorm(x1; g2)
    s, e = top_k(r);  p = softmax(s)                 over the chosen
    y   = sum_k p_k W_d[e_k] (relu(W_g[e_k] h2) * W_u[e_k] h2)   no shared expert
    x2  = x1 + y

then a final RMSNorm and an untied head. The experts are
``models/experts.py::expert_layer`` (``router_score = "softmax"``,
``expert_act = "relu"``, no shared expert), told which experts it holds
(``ep_size`` / ``ep_rank``) and handed ``r``. ``vocab_size`` is the rows
of the embedding and the head the model HOLDS: token ids, logits and
sampling are over them.

Layers are stored a RUN of like layers at a time (``params["runs"]``,
``WindowMoEConfig.runs``: same window flag and same RoPE flag), each
stacked, so that the forward and the engine scan a run and no program
slices a stack of weights apart.

Served, a window layer keeps the last ``window`` rows of a sequence in
a ring of pages and a global layer all of them in a table
(``WindowBlock.cache``; ``serving/kv_cache.py``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from distributed_training_tpu.models.base import ApplyLM, normal_init
from distributed_training_tpu.models.experts import (
    COUNTERS, _cast, expert_layer, rms_norm, router_logits)


@dataclass
class WindowMoEConfig:
    vocab_size: int = 37984       # rows of embedding and head held
    d_model: int = 2560
    n_layers: int = 12
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_d_ff: int = 768
    n_routed_experts: int = 64
    moe_top_k: int = 6
    ep_size: int = 1              # chips a layer's experts lie on
    ep_rank: int = 0              # which of them this is
    window: int = 4096            # keys a window layer sees, own included
    window_layout: tuple = (0, 1, 1, 1) * 3    # 1: a window layer
    rope_layout: tuple = (0, 1, 1, 1) * 3      # 1: RoPE; 0: NoPE
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    qk_std: float = 0.02          # init std of W_q and W_k
    max_seq_len: int = 16384
    dtype: str = "bfloat16"       # compute dtype
    param_dtype: str = "float32"

    # How ``models/experts.py::expert_layer`` scores and activates,
    # and which elements of a head RoPE pairs (``project``).
    router_score = "softmax"
    expert_act = "relu"
    rope_pairs = "halves"

    def __post_init__(self):
        self.window_layout = tuple(int(f) for f in self.window_layout)
        self.rope_layout = tuple(int(f) for f in self.rope_layout)
        for name in ("window_layout", "rope_layout"):
            if len(getattr(self, name)) != self.n_layers:
                raise ValueError(
                    f"{name} has {len(getattr(self, name))} entries for "
                    f"n_layers={self.n_layers}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {self.n_heads} not divisible by "
                             f"n_kv_heads {self.n_kv_heads}")
        if self.n_routed_experts % self.ep_size:
            raise ValueError(
                f"{self.n_routed_experts} experts do not divide over "
                f"ep_size={self.ep_size}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} not in "
                             f"[0, {self.ep_size})")
        if self.moe_top_k > self.n_routed_experts:
            raise ValueError("moe_top_k exceeds n_routed_experts")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        if any(self.window_layout) and self.window < 1:
            raise ValueError("window layers need window >= 1")

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.ep_size

    @property
    def expert_offset(self) -> int:
        return self.ep_rank * self.experts_held

    @property
    def runs(self) -> tuple:
        """``(first layer, layers, window flag, rope flag)`` of each
        maximal run of like layers, in layer order."""
        kinds = list(zip(self.window_layout, self.rope_layout))
        out, lo = [], 0
        for n in range(1, self.n_layers + 1):
            if n == self.n_layers or kinds[n] != kinds[lo]:
                out.append((lo, n - lo) + kinds[lo])
                lo = n
        return tuple(out)


def project(h, a, positions, rope: bool, c: WindowMoEConfig, w=_cast):
    """The normed input ``h (..., D)`` at ``positions (...)`` -> ``q
    (..., H, hd)``, ``k`` and ``v (..., Hkv, hd)``, rotated where the
    layer has positions: RoPE at base ``rope_theta`` with
    ``c.rope_pairs`` ``"halves"`` (``x[i]`` with ``x[i + hd/2]``) or
    ``"adjacent"`` (``x[2i]`` with ``x[2i + 1]``)."""
    from distributed_training_tpu.models.latent_moe import (
        rope_interleaved)
    from distributed_training_tpu.serving.blocks import rope_bhd

    dt = h.dtype
    q = jnp.einsum("...d,dhk->...hk", h, w(a["wq"], dt))
    k = jnp.einsum("...d,dhk->...hk", h, w(a["wk"], dt))
    v = jnp.einsum("...d,dhk->...hk", h, w(a["wv"], dt))
    if rope:
        turn = {"halves": rope_bhd,
                "adjacent": rope_interleaved}[c.rope_pairs]
        q = turn(q, positions, c.rope_theta)
        k = turn(k, positions, c.rope_theta)
    return q, k, v


class WindowMoE(ApplyLM):
    """Functional model: ``init``, ``apply`` (the full forward),
    ``loss`` and ``generate`` by it (``ApplyLM``), and
    ``serving_block`` for the engine."""

    def __init__(self, cfg: WindowMoEConfig):
        self.cfg = cfg

    def init(self, rng: jax.Array):
        """Normal(0, 0.02) leaves (output projections over ``sqrt(2
        n_layers)``, norms ones) but ``W_q`` and ``W_k``, which take
        ``cfg.qk_std``: the width of the attention logits of a seeded
        model is theirs, and at 0.02 a softmax over thousands of keys is
        near uniform, so that what a layer attends to (a window, a
        position) would hardly move its output."""
        c = self.cfg
        pdt = jnp.dtype(c.param_dtype)
        std = 0.02
        out_std = std / (2 * c.n_layers) ** 0.5
        D, H, Hkv, hd, F = (c.d_model, c.n_heads, c.n_kv_heads,
                            c.head_dim, c.moe_d_ff)
        E = c.experts_held

        def run(key, L):
            k = jax.random.split(key, 8)
            return {
                "ln1": jnp.ones((L, D), pdt),
                "ln2": jnp.ones((L, D), pdt),
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
                },
            }

        keys = jax.random.split(rng, 2 + len(c.runs))
        return {
            "tok_embed": normal_init(keys[0], (c.vocab_size, D), std,
                                     pdt),
            "lm_head": normal_init(keys[1], (D, c.vocab_size), std, pdt),
            "final_norm": jnp.ones((D,), pdt),
            "runs": tuple(run(k, n) for k, (_lo, n, _w, _r)
                          in zip(keys[2:], c.runs)),
        }

    def apply(self, params, tokens: jax.Array, rng=None,
              train: bool = False) -> jax.Array:
        """tokens (B, S) -> logits (B, S, V) float32."""
        from distributed_training_tpu.ops.attention import (
            dot_product_attention)

        del rng, train
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        x = params["tok_embed"][tokens].astype(dt)
        for layers, (_lo, _n, window, rope) in zip(params["runs"],
                                                   c.runs):
            def body(x, layer, window=window, rope=rope):
                def attend(q, k, v):
                    # Naive: the flash kernels want tile-friendly
                    # shapes, and this is the plain path.
                    return dot_product_attention(
                        q, k, v, causal=True, impl="naive",
                        window=c.window if window else 0)
                return self.layer(layer, x, positions, rope,
                                  attend), None
            x, _ = jax.lax.scan(body, x, layers)
        return self.head(params, x)

    def layer(self, layer, x, positions, rope: bool, attend):
        """One layer of the full forward on ``x (B, S, D)``;
        ``attend(q, k, v)`` is the layer's attention over the
        sequence itself."""
        c = self.cfg
        h = rms_norm(x, layer["ln1"], c.rms_norm_eps)
        r = router_logits(h, layer["mlp"]["router"])
        attn = attend(*project(h, layer["attn"], positions, rope, c))
        x = x + jnp.einsum("...hk,hkd->...d", attn,
                           layer["attn"]["wo"].astype(x.dtype))
        h = rms_norm(x, layer["ln2"], c.rms_norm_eps)
        return x + expert_layer(h, layer["mlp"], c, logits=r)[0]

    def head(self, params, x, w=_cast):
        """Final hidden states -> float32 logits over the rows held."""
        x = rms_norm(x, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,dv->...v", x,
                          w(params["lm_head"], x.dtype)
                          ).astype(jnp.float32)

    def serving_block(self):
        return WindowBlock(self)


class WindowBlock:
    """This model behind ``serving/blocks.py``'s interface: keys and
    values a kv head in the two pools, a window layer's in the window
    pool's rings (``cache``: ``window``, ``window_layers``).

    The router's logits are made in ``project``, where the attention's
    normed input ``h`` is, and handed on with the queries: ``project``
    returns ``q = (heads, logits)``, ``attend_chunk`` passes the logits
    through beside the attention's output, and ``finish`` takes both.
    Not made again in ``finish`` by norming ``x`` a second time: that
    would read and norm the residual stream twice a layer to make the
    same 64 numbers a token, and two products of one tensor in two
    places are two chances to differ."""

    counters = COUNTERS

    def __init__(self, model: WindowMoE):
        from distributed_training_tpu.serving.blocks import weight

        c = model.cfg
        self.model = model
        self.cfg = c
        self._w = weight              # int8 leaves dequantised at compute
        self.cache = dict(
            n_layers=c.n_layers, n_kv_heads=c.n_kv_heads,
            head_dim=c.head_dim, kind="kv", window=c.window,
            window_layers=tuple(n for n, f in enumerate(c.window_layout)
                                if f),
            block=type(self).__name__)
        self._views = {lo: self.run_view(bool(window), bool(rope))
                       for lo, _n, window, rope in c.runs}

    def run_view(self, window: bool, rope: bool):
        return _Run(self, window, rope)

    def embed(self, params, tokens, positions):
        del positions
        return params["tok_embed"][tokens].astype(
            jnp.dtype(self.cfg.dtype))

    def segments(self, params):
        return tuple(params["runs"])

    def at(self, layer):
        return self._views[layer]

    def logits(self, params, x):
        return self.model.head(params, x, self._w)


class _Run:
    """``WindowBlock`` as one run of like layers sees it
    (``WindowBlock.at``): whether the run's layers are window layers
    and whether they rotate."""

    def __init__(self, block: WindowBlock, window: bool, rope: bool):
        self.block, self.window, self.rope = block, window, rope

    def project(self, layer, x, positions):
        b = self.block
        h = rms_norm(x, layer["ln1"], b.cfg.rms_norm_eps)
        q, k, v = project(h, layer["attn"], positions, self.rope, b.cfg,
                          b._w)
        with jax.named_scope("dtt.moe.route"):
            logits = router_logits(h, b._w(layer["mlp"]["router"],
                                           jnp.float32))
        return (q, logits), k, v

    def attend_chunk(self, layer, q, kp, vp, page_rows, q_pos):
        from distributed_training_tpu.ops.paged_attention import (
            paged_attention_chunk)

        del layer
        heads, logits = q
        return paged_attention_chunk(
            heads, kp, vp, page_rows, q_pos,
            window=self.block.cfg.window if self.window else None,
            ring=self.window), logits

    def finish(self, layer, x, attn, valid):
        b = self.block
        attn, logits = attn
        with jax.named_scope("dtt.attn.out"):
            x = x + jnp.einsum("...hk,hkd->...d", attn,
                               b._w(layer["attn"]["wo"], x.dtype))
        with jax.named_scope("dtt.moe.experts"):
            h = rms_norm(x, layer["ln2"], b.cfg.rms_norm_eps)
            y, counts = expert_layer(h, layer["mlp"], b.cfg, valid,
                                     b._w, logits=logits)
            return x + y, counts


def build_window_moe(loss: str = "auto", dtype: str = "bfloat16",
                     **kwargs) -> WindowMoE:
    """Registry entrypoint (``build_model("window_moe", ...)``)."""
    if loss not in ("auto", "xent"):
        raise ValueError(f"window_moe has one loss (xent), got {loss!r}")
    kwargs.setdefault("dtype", dtype)
    return WindowMoE(WindowMoEConfig(**kwargs))
