"""Decoder with latent attention of TWO kinds: full layers whose queries
attend a learned selection of the positions before them, window layers
with a latent of their own width over the last few hundred, a headwise
gate on both, over sigmoid-routed experts held in part.

The layout ``dots3-note-prev`` publishes its sizes in (the benchmark's
``dots3-note-ep8`` configuration). Layer ``l`` on the residual stream
``x``, ``h = RMSNorm(x)`` (scale only), no biases but the indexer's
LayerNorm:

    x = x + Attn_l(h) ;  x = x + FFN_l(RMSNorm(x))

**Attention** is multi-head latent attention (``models/latent_moe.py``:
``query_latent``, ``project``, shared with that model, the sizes a kind
of layer as arguments, ``SparseLatentMoEConfig.dims``) with the two
latents RESCALED after their norms (``qkv_lora_rescale``):

    c_q = s_q RMSNorm(h W_dq)          s_q = sqrt(d_model / q_lora_rank)
    [c_kv ; k_r] = h W_dkv ;  c_kv = s_kv RMSNorm(c_kv)
                                       s_kv = sqrt(d_model / kv_lora_rank)
    q_h = c_q W_uq,h (nope + rope, the rope part under RoPE, pairs
    interleaved);  k_h,s = [c_kv,s W_uk,h ; RoPE(k_r,s)];  v_h,s = c_kv,s W_uv,h
    a_t,h = softmax over s in S_t of q_t,h . k_h,s / sqrt(nope + rope)
    g_t = sigmoid(h_t W_g)             one gate a head, of the layer's input
    Attn = concat_h(g_t,h sum_s a_t,h,s v_h,s) W_o

What differs between the kinds is ``S_t`` and the sizes:

- a *full* layer (``layer_types[l] == "full_attention"``) has an INDEXER:
  ``qI_j = c_q W_iq,j`` (``index_n_heads`` heads of ``index_head_dim``),
  ``kI_s = LayerNorm(h_s W_ik)``, the first ``qk_rope_head_dim`` of each
  under RoPE, ``w = h W_iw``; ``I(t, s) = sum_j w_t,j relu(qI_t,j .
  kI_s)`` for ``s <= t``, and ``S_t`` the ``index_topk`` positions of
  largest ``I(t, .)``, all of them while ``t < index_topk``;
- a *window* layer (``"sliding_attention"``) has the ``swa_*`` sizes and
  ``S_t = {s : 0 <= t - s < window}``.

**Feed-forward**: the first ``n_dense_layers`` a gated SiLU MLP, the
others ``models/experts.py::expert_layer`` (sigmoid scores, selection
bias, the gates over the chosen scores' sum, a shared expert), told
which experts it holds (``ep_size`` / ``ep_rank``). ``vocab_size`` is
the rows of the embedding and the head the model HOLDS.

Layers are stored a RUN of like layers at a time (``params["runs"]``,
``SparseLatentMoEConfig.runs``: same kind of attention and of
feed-forward), each stacked.

Served, a token leaves behind ``c_kv`` and ``RoPE(k_r)`` in every layer
and ``kI`` in a full one: a full layer's rows in a table that grows, its
index key in the index pool at the same page and slot, a window layer's
rows (of ITS width) in a ring (``SparseLatentBlock.cache``;
``serving/kv_cache.py``). The multi-token-prediction module of the
published recipe is not here.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from distributed_training_tpu.models.base import ApplyLM, normal_init
from distributed_training_tpu.models.experts import (
    COUNTERS, _cast, expert_layer, ffn_scope, gated_mlp, rms_norm)
from distributed_training_tpu.models.latent_moe import (
    project, query_latent, rope_interleaved)

FULL, SLIDING = "full_attention", "sliding_attention"

# The block's counters beside the experts': visible positions the
# indexer scored, and positions attended after its selection, summed
# over full layers and queries.
INDEX_COUNTERS = ("index_keys_scored", "index_keys_kept")


@dataclass(frozen=True)
class LatentDims:
    """One kind of layer's sizes, as ``latent_moe.project`` reads
    them."""

    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    q_scale: float
    kv_scale: float


@dataclass
class SparseLatentMoEConfig:
    vocab_size: int = 19008       # rows of embedding and head held
    d_model: int = 5120
    n_layers: int = 5
    n_dense_layers: int = 1
    layer_types: tuple = (FULL, FULL, SLIDING, SLIDING, SLIDING)
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    window: int = 513             # keys a window layer sees, own included
    qkv_lora_rescale: bool = True
    d_ff: int = 13824
    moe_d_ff: int = 1536
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    moe_top_k: int = 8
    routed_scaling_factor: float = 1.0
    ep_size: int = 1              # chips a layer's experts lie on
    ep_rank: int = 0              # which of them this is
    rms_norm_eps: float = 1e-5
    qk_std: float = 0.02          # init std of W_uq and W_uk
    max_seq_len: int = 16384
    dtype: str = "bfloat16"       # compute dtype
    param_dtype: str = "float32"

    # How ``models/experts.py::expert_layer`` scores and activates.
    router_score = "sigmoid"
    expert_act = "silu"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"n_layers={self.n_layers}")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types are {FULL!r} or {SLIDING!r}, "
                             f"got {self.layer_types}")
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
        if self.qk_rope_head_dim % 2 or self.swa_qk_rope_head_dim % 2:
            raise ValueError("rope head dims must be even")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("the indexer rotates qk_rope_head_dim of "
                             "its index_head_dim")
        if SLIDING in self.layer_types and self.window < 1:
            raise ValueError("window layers need window >= 1")
        if self.index_topk < 1:
            raise ValueError("index_topk must be >= 1")

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.ep_size

    @property
    def expert_offset(self) -> int:
        return self.ep_rank * self.experts_held

    def dims(self, window: bool) -> LatentDims:
        """A window layer's sizes, or a full layer's."""
        pre = "swa_" if window else ""
        q_rank, kv_rank = (getattr(self, pre + "q_lora_rank"),
                           getattr(self, pre + "kv_lora_rank"))
        rescale = self.qkv_lora_rescale
        return LatentDims(
            getattr(self, pre + "n_heads"), q_rank, kv_rank,
            getattr(self, pre + "qk_nope_head_dim"),
            getattr(self, pre + "qk_rope_head_dim"),
            getattr(self, pre + "v_head_dim"),
            getattr(self, pre + "rope_theta"), self.rms_norm_eps,
            (self.d_model / q_rank) ** 0.5 if rescale else 1.0,
            (self.d_model / kv_rank) ** 0.5 if rescale else 1.0)

    @property
    def runs(self) -> tuple:
        """``(first layer, layers, window flag, dense flag)`` of each
        maximal run of like layers, in layer order."""
        kinds = [(t == SLIDING, n < self.n_dense_layers)
                 for n, t in enumerate(self.layer_types)]
        out, lo = [], 0
        for n in range(1, self.n_layers + 1):
            if n == self.n_layers or kinds[n] != kinds[lo]:
                out.append((lo, n - lo) + kinds[lo])
                lo = n
        return tuple(out)


def index_project(h, c_q, ix, positions, c: SparseLatentMoEConfig,
                  w=_cast):
    """The indexer's side of a full layer, of the normed input ``h
    (..., D)`` and the query's latent ``c_q``: its queries ``(..., J,
    d)`` and key ``(..., d)``, the first ``qk_rope_head_dim`` of each
    under RoPE, and the heads' weights ``(..., J)`` float32."""
    from distributed_training_tpu.serving.blocks import layer_norm

    dt = h.dtype
    rope = c.qk_rope_head_dim

    def rotated(x):
        return jnp.concatenate(
            [rope_interleaved(x[..., :rope], positions, c.rope_theta),
             x[..., rope:]], axis=-1)

    q = jnp.einsum("...r,rjd->...jd", c_q, w(ix["wq"], dt))
    k = layer_norm(jnp.einsum("...d,de->...e", h, w(ix["wk"], dt)),
                   ix["k_norm"]["scale"], ix["k_norm"]["bias"])
    weights = jnp.einsum("...d,dj->...j", h, w(ix["ww"], dt))
    return rotated(q), rotated(k), weights.astype(jnp.float32)


def head_gate(h, a, w=_cast):
    """``sigmoid(h W_g)``: one gate a head, ``(..., H)``."""
    g = jnp.einsum("...d,dh->...h", h, w(a["wg"], h.dtype))
    return jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype)


def masked_attention(q_nope, q_rope, c_kv, k_rope, a, d: LatentDims,
                     mask, w=_cast):
    """Attention of ``(B, S)`` tokens over themselves where ``mask (B,
    S, S)`` holds, the keys and values expanded from the latent rows:
    ``(B, S, H, v)``. The plain path (``apply``)."""
    dt = q_nope.dtype
    kv = jnp.einsum("...r,rhk->...hk", c_kv, w(a["wukv"], dt))
    n = d.qk_nope_head_dim
    scores = (jnp.einsum("bshn,bkhn->bhsk", q_nope, kv[..., :n],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshe,bke->bhsk", q_rope, k_rope,
                           preferred_element_type=jnp.float32))
    scores = jnp.where(mask[:, None],
                       scores * (n + d.qk_rope_head_dim) ** -0.5,
                       -jnp.inf)
    return jnp.einsum("bhsk,bkhv->bshv",
                      jax.nn.softmax(scores, axis=-1).astype(dt),
                      kv[..., n:],
                      preferred_element_type=jnp.float32).astype(dt)


class SparseLatentMoE(ApplyLM):
    """Functional model: ``init``, ``apply`` (the full forward),
    ``loss`` and ``generate`` by it (``ApplyLM``), and
    ``serving_block`` for the engine."""

    def __init__(self, cfg: SparseLatentMoEConfig):
        self.cfg = cfg

    def init(self, rng: jax.Array):
        """Normal(0, 0.02) leaves (output projections over ``sqrt(2
        n_layers)``, norms ones and zeros) but ``W_uq`` and ``W_uk``,
        which take ``cfg.qk_std``: the width of the attention logits of
        a seeded model is theirs."""
        c = self.cfg
        pdt = jnp.dtype(c.param_dtype)
        std = 0.02
        out_std = std / (2 * c.n_layers) ** 0.5
        D = c.d_model

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

        def run(key, L, window, dense):
            d = c.dims(window)
            H, n = d.n_heads, d.qk_nope_head_dim
            k = iter(jax.random.split(key, 16))
            out = {
                "ln1": jnp.ones((L, D), pdt),
                "ln2": jnp.ones((L, D), pdt),
                "attn": {
                    "wdq": normal_init(next(k), (L, D, d.q_lora_rank),
                                       std, pdt),
                    "q_norm": jnp.ones((L, d.q_lora_rank), pdt),
                    "wuq": normal_init(
                        next(k), (L, d.q_lora_rank, H,
                                  n + d.qk_rope_head_dim), c.qk_std,
                        pdt),
                    "wdkv": normal_init(
                        next(k),
                        (L, D, d.kv_lora_rank + d.qk_rope_head_dim),
                        std, pdt),
                    "kv_norm": jnp.ones((L, d.kv_lora_rank), pdt),
                    "wukv": jnp.concatenate([
                        normal_init(next(k), (L, d.kv_lora_rank, H, n),
                                    c.qk_std, pdt),
                        normal_init(next(k), (L, d.kv_lora_rank, H,
                                              d.v_head_dim), std, pdt)],
                        axis=-1),
                    "wg": normal_init(next(k), (L, D, H), std, pdt),
                    "wo": normal_init(next(k), (L, H, d.v_head_dim, D),
                                      out_std, pdt),
                },
                "mlp": (gated(next(k), (L,), c.d_ff) if dense
                        else experts(next(k), L)),
            }
            if not window:
                J, di = c.index_n_heads, c.index_head_dim
                out["index"] = {
                    "wq": normal_init(next(k), (L, d.q_lora_rank, J, di),
                                      std, pdt),
                    "wk": normal_init(next(k), (L, D, di), std, pdt),
                    "k_norm": {"scale": jnp.ones((L, di), pdt),
                               "bias": jnp.zeros((L, di), pdt)},
                    "ww": normal_init(next(k), (L, D, J), std, pdt),
                }
            return out

        keys = jax.random.split(rng, 2 + len(c.runs))
        return {
            "tok_embed": normal_init(keys[0], (c.vocab_size, D), std,
                                     pdt),
            "lm_head": normal_init(keys[1], (D, c.vocab_size), std, pdt),
            "final_norm": jnp.ones((D,), pdt),
            "runs": tuple(run(k, n, window, dense)
                          for k, (_lo, n, window, dense)
                          in zip(keys[2:], c.runs)),
        }

    def feed_forward(self, layer, h, valid=None, w=_cast):
        """``FFN(h)`` of a layer of either kind, and its counts."""
        if "router" in layer["mlp"]:
            return expert_layer(h, layer["mlp"], self.cfg, valid, w)
        return (gated_mlp(h, layer["mlp"], w),
                jnp.zeros((len(COUNTERS),), jnp.int32))

    def apply(self, params, tokens: jax.Array, rng=None,
              train: bool = False) -> jax.Array:
        """tokens (B, S) -> logits (B, S, V) float32."""
        from distributed_training_tpu.ops.paged_attention import (
            Selection, index_scores, select_topk)

        del rng, train
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        S = tokens.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), tokens.shape)
        back = positions[:, :, None] - positions[:, None, :]
        x = params["tok_embed"][tokens].astype(dt)
        for layers, (_lo, _n, window, _dense) in zip(params["runs"],
                                                     c.runs):
            d = c.dims(window)

            def body(x, layer, window=window, d=d):
                a = layer["attn"]
                h = rms_norm(x, layer["ln1"], c.rms_norm_eps)
                c_q = query_latent(h, a, d)
                mask = back >= 0
                if window:
                    mask &= back < c.window
                else:
                    q, k, wts = index_project(h, c_q, layer["index"],
                                              positions, c)
                    mask = select_topk(
                        index_scores(Selection(q, wts, None, 0), k),
                        mask, min(c.index_topk, S))[2]
                attn = masked_attention(
                    *project(h, a, positions, d, c_q=c_q), a, d, mask)
                attn = attn * head_gate(h, a)[..., None]
                x = x + jnp.einsum("...hk,hkd->...d", attn,
                                   a["wo"].astype(dt))
                h = rms_norm(x, layer["ln2"], c.rms_norm_eps)
                return x + self.feed_forward(layer, h)[0], None
            x, _ = jax.lax.scan(body, x, layers)
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        return jnp.einsum("...d,dv->...v", x,
                          params["lm_head"].astype(dt)
                          ).astype(jnp.float32)

    def serving_block(self):
        return SparseLatentBlock(self)


class SparseLatentBlock:
    """This model behind ``serving/blocks.py``'s interface: the latent
    row in ``k_pages`` and the rotary key in ``v_pages``, a full layer's
    in the table and a window layer's, at its own width, in the window
    pool's rings; a full layer's index key as the third row
    (``cache``: ``index_dim``, ``index_topk``).

    The gate is made in ``project``, where the layer's normed input
    is, and handed on with the queries, as the indexer's queries and
    weights are; ``attend_chunk`` passes it through beside the
    attention's output with the two index counts, and ``finish`` takes
    the three."""

    counters = COUNTERS + INDEX_COUNTERS

    def __init__(self, model: SparseLatentMoE):
        from distributed_training_tpu.serving.blocks import weight

        c = model.cfg
        self.model = model
        self.cfg = c
        self._w = weight              # int8 leaves dequantised at compute
        window_layers = tuple(n for n, t in enumerate(c.layer_types)
                              if t == SLIDING)
        self.cache = dict(
            n_layers=c.n_layers, n_kv_heads=1, head_dim=c.kv_lora_rank,
            v_head_dim=c.qk_rope_head_dim, kind="latent",
            index_dim=c.index_head_dim, index_topk=c.index_topk,
            block=type(self).__name__)
        if window_layers:
            self.cache.update(
                window=c.window, window_layers=window_layers,
                window_head_dim=c.swa_kv_lora_rank,
                window_v_head_dim=c.swa_qk_rope_head_dim)
        self._views = {lo: _Run(self, window)
                       for lo, _n, window, _dense in c.runs}

    def embed(self, params, tokens, positions):
        del positions
        return params["tok_embed"][tokens].astype(
            jnp.dtype(self.cfg.dtype))

    def segments(self, params):
        return tuple(params["runs"])

    def at(self, layer):
        return self._views[layer]

    def logits(self, params, x):
        x = rms_norm(x, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,dv->...v", x,
                          self._w(params["lm_head"], x.dtype)
                          ).astype(jnp.float32)


class _Run:
    """``SparseLatentBlock`` as one run of like layers sees it
    (``SparseLatentBlock.at``): a window layer's sizes or a full
    layer's."""

    def __init__(self, block: SparseLatentBlock, window: bool):
        self.block, self.window = block, window
        self.dims = block.cfg.dims(window)

    def project(self, layer, x, positions):
        b, d, a = self.block, self.dims, layer["attn"]
        h = rms_norm(x, layer["ln1"], d.rms_norm_eps)
        c_q = query_latent(h, a, d, b._w)
        q_nope, q_rope, c_kv, k_rope = project(h, a, positions, d, b._w,
                                               c_q=c_q)
        gate = head_gate(h, a, b._w)
        rows = (c_kv[..., None, :], k_rope[..., None, :])
        if self.window:
            return ((q_nope, q_rope, gate),) + rows
        iq, ik, iw = index_project(h, c_q, layer["index"], positions,
                                   b.cfg, b._w)
        return ((q_nope, q_rope, gate, iq, iw),) + rows + (
            ik[..., None, :],)

    def attend_chunk(self, layer, q, kp, vp, page_rows, q_pos, ip=None):
        from distributed_training_tpu.ops.paged_attention import (
            Selection, latent_attention_chunk)

        c, d = self.block.cfg, self.dims
        wukv = self.block._w(layer["attn"]["wukv"], q[0].dtype)
        w_uk, w_uv = (wukv[..., :d.qk_nope_head_dim],
                      wukv[..., d.qk_nope_head_dim:])
        if self.window:
            attn = latent_attention_chunk(
                q[0], q[1], kp, vp, page_rows, q_pos, w_uk, w_uv,
                window=c.window, ring=True)
            return attn, q[2], jnp.zeros((2,), jnp.int32)
        attn = latent_attention_chunk(
            q[0], q[1], kp, vp, page_rows, q_pos, w_uk, w_uv,
            select=Selection(q[3], q[4], ip, c.index_topk))
        with jax.named_scope("dtt.attn.select"):
            scored = jnp.maximum(q_pos + 1, 0)
            return attn, q[2], jnp.stack(
                [jnp.sum(scored),
                 jnp.sum(jnp.minimum(scored, c.index_topk))]
            ).astype(jnp.int32)

    def finish(self, layer, x, attn, valid):
        b = self.block
        attn, gate, index_counts = attn
        with jax.named_scope("dtt.attn.out"):
            x = x + jnp.einsum("...hk,hkd->...d", attn * gate[..., None],
                               b._w(layer["attn"]["wo"], x.dtype))
        with jax.named_scope(ffn_scope(layer)):
            h = rms_norm(x, layer["ln2"], b.cfg.rms_norm_eps)
            y, counts = b.model.feed_forward(layer, h, valid, b._w)
            return x + y, jnp.concatenate([counts, index_counts])


def build_sparse_latent_moe(loss: str = "auto", dtype: str = "bfloat16",
                            **kwargs) -> SparseLatentMoE:
    """Registry entrypoint (``build_model("sparse_latent_moe", ...)``)."""
    if loss not in ("auto", "xent"):
        raise ValueError(
            f"sparse_latent_moe has one loss (xent), got {loss!r}")
    kwargs.setdefault("dtype", dtype)
    return SparseLatentMoE(SparseLatentMoEConfig(**kwargs))
