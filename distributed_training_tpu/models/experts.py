"""Routed experts held in part: the one expert feed-forward of the
models that have one (``models/latent_moe.py``,
``models/window_moe.py``, ``models/parallel_moe.py``), with what
differs between them as parameters of the model's configuration ``c``
and as what the layer's parameters ``m`` hold:

- ``c.router_score``: ``"sigmoid"`` — every expert scored by a sigmoid,
  the ``moe_top_k`` largest chosen, the gates the chosen scores over
  their sum. Where ``m`` holds a ``router_bias`` the choice is by score
  + that selection bias (the gates by the scores alone), and where
  ``c`` has a ``routed_scaling_factor`` the gates are times it
  (DeepSeek-V3's ``noaux_tc``: ``latent_moe``); with neither, the
  choice is by the scores and the gates sum to 1 (``parallel_moe``: no
  zeros array and no factor of 1 stands in). ``"softmax"`` — the
  ``moe_top_k`` largest router logits chosen, the gates a softmax over
  the chosen (SmallThinker's primary router; they sum to 1);
- ``c.expert_act``: the gate's activation, ``"silu"`` or ``"relu"``;
- shared experts that every token passes through, if the layer's
  parameters hold any (``m["shared"]``): one gated product, added to
  the routed sum. Several shared experts whose outputs are AVERAGED are
  one such product too, the experts stacked on the hidden axis and the
  result over their number: the model's ``shared=``
  (``parallel_moe.shared_mean``).

**Experts held.** The layer routes over all ``c.n_routed_experts`` and
holds ``c.experts_held`` of them, the contiguous block that starts at
``c.expert_offset``: it computes its own experts' part of the routed
sum (and the shared expert, which every rank computes alike) and
nothing for the experts it lacks. The gates are normalised over all
chosen experts, held or not, so the parts of all ranks add up to the
uncut layer. No token is dropped: every held expert sees every token
and its output is weighted by the token's gate for it, which is 0 where
the token did not choose it. That costs ``held x tokens`` expert
products instead of ``top_k x tokens / ep_size`` but reads each held
expert's weights once, which is what a decode iteration is bound by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The int32 sums ``expert_layer`` returns: (token, expert) picks made,
# picks that landed on an expert held here, the largest count on one
# held expert, expert-layer calls that saw a token, and held experts
# times those calls (what a mean load an expert is taken over).
COUNTERS = ("moe_picks", "moe_picks_held", "moe_load_max",
            "moe_layer_calls", "moe_expert_calls")

_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _cast(leaf, dt):
    return leaf.astype(dt)


def rms_norm(x, scale, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + eps)
    return (y * scale).astype(dtype)


def gated_mlp(h, m, w=_cast, act: str = "silu"):
    dt = h.dtype
    u = (_ACTS[act](jnp.einsum("...d,df->...f", h, w(m["wg"], dt)))
         * jnp.einsum("...d,df->...f", h, w(m["wu"], dt)))
    return jnp.einsum("...f,fd->...d", u, w(m["wd"], dt))


def router_logits(h, router):
    """``h (..., D)`` times the router ``(D, experts)``, float32 like
    published gates."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("...d,de->...e", h.astype(jnp.float32),
                          router.astype(jnp.float32))


def route(h, m, c, logits=None):
    """``h (T, D)`` -> the ``moe_top_k`` experts of each token, of all
    ``n_routed_experts`` (``idx (T, k)``), and their gate weights
    ``(T, k)`` float32. ``logits (T, experts)``: the router's product
    where the model made it elsewhere (of another tensor than ``h``:
    ``models/window_moe.py``), else it is made here of ``h``."""
    if logits is None:
        logits = router_logits(h, m["router"])
    if c.router_score == "softmax":
        top, idx = jax.lax.top_k(logits, c.moe_top_k)
        return idx, jax.nn.softmax(top, axis=-1)
    s = jax.nn.sigmoid(logits)
    chosen_by = (s + m["router_bias"].astype(jnp.float32)
                 if "router_bias" in m else s)
    _, idx = jax.lax.top_k(chosen_by, c.moe_top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = (getattr(c, "routed_scaling_factor", 1.0) * g
         / jnp.sum(g, -1, keepdims=True))
    return idx, g


def ffn_scope(layer) -> str:
    """The ``jax.named_scope`` (``telemetry/op_scopes.py::SCOPES``) of
    a layer's feed-forward with its norm and residual: an expert layer's
    or a dense one's, by what its parameters hold."""
    return "dtt.moe.experts" if "router" in layer["mlp"] else "dtt.mlp"


def expert_layer(h, m, c, valid=None, w=_cast, logits=None,
                 route=route, shared=None):
    """The expert feed-forward on ``h (..., D)``: this rank's experts'
    part of the routed sum plus the shared expert where the layer has
    one, and ``COUNTERS`` over the rows ``valid (...)`` marks (all, if
    None). ``logits (..., experts)`` as in ``route``. ``route`` and
    ``shared`` (``(x, m["shared"], w) -> y``) are the model's own names
    for the two, where it keeps them patchable
    (``models/latent_moe.py``)."""
    dt = h.dtype
    lead = h.shape[:-1]
    x = h.reshape(-1, h.shape[-1])
    ok = (jnp.ones(x.shape[:1], bool) if valid is None
          else valid.reshape(-1))
    with jax.named_scope("dtt.moe.route"):
        if logits is not None:
            logits = logits.reshape(-1, logits.shape[-1])
        idx, g = route(x, m, c) if logits is None else route(x, m, c,
                                                              logits)
        local = idx - c.expert_offset
        # one_hot of an index outside [0, held) is the zero row: an
        # expert that lies on another rank takes no weight here.
        onehot = jax.nn.one_hot(local, c.experts_held,
                                dtype=jnp.float32)
        combine = jnp.einsum("tk,tke->te", g, onehot)
    with jax.named_scope("dtt.moe.experts"):
        act = (_ACTS[c.expert_act](
            jnp.einsum("td,edf->tef", x, w(m["wg"], dt)))
            * jnp.einsum("td,edf->tef", x, w(m["wu"], dt)))
        y = jnp.einsum("tef,efd->td",
                       act * combine.astype(dt)[..., None],
                       w(m["wd"], dt))
        if "shared" in m:
            y = y + (shared(x, m["shared"], w) if shared is not None
                     else gated_mlp(x, m["shared"], w, c.expert_act))
    with jax.named_scope("dtt.moe.route"):    # what the router chose
        load = jnp.sum(onehot * ok[:, None, None], axis=(0, 1))
        counts = jnp.stack([
            jnp.sum(ok) * c.moe_top_k, jnp.sum(load), jnp.max(load),
            jnp.any(ok), jnp.any(ok) * c.experts_held]
        ).astype(jnp.int32)
    return y.reshape(lead + (h.shape[-1],)), counts
