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
uncut layer. No token is dropped.

**One grouped product over the picks.** The (row, pick) pairs that
land on a held expert, dead rows left out, are sorted by expert and
their rows gathered; ONE Pallas kernel (``ops/grouped_experts.py``,
``dtt_grouped_experts``) runs each held expert over its own rows, a tile
of ``_TILE_ROWS`` rows a visit, and visits only the tiles that hold some
group: the work follows the picks, and an expert that no row picked is
never read. Each pick's float32 row of the down product goes back to
its row, a row's picks are summed in float32 and cast once. That rounds
where the dense form (every held expert over every row, weighted by the
row's gate for it, 0 where the row did not choose it) rounds once XLA
has fused it: the gate and up products and the gate weight to the
activation dtype, the activation times them in float32 and rounded once,
the down product and the sum over a row's picks in float32. The dense
form is what the layer's gradient is taken of (``_routed``: the kernel
has no gradient of its own) and the tests' reference.

**Weights where they lie.** The kernel reads the held experts stacked
over a run's layers, ``(L, E, D, F)``, at the layer's index: a layer
scan's body takes its layer by ``layer_of``, which leaves them whole
with the index beside them as ``m["layer"]``. A Pallas call's operand is
a buffer of its own, so a layer's experts sliced out of the stack would
be copied for every call. Experts stored in another dtype than the
activations' (float32 masters, int8) are the layer's own, through ``w``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distributed_training_tpu.ops.flash_attention import _platform_is_tpu
from distributed_training_tpu.ops.grouped_experts import (
    grouped_experts, visits)

# The int32 sums ``expert_layer`` returns: (token, expert) picks made,
# picks that landed on an expert held here, the largest count on one
# held expert, expert-layer calls that saw a token, held experts times
# those calls (what a mean load an expert is taken over), and the rows
# of expert products computed (the rows of the tiles the kernel
# visited).
COUNTERS = ("moe_picks", "moe_picks_held", "moe_load_max",
            "moe_layer_calls", "moe_expert_calls", "moe_rows_computed")

_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}

# Rows a visit of the grouped product's kernel works on: at a prompt
# chunk of 1,024 rows, 64 was slower in all four expert configurations
# on a v5e and 256 faster in two and slower in two
# (``benchmarks/expert_form_table.py``; PERF.md section 6).
_TILE_ROWS = 128
# The held experts' weights, which ``layer_of`` leaves stacked.
_HELD = ("wg", "wu", "wd")


def layer_of(layers, i):
    """Layer ``i`` of a run's parameters stacked over its layers
    (leaves ``(L, ...)``): every leaf sliced, but where the layer's
    ``mlp`` holds routed experts their ``wg``, ``wu`` and ``wd`` stay
    whole, with ``i`` beside them as ``mlp["layer"]``, which
    ``expert_layer`` hands its kernel. The body of a layer scan takes
    its layer so (``serving/engine.py::_scan_layers``)."""
    def take(a):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    mlp = layers.get("mlp", {})
    if "router" not in mlp or not all(n in mlp for n in _HELD):
        return jax.tree.map(take, layers)
    layer = jax.tree.map(take, {**layers, "mlp": {
        k: v for k, v in mlp.items() if k not in _HELD}})
    layer["mlp"].update({n: mlp[n] for n in _HELD}, layer=i)
    return layer


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
    None; a dead row takes no pick and gets no routed part). ``logits
    (..., experts)`` as in ``route``. ``route`` and ``shared`` (``(x,
    m["shared"], w) -> y``) are the model's own names for the two, where
    it keeps them patchable (``models/latent_moe.py``). ``m``'s held
    experts are one layer's, ``(E, D, F)``, or a run's stacked over its
    layers with the layer's index ``m["layer"]`` (``layer_of``)."""
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
        # A pick of a dead row, or of an expert that lies on another
        # rank, is no pick here.
        mine = (local >= 0) & (local < c.experts_held) & ok[:, None]
        # Picks on each held expert (one_hot of an index outside
        # [0, held) is the zero row).
        load = jnp.sum(jax.nn.one_hot(local, c.experts_held,
                                      dtype=jnp.int32)
                       * ok[:, None, None], axis=(0, 1))
    with jax.named_scope("dtt.moe.experts"):
        held, layer = _stacks(m, w, dt)
        y = _routed(c.expert_act, x, g, local, mine, load, *held, layer)
        if "shared" in m:
            y = y + (shared(x, m["shared"], w) if shared is not None
                     else gated_mlp(x, m["shared"], w, c.expert_act))
    with jax.named_scope("dtt.moe.route"):    # what the router chose
        rows = visits(load, _TILE_ROWS, 1)[2] * _TILE_ROWS
        counts = jnp.stack([
            jnp.sum(ok) * c.moe_top_k, jnp.sum(load), jnp.max(load),
            jnp.any(ok), jnp.any(ok) * c.experts_held, rows]
        ).astype(jnp.int32)
    return y.reshape(lead + (h.shape[-1],)), counts


def _stacks(m, w, dt):
    """``([wg, wu, wd], layer)``: the held experts as the kernel reads
    them, stacked over layers, and the layer's index. A run's stacks
    (``layer_of``) stored in ``dt`` are read where they lie; else the
    layer's own experts, through ``w``, are a stack of one."""
    first = jnp.zeros((), jnp.int32)
    if "layer" not in m:
        return [w(m[n], dt)[None] for n in _HELD], first
    i = m["layer"]
    if all(getattr(m[n], "dtype", None) == dt for n in _HELD):
        return [m[n] for n in _HELD], i

    def take(a):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return [w(jax.tree.map(take, m[n]), dt)[None] for n in _HELD], first


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(act, x, g, local, mine, load, wg, wu, wd, layer):
    """The held experts' part of the routed sum on ``x (T, D)``, in its
    dtype, as one grouped product over the (row, pick) pairs ``mine
    (T, k)`` marks (``local (T, k)`` in ``[0, held)``, ``g (T, k)`` the
    gates), sorted by expert: ``ops/grouped_experts.py``'s kernel over
    the stacked ``wg``, ``wu``, ``wd`` at ``layer``; ``load (held,)``
    the groups' sizes. Each pick's float32 row of the down product is
    gathered back to its row and a row's picks summed in float32 before
    the one cast. Its gradient is ``_dense``'s."""
    T, k = local.shape
    held = wg.shape[1]
    tm = _TILE_ROWS
    # A row picks distinct experts: at most min(k, held) land here.
    cap = T * min(k, held)
    rows = -(-cap // tm) * tm
    key = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    # Where each (row, pick) pair lies in the sorted order.
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype),
        unique_indices=True).reshape(T, k)
    order = order[:cap]
    xs = jnp.pad(x[order // k], ((0, rows - cap), (0, 0)))
    gates = jnp.pad(g.reshape(-1)[order], (0, rows - cap))
    down = grouped_experts(xs, gates, load.astype(jnp.int32), wg, wu, wd,
                           layer, act=act, tile_rows=tm,
                           interpret=not _platform_is_tpu())
    # Rows past the live picks are never written: a pick that is not
    # held here reads a zero, not them.
    place = jnp.minimum(place, rows - 1)
    y = sum(jnp.where(mine[:, j, None], down[place[:, j]], 0.0)
            for j in range(k))
    return y.astype(x.dtype)


def _dense(act, x, g, local, mine, wg, wu, wd, layer):
    """``_routed``'s sum in the dense form: every held expert over every
    row, weighted by the row's gate for it, 0 where ``mine`` has no
    such pick."""
    dt = x.dtype
    wg, wu, wd = (jax.lax.dynamic_index_in_dim(a, layer, 0, False)
                  for a in (wg, wu, wd))
    combine = jnp.einsum("tk,tke->te", jnp.where(mine, g, 0.0),
                         jax.nn.one_hot(local, wg.shape[0],
                                        dtype=g.dtype))
    a = (_ACTS[act](jnp.einsum("td,edf->tef", x, wg))
         * jnp.einsum("td,edf->tef", x, wu))
    return jnp.einsum("tef,efd->td", a * combine.astype(dt)[..., None],
                      wd)


def _routed_fwd(act, x, g, local, mine, load, wg, wu, wd, layer):
    return (_routed(act, x, g, local, mine, load, wg, wu, wd, layer),
            (x, g, local, mine, wg, wu, wd, layer))


def _routed_bwd(act, res, dy):
    x, g, local, mine, wg, wu, wd, layer = res
    _, vjp = jax.vjp(lambda x, g, wg, wu, wd: _dense(
        act, x, g, local, mine, wg, wu, wd, layer), x, g, wg, wu, wd)
    dx, dg, dwg, dwu, dwd = vjp(dy)
    return dx, dg, None, None, None, dwg, dwu, dwd, None


_routed.defvjp(_routed_fwd, _routed_bwd)
