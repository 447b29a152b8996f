"""Pipeline parallelism over the ``pp`` mesh axis: GPipe + interleaved.

The transformer's decoder stack is already a *stacked-layer* pytree
(leaves shaped ``(L, ...)``, models/transformer.py), which makes pipeline
parallelism a sharding statement plus a schedule:

- **layout**: shard the stacked-layer leading dim over ``pp`` — stage
  ``i`` physically holds a slice of the layers. This is the partition
  jit cannot exploit on its own (layers execute sequentially), hence the
  explicit schedule.
- **GPipe schedule**: split the batch into ``M`` microbatches and run
  the classic wavefront for ``M + pp - 1`` ticks inside ``shard_map``:
  stage 0 injects microbatch ``t``; every stage applies its local layers
  to its buffer; buffers rotate to the next stage via ``ppermute``
  (XLA collective-permute on ICI); the last stage banks finished
  microbatches. Bubble fraction ``(pp-1)/(M+pp-1)``.
- **Interleaved schedule** (Megatron-style virtual stages): each device
  owns ``v`` *non-contiguous* layer chunks, so the ring has ``v·pp``
  virtual stages of ``L/(v·pp)`` layers and a tick is one chunk. The
  pipeline fills in ``pp - 1`` chunk-ticks instead of ``pp - 1``
  full-stage ticks — idle device-ticks shrink ``v``-fold (see
  ``schedule_stats``; asserted in tests/test_pipeline.py).
- **backward**: plain autodiff. ``ppermute`` transposes to the reverse
  permute, so the same schedule runs backwards (activations
  rematerialize per-stage via the remat'd tick).
- **dropout**: the stage body receives each layer's *global* id and the
  microbatch index of the tick, so per-(layer, microbatch) rngs are
  derived identically on every schedule — pipelined dropout draws the
  same masks regardless of pp (models/transformer.py threads them).

All devices execute the same program every tick (SPMD — no
data-dependent communication); stage roles differ only by masking on
``axis_index``. The reference repo has no pipeline (SURVEY.md §2.3);
this exists so deep models scale past one chip's HBM along depth as
well as width.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_training_tpu.runtime import AXIS_PP

SCHEDULES = ("gpipe", "interleaved")


def pipeline_spec(leaf_ndim: int) -> P:
    """Spec for a stacked-layer param leaf inside the pipeline
    shard_map: leading (layer) dim over pp, rest replicated."""
    return P(AXIS_PP, *([None] * (leaf_ndim - 1)))


def schedule_stats(pp: int, num_microbatches: int, schedule: str,
                   virtual_stages: int = 2) -> dict:
    """Static schedule accounting in *chunk-tick* units (a chunk is
    ``L/(v·pp)`` layers; a GPipe tick costs ``v`` chunk-ticks so both
    schedules are measured in the same currency).

    Returns ticks, total device-slots, useful slots, and idle slots.
    """
    m = num_microbatches
    if schedule == "gpipe":
        ticks = (m + pp - 1) * virtual_stages
    elif schedule == "interleaved":
        # last microbatch enters at (g·v·pp + r) and takes v·pp ticks
        # (same arithmetic as _interleave_tables).
        g, r = divmod(m - 1, pp)
        ticks = g * virtual_stages * pp + r + virtual_stages * pp
    else:
        raise ValueError(f"unknown schedule '{schedule}'")
    slots = ticks * pp
    useful = m * virtual_stages * pp
    return {"ticks": ticks, "slots": slots, "useful": useful,
            "idle": slots - useful}


def _interleave_tables(pp: int, M: int, v: int):
    """Static (T, pp) tables for the interleaved schedule: microbatch
    index (−1 = idle), virtual stage (−1 = idle) per (tick, device).

    Microbatch ``m`` (group ``g = m // pp``, slot ``r = m % pp``) enters
    virtual stage 0 at tick ``g·v·pp + r`` and advances one virtual
    stage per tick; virtual stage ``s`` lives on device ``s % pp``. The
    group spacing guarantees at most one live buffer per device per
    tick (device d, tick t holds the unique in-flight m with
    ``t − e_m ≡ d (mod pp)``)."""
    S = v * pp
    entry = [(m // pp) * S + (m % pp) for m in range(M)]
    T = entry[-1] + S
    mb = -np.ones((T, pp), dtype=np.int32)
    vs = -np.ones((T, pp), dtype=np.int32)
    for m in range(M):
        for s in range(S):
            t = entry[m] + s
            d = s % pp
            assert mb[t, d] < 0, "schedule collision"
            mb[t, d] = m
            vs[t, d] = s
    return jnp.asarray(mb), jnp.asarray(vs)


def _gpipe(stage_params, layer_ids, x_mb, aux0, *, body_fn,
           num_microbatches, axis_name):
    """GPipe wavefront inside shard_map. stage_params leaves:
    (L/pp, ...) local shard; layer_ids: (L/pp,) global layer ids;
    x_mb: (M, B_mb, S_local, D) microbatched activations — replicated
    across pp; S_local = S/sp when ``pipeline_apply`` got a
    ``seq_axis`` (the stage body then holds only its sequence slice).
    Returns processed (M, B_mb, S_local, D) + summed aux."""
    pp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = num_microbatches
    T = M + pp - 1
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    buf = jnp.zeros_like(x_mb[0])
    out = jnp.zeros_like(x_mb)
    aux_acc = aux0

    def tick(carry, t):
        buf, out, aux_acc = carry
        # stage idx processes microbatch t - idx while 0 <= t - idx < M
        mb_idx = jnp.clip(t - idx, 0, M - 1)
        # stage 0 injects microbatch t while t < M
        inject = x_mb[jnp.clip(t, 0, M - 1)]
        is_stage0 = (idx == 0)
        take = jnp.logical_and(is_stage0, t < M)
        buf = jnp.where(take, inject, buf)

        buf, aux = body_fn(stage_params, layer_ids, buf, mb_idx)
        # only count aux for ticks where this stage held real data:
        # stage i is busy for t in [i, i + M)
        busy = jnp.logical_and(t >= idx, t < idx + M)
        aux_acc = aux_acc + jnp.where(busy, aux, 0.0)

        # last stage banks microbatch t - (pp - 1)
        done_t = t - (pp - 1)
        is_last = (idx == pp - 1)
        bank = jnp.logical_and(is_last,
                               jnp.logical_and(done_t >= 0, done_t < M))
        out = jax.lax.dynamic_update_index_in_dim(
            out,
            jnp.where(bank, buf, out[jnp.clip(done_t, 0, M - 1)]),
            jnp.clip(done_t, 0, M - 1), axis=0)

        buf = jax.lax.ppermute(buf, axis_name, perm)
        return (buf, out, aux_acc), None

    (buf, out, aux_acc), _ = jax.lax.scan(
        jax.checkpoint(tick, prevent_cse=False), (buf, out, aux_acc),
        jnp.arange(T))
    del buf

    # results live on the last stage; broadcast to all stages so the
    # (replicated-over-pp) head/loss sees them: mask + psum.
    keep = (idx == pp - 1).astype(out.dtype)
    out = jax.lax.psum(out * keep, axis_name)
    # aux was accumulated per-stage over its own layers: sum of stages.
    aux_acc = jax.lax.psum(aux_acc, axis_name)
    return out, aux_acc


def _interleaved(stage_params, layer_ids, x_mb, aux0, *, body_fn,
                 num_microbatches, virtual_stages, axis_name):
    """Interleaved virtual-stage schedule inside shard_map.

    stage_params leaves: (L/pp, ...) — the local slice holds this
    device's ``v`` chunks back to back (chunk c = local layers
    [c·Lc, (c+1)·Lc), pre-permuted by the caller so chunk c is virtual
    stage ``c·pp + d``). Each tick applies ONE chunk, selected by
    ``lax.switch`` on the static schedule table, so a tick costs
    1/v of a GPipe tick and the fill bubble shrinks v-fold.
    x_mb's sequence dim is local (S/sp) when ``pipeline_apply`` got a
    ``seq_axis`` — same contract as ``_gpipe``."""
    pp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = num_microbatches
    v = virtual_stages
    S = v * pp
    mb_tbl, vs_tbl = _interleave_tables(pp, M, v)
    T = mb_tbl.shape[0]
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    L_local = jax.tree.leaves(stage_params)[0].shape[0]
    Lc = L_local // v

    def chunk_body(c, buf, mb_idx):
        p_c = jax.tree.map(
            lambda leaf: jax.lax.dynamic_slice_in_dim(
                leaf, c * Lc, Lc, axis=0), stage_params)
        ids_c = jax.lax.dynamic_slice_in_dim(layer_ids, c * Lc, Lc)
        return body_fn(p_c, ids_c, buf, mb_idx)

    buf = jnp.zeros_like(x_mb[0])
    out = jnp.zeros_like(x_mb)
    aux_acc = aux0

    def tick(carry, t):
        buf, out, aux_acc = carry
        m_here = mb_tbl[t, idx]            # -1 when idle
        s_here = vs_tbl[t, idx]
        busy = m_here >= 0
        mb_idx = jnp.clip(m_here, 0, M - 1)
        chunk = jnp.clip(s_here // pp, 0, v - 1)

        inject = jnp.logical_and(busy, s_here == 0)
        buf = jnp.where(inject, x_mb[mb_idx], buf)

        branches = [functools.partial(chunk_body, c) for c in range(v)]
        new_buf, aux = jax.lax.switch(chunk, branches, buf, mb_idx)
        buf = jnp.where(busy, new_buf, buf)
        aux_acc = aux_acc + jnp.where(busy, aux, 0.0)

        bank = jnp.logical_and(busy, s_here == S - 1)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(bank, buf, out[mb_idx]), mb_idx, axis=0)

        buf = jax.lax.ppermute(buf, axis_name, perm)
        return (buf, out, aux_acc), None

    (buf, out, aux_acc), _ = jax.lax.scan(
        jax.checkpoint(tick, prevent_cse=False), (buf, out, aux_acc),
        jnp.arange(T))
    del buf

    # finished microbatches were banked on device pp-1 (virtual stage
    # S-1 lives there); broadcast like the GPipe path.
    keep = (idx == pp - 1).astype(out.dtype)
    out = jax.lax.psum(out * keep, axis_name)
    aux_acc = jax.lax.psum(aux_acc, axis_name)
    return out, aux_acc


def interleave_layer_order(L: int, pp: int, v: int) -> np.ndarray:
    """Permutation placing global layer order into interleaved device
    storage: device d's local slice holds chunks (0·pp+d, 1·pp+d, ...)
    back to back. Entry j of the result is the global layer stored at
    stacked position j."""
    Lc = L // (v * pp)
    order = []
    for d in range(pp):
        for c in range(v):
            s = c * pp + d
            order.extend(range(s * Lc, (s + 1) * Lc))
    return np.asarray(order, dtype=np.int32)


def pipeline_apply(body_fn: Callable, stacked_params, x: jax.Array,
                   mesh: Mesh, num_microbatches: int,
                   batch_axes=(), axis_name: str = AXIS_PP,
                   schedule: str = "gpipe", virtual_stages: int = 2,
                   seq_axis=None):
    """Apply ``body_fn`` (one stage-chunk's layers over one microbatch:
    ``(stage_params, layer_ids, x, mb_idx) -> (x, aux)``) as a pipeline.

    ``x``: (B, S, D) activations; B must divide into ``num_microbatches``.
    ``stacked_params``: pytree with leading layer dim on every leaf.
    ``layer_ids`` gives the stage body each layer's *global* index (for
    per-layer dropout rngs that are schedule-invariant); ``mb_idx`` the
    microbatch being processed this tick.
    ``schedule``: "gpipe", or "interleaved" with ``virtual_stages``
    chunks per device (requires L % (v·pp) == 0; costs one stacked-param
    gather per step to place chunks into device storage order).
    ``seq_axis``: mesh axis sharding the sequence dim of ``x`` (sp, for
    Ulysses attention inside the stage body); activations stay
    sequence-sharded as they rotate through stages — the pp ppermute
    moves each (pp, sp) shard to its pp-neighbor with the same sp index.
    Returns ``(x_out, aux_sum)`` with x_out shaped like x.
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown schedule '{schedule}' (expected {SCHEDULES})")
    B = x.shape[0]
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pp = sizes.get(axis_name, 1)
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % pp:
        raise ValueError(f"{L} layers not divisible by {pp} stages")

    layer_ids = jnp.arange(L, dtype=jnp.int32)
    if schedule == "interleaved":
        if L % (virtual_stages * pp):
            raise ValueError(
                f"{L} layers not divisible by virtual_stages*pp="
                f"{virtual_stages * pp}")
        order = jnp.asarray(
            interleave_layer_order(L, pp, virtual_stages))
        stacked_params = jax.tree.map(
            lambda p: jnp.take(p, order, axis=0), stacked_params)
        layer_ids = jnp.take(layer_ids, order)

    # STRIDED microbatch split (microbatch m = rows m, m+M, m+2M, ...),
    # not contiguous chunks: each device's contiguous batch shard then
    # contributes the same dim-1 slot to every microbatch, so rows never
    # leave their home device. A contiguous (M, B/M, ...) reshape of the
    # (dp, fsdp)-sharded batch dim is a physical relayout, which GSPMD
    # resolves with an involuntary full rematerialization at the
    # shard_map boundary (replicate + repartition, every step). The
    # explicit constraints pin the boundary layout to the in/out specs
    # so the compiler can't shard the microbatch dim over pp either.
    x_mb = jnp.swapaxes(
        x.reshape(B // M, M, *x.shape[1:]), 0, 1)

    param_specs = jax.tree.map(
        lambda leaf: pipeline_spec(leaf.ndim), stacked_params)
    xspec = P(None, tuple(batch_axes) or None, seq_axis, None)
    x_mb = jax.lax.with_sharding_constraint(
        x_mb, NamedSharding(mesh, xspec))

    if schedule == "interleaved":
        inner = functools.partial(
            _interleaved, body_fn=body_fn, num_microbatches=M,
            virtual_stages=virtual_stages, axis_name=axis_name)
    else:
        inner = functools.partial(
            _gpipe, body_fn=body_fn, num_microbatches=M,
            axis_name=axis_name)

    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(param_specs, P(AXIS_PP), xspec, P(None)),
        out_specs=(xspec, P(None)),
        check_vma=False,
    )
    # The aux accumulator crosses the shard_map boundary as shape (1,)
    # rather than a scalar: when the aux actually carries gradient
    # (MoE load-balancing loss), shard_map's partial-eval stages a
    # scalar residual whose out-names check fails (_SpecError) on this
    # jax — a rank-1 carry sidesteps it, and the squeeze below keeps
    # the external contract (scalar aux) unchanged.
    out_mb, aux = fn(stacked_params, layer_ids, x_mb,
                     jnp.zeros((1,), jnp.float32))
    out_mb = jax.lax.with_sharding_constraint(
        out_mb, NamedSharding(mesh, xspec))
    out = jnp.swapaxes(out_mb, 0, 1).reshape(B, *x.shape[1:])
    return out, aux[0]
