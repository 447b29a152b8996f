"""Train state: a transparent pytree, born sharded.

``{"params", "opt_state", "step"}`` — the unit the checkpoint layer
saves/restores (superset of the reference's ``{"MODEL_STATE",
"EPOCHS_RUN"}`` snapshot, src/distributed_trainer.py:88-91, which dropped
optimizer state entirely; SURVEY.md §5.4).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_training_tpu.parallel.strategy import ShardingStrategy


def state_specs(strategy: ShardingStrategy,
                optimizer: optax.GradientTransformation,
                param_shapes: Any, logical_axes: Any = None,
                opt_shapes: Any = None) -> dict:
    """PartitionSpecs for the full train state.

    Optimizer-state leaves that mirror params (Adam moments, momentum)
    inherit the param's spec via ``optax.tree_map_params``; scalar/other
    leaves replicate. ``opt_shapes`` may be precomputed by the caller
    (the trainer shares one abstract trace with state_shardings).
    """
    param_specs = strategy.specs_for_tree(param_shapes, logical_axes)
    # Param-shaped optimizer leaves get the strategy's OPT layout —
    # identical to the param layout except under ZeRO-1, where moments
    # shard over the data axes while params stay replicated.
    opt_base_specs = strategy.opt_specs_for_tree(param_shapes,
                                                 logical_axes)
    if opt_shapes is None:
        opt_shapes = jax.eval_shape(optimizer.init, param_shapes)

    def spec_for_opt_leaf(leaf, spec, pshape):
        # Optimizer state inherits the param's spec ONLY when it is
        # exactly param-shaped. Anything else replicates: Adafactor's
        # factored v_row/v_col drop one of the param's dims, so a
        # rank-compatible spec can still land a sharded axis on the
        # WRONG (possibly non-divisible) dimension — caught by the 7B
        # fsdp=16 topology compile, where GQA wk (L, D, Hkv, hd) has
        # param spec P(None, 'fsdp') but v_row is (L, Hkv, hd) and
        # dim 1 became Hkv=8, not divisible by 16. (The earlier
        # rank/size guard missed exactly this equal-rank-prefix case.)
        # Factored moments are tiny by construction, so replication
        # costs nothing material.
        if (isinstance(spec, P) and hasattr(leaf, "shape")
                and hasattr(pshape, "shape")
                and tuple(leaf.shape) != tuple(pshape.shape)):
            return P()
        return spec

    opt_specs = optax.tree_map_params(
        optimizer,
        spec_for_opt_leaf,
        opt_shapes,
        opt_base_specs,
        param_shapes,
        transform_non_params=lambda _leaf: P(),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    return {"params": param_specs, "opt_state": opt_specs, "step": P()}


def state_shardings(mesh: Mesh, specs: dict,
                    offload_opt_state: bool = False,
                    opt_shapes: Any = None) -> dict:
    """NamedShardings for the state tree.

    ``offload_opt_state=True`` makes ``pinned_host`` memory the
    RESIDENCY of the optimizer moments — the analogue of the reference
    FSDP's CPU offload (fsdp_strategy.py:23-25, which was unreachable
    there, SURVEY.md §8 B7, and which likewise round-trips state to
    the accelerator per use). The trainer streams the moments to
    device around each step and back (see Trainer.train_step), so
    between steps HBM holds params + activations only — AdamW's
    2×params fp32, the bulk of big-model residency, lives in host RAM.
    In-jit streaming via memory-space annotations (tiles resident
    only) is the upgrade path once XLA's host-offload annotations are
    reliable on the deployed runtime — attempted on jax 0.9.0 (r4):
    any jit whose out_shardings mix memory kinds AND include a scalar
    output (Adam's count) fails XLA SPMD's
    "Side-effect HLO must have sharding" RET_CHECK
    (spmd_partitioner.cc:5743) because the scalar's placement
    custom-call carries no sharding; and in-traced ``device_put`` to
    host does not pin output residency without out_shardings. Re-try
    when the partitioner handles scalar placements. Requires
    host-memory support (``supports_memory_kind``); raises otherwise
    rather than silently keeping state on device."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    if offload_opt_state:
        if not supports_memory_kind(mesh, "pinned_host"):
            raise ValueError(
                "offload_opt_state=true but this runtime has no "
                "pinned_host memory space (CPU test meshes and old "
                "libtpu builds lack it)")
        if opt_shapes is None:
            raise ValueError(
                "offload_opt_state=true requires opt_shapes (scalar "
                "step counters must stay on device)")

        def offload(sh: NamedSharding, leaf) -> NamedSharding:
            # Only array-sized leaves move to host: scalar counters
            # (Adam's count) trip XLA's side-effecting placement
            # custom-call under SPMD, and offloading them buys nothing.
            if getattr(leaf, "ndim", 0) >= 1 and np.prod(leaf.shape) > 1:
                return sh.with_memory_kind("pinned_host")
            return sh

        shardings["opt_state"] = jax.tree.map(
            offload, shardings["opt_state"], opt_shapes)
    return shardings


def supports_memory_kind(mesh: Mesh, kind: str) -> bool:
    """Whether the mesh's devices expose the given memory space."""
    try:
        dev = mesh.devices.reshape(-1)[0]
        return any(m.kind == kind for m in dev.addressable_memories())
    except (AttributeError, RuntimeError, jax.errors.JaxRuntimeError):
        return False


def init_state(model, optimizer, rng: jax.Array, shardings: dict) -> dict:
    """Initialize params and optimizer state directly into their sharded
    layout — no host-side full materialization, so 7B-class models
    never need to fit on one host (contrast: the reference builds the
    full model on every rank then wraps, src/distributed_trainer.py:137)."""
    params = jax.jit(model.init,
                     out_shardings=shardings["params"])(rng)
    opt_state = jax.jit(optimizer.init,
                        out_shardings=shardings["opt_state"])(params)
    # Placed like every other leaf: a step scalar left on the default
    # device lowers without its sharding annotation, so the program the
    # first call compiles differs (by one attribute) from the one the
    # collectives audit lowers from abstract_state — and the audit's
    # compile then misses the persistent cache instead of hitting it.
    step = jax.device_put(jnp.zeros((), jnp.int32), shardings["step"])
    return {"params": params, "opt_state": opt_state, "step": step}


def abstract_state(model, optimizer, rng: jax.Array,
                   shardings: dict) -> dict:
    """ShapeDtypeStructs (with shardings attached) for checkpoint
    restore-in-place."""
    p_shapes = jax.eval_shape(model.init, rng)
    o_shapes = jax.eval_shape(optimizer.init, p_shapes)
    shapes = {"params": p_shapes, "opt_state": o_shapes,
              "step": jax.ShapeDtypeStruct((), jnp.int32)}
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)
