"""Race / divergence / numerical-health diagnostics.

The reference's only divergence tooling is a human diffing per-rank
grad/weight-norm log lines (src/playground/ddp_script.py:149-164;
SURVEY.md §5.2). Here the checks are compiled collectives:

- ``replica_divergence``: are the data-parallel replicas of every param
  bitwise-in-sync? Computed as (max - min) over replicas of a per-leaf
  fingerprint, with a single psum-family reduction — the SPMD
  formalization of "diff the rank logs".
- ``check_finite``: which leaves contain NaN/Inf, as a host-side report
  (the trainer's in-step ``nan_guard`` skips bad updates; this is the
  post-mortem view).
"""

from __future__ import annotations

import collections
import logging
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributed_training_tpu.runtime import BATCH_AXES

logger = logging.getLogger(__name__)


def _fingerprint(x: jax.Array) -> jax.Array:
    """Order-stable int32 scalar fingerprint of a tensor's bits.
    float-sum fingerprints can collide on permuted values and round away
    small diffs; position-weighted int sums (wrapping overflow is fine —
    it is deterministic and identical across in-sync replicas) are
    sensitive to any elementwise change."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    idx = jnp.arange(bits.size, dtype=jnp.int32).reshape(bits.shape)
    return jnp.sum(bits * (idx % 8191 + 1))


# jit/shard_map cache: building a fresh closure per call would recompile
# the whole-params program on every periodic check. LRU-bounded: the
# key holds a Mesh (and through the jitted fn, its devices), so an
# unbounded dict pins every mesh a long test session ever built.
_DIVERGENCE_FNS: "collections.OrderedDict" = collections.OrderedDict()
_DIVERGENCE_CACHE_MAX = 8


def clear_divergence_cache() -> None:
    """Drop all cached divergence programs (test isolation hook)."""
    _DIVERGENCE_FNS.clear()


def _divergence_fn(mesh: Mesh, axes: tuple[str, ...],
                   specs_treedef, specs_leaves: tuple):
    key = (mesh, axes, specs_treedef, specs_leaves)
    fn = _DIVERGENCE_FNS.get(key)
    if fn is not None:
        _DIVERGENCE_FNS.move_to_end(key)
    if fn is None:
        in_specs = jax.tree_util.tree_unflatten(
            specs_treedef, list(specs_leaves))
        out_specs = jax.tree_util.tree_unflatten(
            specs_treedef, [P()] * len(specs_leaves))

        def per_replica(tree):
            def spread(x):
                f = _fingerprint(x)
                hi = f
                lo = f
                for a in axes:
                    hi = jax.lax.pmax(hi, a)
                    lo = jax.lax.pmin(lo, a)
                # int32 wrap-around subtraction is still 0 ⇔ equal.
                return jnp.abs(hi - lo)
            return jax.tree.map(spread, tree)

        fn = jax.jit(shard_map(per_replica, mesh=mesh,
                               in_specs=(in_specs,),
                               out_specs=out_specs, check_vma=False))
        _DIVERGENCE_FNS[key] = fn
        while len(_DIVERGENCE_FNS) > _DIVERGENCE_CACHE_MAX:
            _DIVERGENCE_FNS.popitem(last=False)
    return fn


def replica_divergence(params: Any, mesh: Mesh,
                       axes: tuple[str, ...] = BATCH_AXES,
                       param_specs: Any = None) -> dict:
    """Max absolute fingerprint spread across data-parallel replicas,
    per param leaf. 0 everywhere ⇔ replicas identical over ``axes``.

    ``param_specs``: PartitionSpec pytree describing how ``params`` are
    actually sharded (a strategy's ``specs_for_tree``). Defaults to
    fully-replicated specs — correct for DDP; for FSDP/TP pass the real
    specs (so shards are fingerprinted in place, no all-gather) and
    restrict ``axes`` to axes the params are replicated over.

    Under single-controller SPMD, XLA keeps replicated values consistent
    by construction; this check matters for multi-process runs (where
    each host materializes its own addressable shards) and as a
    regression harness for custom-collective code (playground,
    hand-written psum paths)."""
    axes = tuple(a for a in axes
                 if dict(zip(mesh.axis_names, mesh.devices.shape))
                 .get(a, 1) > 1)
    if not axes:
        return {"max_divergence": 0, "leaves": {}}

    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(), params)
    # Specs must not shard over the axes we compare across.
    used = {a for s in jax.tree.leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P))
        for part in s if part is not None
        for a in ((part,) if isinstance(part, str) else part)}
    overlap = used & set(axes)
    if overlap:
        raise ValueError(
            f"params are sharded over {sorted(overlap)}; there are no "
            f"replicas to compare over those axes — restrict `axes`")

    leaves, treedef = jax.tree_util.tree_flatten(
        param_specs, is_leaf=lambda x: isinstance(x, P))
    fn = _divergence_fn(mesh, axes, treedef, tuple(leaves))
    spreads = fn(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(spreads)
    leaves_out = {jax.tree_util.keystr(path): int(v) for path, v in flat}
    worst = max(leaves_out.values(), default=0)
    if worst > 0:
        bad = {k: v for k, v in leaves_out.items() if v > 0}
        logger.warning("replica divergence detected: %s", bad)
    return {"max_divergence": worst, "leaves": leaves_out}


def check_finite(tree: Any) -> dict:
    """Host-side NaN/Inf report: count of non-finite entries per leaf;
    empty dict means all finite. Summing the (rare) non-finite indicator
    in float32 is exact below 2^24 and saturates-but-stays-positive
    above, so a poisoned leaf can never be reported clean — unlike a
    float mean of isfinite (rounds sparse NaNs in big leaves to 0) or an
    int32 sum (wraps past 2^31, possibly to <=0)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda x: jnp.sum(
            (~jnp.isfinite(x)).astype(jnp.float32)), tree))
    bad = {jax.tree_util.keystr(path): int(v)
           for path, v in flat if float(v) > 0}
    if bad:
        logger.error("non-finite values: %s", bad)
    return bad


def assert_replicas_in_sync(params: Any, mesh: Mesh,
                            axes: tuple[str, ...] = BATCH_AXES) -> None:
    """Test/debug assertion form of ``replica_divergence``."""
    report = replica_divergence(params, mesh, axes)
    if report["max_divergence"] > 0:
        bad = {k: v for k, v in report["leaves"].items() if v > 0}
        raise AssertionError(f"replicas diverged: {bad}")


def grad_global_norm_by_module(grads: Any) -> dict[str, float]:
    """Per-top-level-module gradient norms (debug aid for loss spikes)."""
    out = {}
    if isinstance(grads, dict):
        for key, sub in grads.items():
            sq = jax.tree.reduce(
                lambda acc, g: acc + jnp.sum(jnp.square(
                    g.astype(jnp.float32))), sub, jnp.zeros(()))
            out[key] = float(jnp.sqrt(sq))
    else:
        out["all"] = float(
            jnp.sqrt(jax.tree.reduce(
                lambda acc, g: acc + jnp.sum(jnp.square(
                    g.astype(jnp.float32))), grads, jnp.zeros(()))))
    return out


def summarize_state(state: Any) -> dict:
    """One-call health summary: finiteness + basic scale stats."""
    params = (state["params"]
              if isinstance(state, dict) and "params" in state
              else state)
    nonfinite = check_finite(params)
    norms = grad_global_norm_by_module(params)
    return {"nonfinite": nonfinite, "param_norms": norms,
            "healthy": not nonfinite}
