"""DDP from collective primitives — the TPU re-derivation.

Line-for-line *conceptual* parity with the reference's pedagogical script
(src/playground/ddp_script.py), whose recipe is (SURVEY.md §3.2):

1. identical seed on every rank                 (ddp_script.py:108)
2. broadcast params from rank 0                 (:120-121)
3. shard the dataset by rank                    (:124-132)
4. forward/backward locally, then per-parameter
   ``all_reduce(SUM) / world_size``             (:149-154)
5. identical optimizer step on every rank       (:166)
6. optional per-rank grad/weight-norm logging   (:155-164, behind a
   debug flag here — always-on was reference bug B8)

The TPU translation: "ranks" are devices on a 1-D ``dp`` mesh inside one
process; per-rank code is the function passed to ``shard_map``, and the
collectives are explicit ``jax.lax`` calls — ``pmean`` for the gradient
all-reduce (psum/world_size, exactly Q10's convention) and ``ppermute``
broadcast for the initial param sync. Everything the production trainer
gets implicitly from sharding layouts is spelled out here by hand.

Run:  python -m distributed_training_tpu.playground.ddp_from_primitives \
          --world-size 4 --epochs 3 [--log-norms]
"""

from __future__ import annotations

import argparse
import functools
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


# -- model: SimpleModel = Linear(10, 1) (parity: ddp_script.py:16-23) ----


def init_params(rng: jax.Array, in_dim: int = 10) -> dict:
    bound = 1.0 / np.sqrt(in_dim)
    wk, bk = jax.random.split(rng)
    return {
        "w": jax.random.uniform(wk, (in_dim, 1), jnp.float32,
                                -bound, bound),
        "b": jax.random.uniform(bk, (1,), jnp.float32, -bound, bound),
    }


def forward(params: dict, x: jax.Array) -> jax.Array:
    return x @ params["w"] + params["b"]


def mse_loss(params: dict, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean((forward(params, x) - y) ** 2)  # ddp_script.py:135


# -- dataset: DummyDataset randn pairs (parity: ddp_script.py:26-36) -----


def make_dataset(size: int = 1000, in_dim: int = 10, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((size, in_dim)).astype(np.float32)
    y = rng.standard_normal((size, 1)).astype(np.float32)
    return x, y


# -- the per-rank program ------------------------------------------------


def _rank_step(params, x_local, y_local, lr, *, log_norms):
    """What ONE rank does for one batch. Runs under shard_map: shapes
    here are per-device shards and collectives are explicit."""
    # (4) local forward/backward…
    loss, grads = jax.value_and_grad(mse_loss)(params, x_local, y_local)

    # …then the gradient all-reduce. pmean == psum / axis_size: the
    # allreduce-SUM-then-divide convention of ddp_script.py:150-154 (Q10).
    grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
    # Each rank also averages its loss for reporting (not required for
    # correctness — gradients are already synced).
    mean_loss = jax.lax.pmean(loss, "dp")

    # (5) identical SGD step on every rank — replicas stay in lockstep.
    params = jax.tree.map(lambda p, g: p - lr * g, params, grads)

    # Per-rank values get a leading length-1 axis so shard_map can
    # concatenate them over 'dp' (out_specs P('dp')) — without that they
    # would collapse to one undefined replica's value at the boundary.
    metrics = {"loss": mean_loss, "local_loss": loss[None]}
    if log_norms:
        # (6) per-param grad/weight norms, per rank (ddp_script.py:155-164)
        metrics["grad_norms"] = jax.tree.map(
            lambda g: jnp.linalg.norm(g)[None], grads)
        metrics["param_norms"] = jax.tree.map(
            lambda p: jnp.linalg.norm(p)[None], params)
    return params, metrics


def _broadcast_from_rank0(params, mesh: Mesh):
    """(2) param broadcast. Seeding already makes replicas identical
    (ddp_script.py:108); the broadcast is belt-and-braces exactly like
    the reference (:118-121). Expressed as: zero out every rank's params
    except rank 0, then psum — a broadcast built from an all-reduce."""

    def bcast(p):
        rank = jax.lax.axis_index("dp")
        keep = jnp.where(rank == 0, 1.0, 0.0)
        return jax.lax.psum(p * keep, "dp")

    fn = shard_map(
        lambda t: jax.tree.map(bcast, t),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    return fn(params)


def train_ddp(world_size: int | None = None, epochs: int = 3,
              batch_size: int = 32, lr: float = 0.01,
              dataset_size: int = 1000, seed: int = 42,
              log_norms: bool = False, log_dir: str | None = None,
              devices=None) -> dict:
    """Run the pedagogical DDP loop; returns final params + history."""
    devices = devices or jax.devices()
    world_size = world_size or len(devices)
    if world_size > len(devices):
        raise ValueError(
            f"world_size {world_size} > available devices "
            f"{len(devices)}")
    mesh = Mesh(np.asarray(devices[:world_size]), ("dp",))
    logger.info("playground DDP: world_size=%d on %s", world_size,
                devices[0].platform)

    if log_dir:  # per-rank log files (ddp_script.py:70-78)
        os.makedirs(log_dir, exist_ok=True)

    # (1) identical seed everywhere → identical init (ddp_script.py:108)
    params = init_params(jax.random.PRNGKey(seed))
    # (2) broadcast from rank 0
    params = _broadcast_from_rank0(params, mesh)

    x, y = make_dataset(dataset_size, seed=seed)
    # (3) shard data by rank — same strided DistributedSampler arithmetic
    # as production (data/sampler.py)
    from distributed_training_tpu.data.sampler import (
        DistributedShardSampler,
    )
    sampler = DistributedShardSampler(dataset_size, world_size,
                                      shuffle=True, seed=seed)

    batch_sharding = NamedSharding(mesh, P("dp"))
    metric_specs = {"loss": P(), "local_loss": P("dp")}
    if log_norms:
        ptree = jax.tree.map(lambda _: P("dp"), params)
        metric_specs["grad_norms"] = ptree
        metric_specs["param_norms"] = ptree
    step = shard_map(
        functools.partial(_rank_step, log_norms=log_norms),
        mesh=mesh,
        in_specs=(P(), P("dp"), P("dp"), P()),
        out_specs=(P(), metric_specs),
        check_vma=False,
    )
    # Donate the params buffer: the caller rebinds ``params`` to the
    # step's output every iteration, so the old copy is dead — same
    # contract as the production trainer's donate_argnums=(0,).
    step = jax.jit(step, static_argnames=(), donate_argnums=(0,))

    steps_per_epoch = sampler.num_samples // batch_size
    history: list[dict] = []
    for epoch in range(epochs):
        sampler.set_epoch(epoch)  # reshuffle (ddp_script.py:140)
        shard_idx = np.stack([sampler.shard_indices(r)
                              for r in range(world_size)])
        epoch_losses = []
        for s in range(steps_per_epoch):
            rows = shard_idx[:, s * batch_size:(s + 1) * batch_size]
            xb = jax.device_put(x[rows.reshape(-1)], batch_sharding)
            yb = jax.device_put(y[rows.reshape(-1)], batch_sharding)
            lr_arr = jnp.float32(lr)
            params, metrics = step(params, xb, yb, lr_arr)
            epoch_losses.append(float(metrics["loss"]))
            if log_norms and log_dir:
                _write_rank_logs(log_dir, epoch, s, metrics, world_size)
        entry = {"epoch": epoch,
                 "mean_loss": float(np.mean(epoch_losses))}
        history.append(entry)
        logger.info("epoch %d | mean_loss %.6f", epoch,
                    entry["mean_loss"])

    return {"params": params, "history": history, "mesh": mesh}


def _write_rank_logs(log_dir, epoch, step, metrics, world_size):
    """Per-rank log files like logs/ddp_rank_<r>.log (ddp_script.py:74).
    ``metrics['local_loss']`` etc. carry one entry per rank."""
    local = np.asarray(metrics["local_loss"])
    gnorms = {k: np.asarray(v) for k, v in
              _flatten(metrics.get("grad_norms", {})).items()}
    pnorms = {k: np.asarray(v) for k, v in
              _flatten(metrics.get("param_norms", {})).items()}
    for r in range(world_size):
        path = os.path.join(log_dir, f"ddp_rank_{r}.log")
        norm_txt = " ".join(f"|g[{k}]|={v[r]:.4f}"
                            for k, v in gnorms.items())
        wnorm_txt = " ".join(f"|w[{k}]|={v[r]:.4f}"
                             for k, v in pnorms.items())
        with open(path, "a") as f:
            f.write(f"epoch={epoch} step={step} "
                    f"local_loss={local[r]:.6f} {norm_txt} "
                    f"{wnorm_txt}\n")


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}."))
    else:
        out[prefix.rstrip(".")] = tree
    return out


def main(argv=None) -> int:
    # argparse CLI, parity: ddp_script.py:186-241
    p = argparse.ArgumentParser(
        description="DDP from collective primitives (pedagogical)")
    p.add_argument("--world-size", type=int, default=None,
                   help="ranks (devices); default: all devices")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--dataset-size", type=int, default=1000)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--log-norms", action="store_true",
                   help="per-rank grad/weight norm logging (ref B8: "
                        "off by default, it is instrumentation)")
    p.add_argument("--log-dir", default="logs")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    result = train_ddp(
        world_size=args.world_size, epochs=args.epochs,
        batch_size=args.batch_size, lr=args.lr,
        dataset_size=args.dataset_size, seed=args.seed,
        log_norms=args.log_norms, log_dir=args.log_dir)
    print(f"final mean_loss: {result['history'][-1]['mean_loss']:.6f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
