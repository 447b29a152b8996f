"""Driver for traffic of kind ``train``: the program's ``Trainer`` fed
by its ``ShardedDataLoader``, timed from step end to step end."""

from __future__ import annotations

import math
import time

from perfbench import common, yardstick


def build(ctx):
    """The trainer as the CLI builds it (``train/cli.py``): config from
    the traffic file's overrides, the runtime's mesh, the model at the
    configuration's sizes, synthetic token rows from the seed."""
    from distributed_training_tpu.config import load_config
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               SyntheticLMDataset)
    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.resilience import elastic
    from distributed_training_tpu.runtime import initialize_runtime
    from distributed_training_tpu.train.trainer import Trainer

    t, prog = ctx.traffic, ctx.config["program"]
    cfg = load_config(None, "config",
                      [*t["overrides"], f"train.seed={ctx.seed}"])
    rt = initialize_runtime(cfg)
    cfg.train.batch_size = elastic.per_shard_batch(
        t["global_batch"], rt.data_shard_count)
    model = build_model(prog["build_model"], dtype=cfg.train.dtype,
                        **prog["kwargs"], **t["model_kwargs"])
    dataset = SyntheticLMDataset(
        size=t["dataset_rows"], seq_len=t["seq_len"],
        vocab_size=prog["token_vocab"], seed=ctx.seed)
    loader = ShardedDataLoader(dataset, rt,
                               batch_size=cfg.train.batch_size,
                               shuffle=True, seed=ctx.seed)
    return Trainer(cfg, rt, model, loader), loader, dataset


def batches(loader):
    epoch = 0
    while True:
        yield from loader.epoch(epoch)
        epoch += 1


class Distance:
    """How far the program's loss and its gradients on ``rows`` are from
    the plain float32 reference's. The program's side is what
    ``make_train_step`` differentiates, ``model.loss(..., train=True)``:
    its kernels forward and backward, its remat, its loss head. The
    gradients are compared by ``|got - want| / |want|`` in the 2-norm,
    twice. ``grad_gap`` is the worst leaf of the reference's layout,
    layer by layer for the leaves of a layer: math that is skipped (a
    layer, an embedding, a bias) is off by 1 there, whatever the size
    of the model. ``grad_gap_whole`` is over all gradients at once: it
    hardly moves from seed to seed, so it tells precision that was
    lowered from the program's own bfloat16
    (``perfbench/tests/check_sensitivity.py`` measures both)."""

    def __init__(self, ctx, model, shardings):
        import jax
        import jax.numpy as jnp

        ref = common.load_reference(ctx.config)
        n_head = ctx.config["n_head"]

        def value_and_grad(f):
            return jax.jit(jax.value_and_grad(f),
                           out_shardings=(None, shardings))

        self.program = value_and_grad(lambda p, rows, rng: model.loss(
            p, {"tokens": rows}, rng, train=True)[0])
        self.reference = value_and_grad(lambda p, rows: ref.loss(
            ref.from_program(p), rows, n_head))

        @jax.jit
        def norms(got, want):
            got, want = ref.from_program(got), ref.from_program(want)
            out = {}
            for (path, g), w in zip(
                    jax.tree_util.tree_leaves_with_path(got),
                    jax.tree.leaves(want)):
                name = "/".join(str(k.key) for k in path)
                axes = tuple(range(int(name.startswith("layers/")),
                                   g.ndim))
                out[name] = (jnp.sqrt(jnp.sum((g - w) ** 2, axes)),
                             jnp.sqrt(jnp.sum(w ** 2, axes)))
            return out
        self.norms = norms

    def __call__(self, program_params, rows, rng, want) -> dict:
        """``want`` is ``self.reference(params, rows)``: loss and
        gradients."""
        import jax
        import numpy as np

        got_loss, got = self.program(program_params, rows, rng)
        want_loss, want = want
        worst, where, diff2, norm2 = 0.0, None, 0.0, 0.0
        for name, (diff, norm) in jax.device_get(
                self.norms(got, want)).items():
            # A leaf the program lacks (norm 0) counts for nothing, a
            # NaN for infinitely much.
            ratio = np.nan_to_num(diff.ravel() / np.where(
                norm.ravel() > 0, norm.ravel(), np.inf), nan=np.inf)
            if ratio.max() > worst:
                worst = float(ratio.max())
                where = f"{name}[{ratio.argmax()}]"
            diff2 += float(np.sum(diff.astype(np.float64) ** 2))
            norm2 += float(np.sum(norm.astype(np.float64) ** 2))
        whole = (diff2 / norm2) ** 0.5
        return {"program_loss": float(got_loss),
                "reference_loss": float(want_loss),
                "loss_gap": abs(float(got_loss) - float(want_loss)),
                "grad_gap": worst, "grad_gap_at": where,
                "grad_gap_whole": whole if whole == whole else float("inf")}


def initial(trainer, dataset, check_rows: int, draw: int = 0):
    """The initial parameters, made again from the seed (the trainer's
    have been trained, and are freed first), and the first
    ``check_rows`` rows of the data. ``draw`` above 0 gives other
    weights and other rows of the same kind, for
    ``check_sensitivity.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    shardings = trainer.state_shardings["params"]
    trainer.state = None
    key = trainer.init_rng if draw == 0 else jax.random.fold_in(
        trainer.init_rng, draw)
    params = jax.jit(trainer.model.init, out_shardings=shardings)(key)
    rows = jnp.asarray(dataset.batch(
        draw * check_rows + np.arange(check_rows))["tokens"])
    return params, shardings, rows


def check_against_reference(ctx, trainer, dataset) -> dict:
    """At the initial parameters, on a slice of the data: the program's
    loss and gradients against the reference's, each within the
    tolerance the traffic file gives with its reason."""
    t = ctx.traffic
    params, shardings, rows = initial(trainer, dataset, t["check_rows"])
    distance = Distance(ctx, trainer.model, shardings)
    found = distance(params, rows, trainer.step_rng,
                     distance.reference(params, rows))
    return {**found, **within(found, t)}


TOLERANCES = {"loss_gap": "loss_tolerance", "grad_gap": "grad_tolerance",
              "grad_gap_whole": "grad_whole_tolerance"}


def within(found: dict, traffic: dict) -> dict:
    """Each distance against the tolerance the traffic file gives it."""
    passes = {f"{gap}_passes": found[gap] <= traffic[key]
              for gap, key in TOLERANCES.items()}
    return {**{key: traffic[key] for key in TOLERANCES.values()},
            **passes, "ok": all(passes.values())}


def run(ctx) -> dict:
    import jax

    t = ctx.traffic
    trainer, loader, dataset = build(ctx)
    it = batches(loader)
    for _ in range(t["warmup_steps"]):
        metrics = trainer.train_step(next(it))
    jax.block_until_ready(metrics["loss"])
    compiled = trainer._step_fn._cache_size()

    ends: list = []     # host clock at the end of each step
    waits: list = []    # seconds each step waited for its batch
    losses: list = []
    pending: list = []  # the step dispatched last, not yet ended

    def one_step():
        """Dispatch a step, then wait for the one before it: the device
        always has its next step queued, as in ``Trainer._run_epoch``,
        and every step's end is still seen on the host clock."""
        with common.annotate("perfbench.next_batch", ctx.trace):
            t_wait = time.perf_counter()
            batch = next(it)
            waits.append(time.perf_counter() - t_wait)
        with common.annotate("perfbench.train_step", ctx.trace):
            pending.append(trainer.train_step(batch)["loss"])
            if len(pending) > 1:
                losses.append(float(pending.pop(0)))
                ends.append(time.perf_counter())

    def until(deadline):
        while time.perf_counter() < deadline:
            one_step()

    one_step()
    one_step()           # the first step end opens the window
    setup_s = ctx.window_opens()
    until(ends[0] + ctx.seconds)
    n = len(ends) - 1
    elapsed = ends[-1] - ends[0]
    wait_s = sum(waits[2:2 + n])
    trace = None
    if ctx.trace:
        trace = common.traced(ctx, lambda: until(
            time.perf_counter() + t["trace_seconds"]))
    losses.append(float(pending.pop()))
    recompiled = trainer._step_fn._cache_size() - compiled
    memory = common.memory_peaks()

    tokens_per_step = t["global_batch"] * t["seq_len"]
    chips = ctx.cell["chips"]
    check = check_against_reference(ctx, trainer, dataset)
    common.log(f"{n} steps in {elapsed:.3f}s; loss first {losses[0]:.4f} "
               f"last {losses[n]:.4f}; reference check {check}")
    finite = all(math.isfinite(x) for x in losses)
    c = ctx.config
    return {
        "correct": finite and check["ok"] and recompiled == 0,
        "attempted": n, "failed": 0 if finite else 1,
        "setup_s": setup_s, "memory": memory, "trace": trace,
        "end_to_end": {
            "train_tok_s_chip": n * tokens_per_step / elapsed / chips},
        "obs": {
            "train_steps": n, "train_elapsed_s": elapsed,
            "train_wait_s": wait_s,
            "tokens_per_step": tokens_per_step,
            "flops_per_token": yardstick.train_flops_per_token(
                c["n_embd"], c["n_layer"],
                c["program"]["kwargs"]["vocab_size"], c["n_positions"],
                t["seq_len"]),
            "recompiled": recompiled, "check": check},
    }
