"""End-to-end trainer tests on the 8-device CPU mesh.

This is SURVEY.md §7's "minimum end-to-end slice": config-driven MLP on
the synthetic dataset, DP and FSDP layouts, convergence on the learnable
task, replica consistency, and loss parity across strategies.
"""


import jax
import numpy as np
import pytest

from distributed_training_tpu.config import Config
from distributed_training_tpu.data import (ShardedDataLoader,
                                           SyntheticRegressionDataset)
from distributed_training_tpu.models.mlp import MLP
from distributed_training_tpu.runtime import fake_cpu_runtime
from distributed_training_tpu.train.trainer import Trainer


def make_trainer(rt, strategy="ddp", loss="mse", epochs=2, dataset=None,
                 **train_over):
    cfg = Config()
    cfg.train.parallel_strategy = strategy
    cfg.train.total_epochs = epochs
    cfg.train.batch_size = 4
    cfg.train.dataset_size = 128
    cfg.train.log_every = 0
    for k, v in train_over.items():
        setattr(cfg.train, k, v)
    ds = dataset or SyntheticRegressionDataset(
        size=cfg.train.dataset_size, in_dim=20, out_dim=1, seed=0,
        kind="linear")
    loader = ShardedDataLoader(ds, rt, batch_size=cfg.train.batch_size,
                               shuffle=cfg.train.shuffle,
                               seed=cfg.train.seed)
    model = MLP(input_size=20, output_size=1, loss_name=loss)
    return Trainer(cfg, rt, model, loader), cfg


def test_mlp_converges_dp(cpu8):
    trainer, _ = make_trainer(cpu8, "ddp", epochs=5,
                              learning_rate=0.05)
    first = trainer._run_epoch(0)["mean_loss"]
    summary = trainer.train()
    assert summary["mean_loss"] < first * 0.5, (
        f"no convergence: first={first}, last={summary['mean_loss']}")


def test_dp_and_fsdp_agree(cpu8):
    """DDP and FSDP are the same math in different layouts — identical
    data + init must give near-identical loss trajectories (the
    loss-curve-parity requirement, BASELINE.json north star)."""
    rt_fsdp = fake_cpu_runtime(8, fsdp=8)
    losses = {}
    for tag, rt, strat in (("ddp", cpu8, "ddp"), ("fsdp", rt_fsdp, "fsdp")):
        # min_shard_elems=1 forces real sharding of the tiny MLP's params
        # under fsdp (the (20,1) kernel won't split 8 ways, but bias and
        # any divisible dims will; layout differs from ddp either way).
        trainer, _ = make_trainer(rt, strat, epochs=2, learning_rate=0.05,
                                  min_shard_elems=1)
        summary = trainer.train()
        losses[tag] = summary["mean_loss"]
    assert losses["ddp"] == pytest.approx(losses["fsdp"], rel=1e-4)


def test_prob_xent_parity_is_gradient_free(cpu8):
    """Reference B5 preserved: the degenerate single-logit prob-xent loss
    trains nothing — loss identically 0, params unchanged."""
    ds = SyntheticRegressionDataset(size=64, seed=0)  # uniform parity data
    trainer, _ = make_trainer(cpu8, "ddp", loss="prob_xent", epochs=1,
                              dataset=ds, dataset_size=64)
    params_before = jax.tree.map(np.asarray, trainer.state["params"])
    summary = trainer.train()
    assert summary["mean_loss"] == pytest.approx(0.0, abs=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        trainer.state["params"], params_before)


def test_step_counter_and_state_sharded(cpu8):
    trainer, _ = make_trainer(cpu8, "ddp", epochs=1)
    trainer.train()
    # 128 samples / 8 shards / batch 4 = 4 steps/epoch
    assert int(trainer.state["step"]) == 4
    assert trainer.epochs_run == 1


def test_fsdp_params_actually_sharded():
    rt = fake_cpu_runtime(8, fsdp=8)
    trainer, _ = make_trainer(rt, "fsdp", epochs=1,
                              dataset=SyntheticRegressionDataset(
                                  size=128, in_dim=64, out_dim=8, seed=0,
                                  kind="linear"))
    # With min_shard_elems default the tiny MLP replicates; rebuild a
    # trainer with a bigger layer via hidden sizes to check sharding.
    model = MLP(input_size=64, output_size=8, hidden_sizes=[512])
    from distributed_training_tpu.parallel import get_strategy
    strat = get_strategy("fsdp", rt.spec, min_shard_elems=1)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = strat.specs_for_tree(shapes, model.logical_axes())
    # embedding-dim rule routes w to fsdp
    assert any("fsdp" in str(s) for s in jax.tree.leaves(
        specs, is_leaf=lambda x: True))


def test_nan_guard_skips_bad_step(cpu8):
    ds = SyntheticRegressionDataset(size=64, in_dim=20, out_dim=1,
                                    seed=0, kind="linear")
    bad = dict(ds.columns)
    bad["x"] = bad["x"].copy()
    bad["x"][:] = np.nan
    from distributed_training_tpu.data.datasets import ArrayDataset
    nan_ds = ArrayDataset(**bad)
    trainer, _ = make_trainer(cpu8, "ddp", epochs=1, dataset=nan_ds,
                              dataset_size=64, nan_guard=True)
    params_before = jax.tree.map(np.asarray, trainer.state["params"])
    trainer.train()
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        trainer.state["params"], params_before)


def test_adamw_cosine_warmup(cpu8):
    trainer, _ = make_trainer(cpu8, "ddp", epochs=2, optimizer="adamw",
                              lr_schedule="cosine", warmup_steps=2,
                              grad_clip_norm=1.0, learning_rate=0.01)
    summary = trainer.train()
    assert np.isfinite(summary["mean_loss"])


def test_evaluate(cpu8):
    trainer, _ = make_trainer(cpu8, "ddp", epochs=1)
    batches = list(trainer.loader.epoch(0))
    val = trainer.evaluate(batches)
    assert np.isfinite(val)


def test_save_every_zero_disables_checkpointing(cpu8, tmp_path):
    """save_every=0 means 'never save' — regression: it used to crash
    with ZeroDivisionError when a checkpointer was attached (the CLI
    always attaches one)."""
    from distributed_training_tpu.checkpoint import Checkpointer
    from distributed_training_tpu.data import SyntheticRegressionDataset

    cfg = Config()
    cfg.train.total_epochs = 2
    cfg.train.save_every = 0
    cfg.train.batch_size = 4
    cfg.train.log_every = 0
    cfg.train.snapshot_path = str(tmp_path / "ckpt")
    ds = SyntheticRegressionDataset(size=32, seed=0, kind="linear")
    loader = ShardedDataLoader(ds, cpu8, batch_size=4, shuffle=False)
    model = MLP(input_size=20, output_size=1)
    ckpt = Checkpointer(cfg.train.snapshot_path, async_save=False)
    trainer = Trainer(cfg, cpu8, model, loader, ckpt)
    trainer.train()
    assert ckpt.latest_step() is None  # nothing saved
    ckpt.close()


def test_metrics_jsonl_stream(cpu8, tmp_path):
    """metrics_jsonl appends one JSON line per recorded entry (loss
    rows and unthrottled val_loss rows)."""
    import json

    from distributed_training_tpu.data import SyntheticRegressionDataset
    from distributed_training_tpu.data.datasets import train_eval_split

    cfg = Config()
    cfg.train.total_epochs = 2
    cfg.train.batch_size = 4
    cfg.train.log_every = 1
    cfg.train.eval_every = 1
    cfg.train.metrics_jsonl = str(tmp_path / "metrics.jsonl")
    ds = SyntheticRegressionDataset(size=96, seed=0, kind="linear")
    train_ds, eval_ds = train_eval_split(ds, 0.25, seed=0,
                                         multiple_of=32)
    loader = ShardedDataLoader(train_ds, cpu8, batch_size=4,
                               shuffle=False)
    eval_loader = ShardedDataLoader(eval_ds, cpu8, batch_size=4,
                                    shuffle=False)
    model = MLP(input_size=20, output_size=1)
    trainer = Trainer(cfg, cpu8, model, loader,
                      eval_loader=eval_loader)
    trainer.train()
    lines = [json.loads(x) for x in
             open(cfg.train.metrics_jsonl).read().splitlines()]
    assert len(lines) >= 4
    assert lines[0] == {"run_start": True, "step": 0}
    assert any("loss" in e for e in lines)
    assert any("val_loss" in e for e in lines)
    steps = [e["step"] for e in lines]
    assert steps == sorted(steps)

    # A fresh run in the same run_dir truncates (no interleaving).
    trainer2 = Trainer(cfg, cpu8, model, loader,
                       eval_loader=eval_loader)
    trainer2.metrics.record(1, {"loss": float("nan")}, epoch=0)
    lines2 = [json.loads(x) for x in
              open(cfg.train.metrics_jsonl).read().splitlines()]
    assert lines2[0] == {"run_start": True, "step": 0}
    assert len(lines2) == 2          # truncated, then one new entry
    assert lines2[1]["loss"] is None  # NaN mapped to null, valid JSON


def test_vocab_mismatch_fails_preflight(cpu8):
    """A dataset whose token ids exceed the model's vocab previously
    trained to NaN (out-of-range embedding gathers clamp silently);
    the trainer must name the config mistake before tracing."""
    from distributed_training_tpu.data import SyntheticLMDataset
    from distributed_training_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = Config()
    cfg.train.batch_size = 1
    model = Transformer(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2,
        max_seq_len=16, dtype="float32", attention_impl="naive"))
    ds = SyntheticLMDataset(size=8, seq_len=16, vocab_size=50257,
                            seed=0)
    loader = ShardedDataLoader(ds, cpu8, batch_size=1)
    with pytest.raises(ValueError, match="vocab of 50257"):
        Trainer(cfg, cpu8, model, loader)


def test_first_step_and_audit_lower_the_same_program(cpu8):
    """The collectives audit lowers the step again from abstract
    inputs. Every state leaf — the step scalar included — is placed in
    its sharding at init, so both lowerings are the SAME module and
    the audit's compile is the first step's, served from JAX's cache
    (one backend compile on the chip, not two)."""
    from distributed_training_tpu.train import state as state_lib

    trainer, _ = make_trainer(cpu8, "ddp")
    batch = next(iter(trainer.loader.epoch(0)))
    abstract = state_lib.abstract_state(
        trainer.model, trainer.optimizer, trainer.init_rng,
        trainer._device_state_shardings)
    concrete = trainer._step_fn.lower(trainer.state, batch,
                                      trainer.step_rng).as_text()
    audited = trainer._step_fn.lower(abstract, batch,
                                     trainer.step_rng).as_text()
    assert concrete == audited
