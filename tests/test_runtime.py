"""Runtime/mesh layer tests on 8 fake CPU devices."""

import ast
import inspect
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_training_tpu import runtime
from distributed_training_tpu.config import Config, MeshConfig
from distributed_training_tpu.runtime import (
    MeshSpec, RuntimeError_, build_mesh, fake_cpu_runtime,
    initialize_runtime, runtime_for_mesh,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mesh_spec_resolve_fill():
    spec = MeshSpec.resolve(MeshConfig(dp=-1, fsdp=2), 8)
    assert spec.dp == 4 and spec.fsdp == 2 and spec.total == 8


def test_mesh_spec_resolve_exact():
    spec = MeshSpec.resolve(MeshConfig(dp=2, fsdp=2, tp=2), 8)
    assert spec.total == 8


def test_mesh_spec_mismatch_raises():
    with pytest.raises(RuntimeError_):
        MeshSpec.resolve(MeshConfig(dp=3, fsdp=1), 8)
    with pytest.raises(RuntimeError_):
        MeshSpec.resolve(MeshConfig(dp=-1, fsdp=3), 8)
    with pytest.raises(RuntimeError_):
        MeshSpec.resolve(MeshConfig(dp=-1, fsdp=-1), 8)


def test_build_mesh_axes():
    spec = MeshSpec(dp=2, fsdp=2, sp=2, tp=1, pp=1)
    mesh = build_mesh(spec, jax.devices("cpu")[:8])
    assert mesh.axis_names == ("pp", "dp", "fsdp", "sp", "tp")
    assert dict(zip(mesh.axis_names, mesh.devices.shape))["dp"] == 2


def test_initialize_runtime_cpu():
    cfg = Config()
    cfg.train.device = "cpu"
    rt = initialize_runtime(cfg)
    assert rt.num_devices == 8
    assert rt.spec.dp == 8  # -1 filled
    assert rt.is_coordinator
    assert rt.data_shard_count == 8
    assert "mesh" in rt.describe()


def test_fake_cpu_runtime_axes():
    rt = fake_cpu_runtime(8, fsdp=4)
    assert rt.spec.fsdp == 4 and rt.spec.dp == 2


def test_batch_sharding_places_shards(cpu8):
    x = jnp.arange(16.0).reshape(16, 1)
    y = jax.device_put(x, cpu8.batch_sharding)
    assert len(y.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_psum_over_mesh(cpu8):
    """XLA collective smoke test: jit + sharding constraint produces the
    same result as unsharded compute (the compiled-allreduce path that
    replaces NCCL; SURVEY.md §2.2)."""
    x = jnp.ones((8, 4))

    @jax.jit
    def f(x):
        x = jax.lax.with_sharding_constraint(x, cpu8.batch_sharding)
        return x.sum()

    assert float(f(x)) == 32.0


def test_runtime_for_mesh_roundtrip(cpu8):
    rt = runtime_for_mesh(cpu8.mesh)
    assert rt.spec == cpu8.spec


def test_sharding_helper(cpu8):
    s = cpu8.sharding("dp", None)
    assert s.spec == P("dp", None)


def test_mesh_zero_and_negative_sizes_rejected():
    with pytest.raises(RuntimeError_):
        MeshSpec.resolve(MeshConfig(dp=-1, fsdp=0), 8)
    with pytest.raises(RuntimeError_):
        MeshSpec.resolve(MeshConfig(dp=-2, fsdp=1), 8)


# -- no fallback that hides the device --------------------------------------


def test_device_auto_on_unrequested_cpu_raises(monkeypatch):
    """``train.device: auto`` means "the accelerator": when JAX fell
    back to a CPU nobody asked for, initialize_runtime raises instead
    of training there. (Tests themselves ask: conftest sets
    JAX_PLATFORMS=cpu, so the plain call works.)"""
    assert runtime.cpu_requested()
    assert initialize_runtime(Config()).platform == "cpu"
    monkeypatch.setattr(runtime, "cpu_requested", lambda: False)
    with pytest.raises(RuntimeError_, match="no accelerator"):
        initialize_runtime(Config())
    with pytest.raises(RuntimeError_, match="no accelerator"):
        runtime.default_platform()


@pytest.mark.parametrize("platforms,asked", [
    ("cpu", True), ("cpu,tpu", True), ("tpu,cpu", False), ("tpu", False),
    ("", False), (None, False)])
def test_cpu_requested_reads_the_first_platform(monkeypatch, platforms,
                                                asked):
    """``tpu,cpu`` (the chip machine's setting) is not a CPU request:
    only the first entry is the default backend."""
    stub = types.SimpleNamespace(
        config=types.SimpleNamespace(jax_platforms=platforms))
    monkeypatch.setattr(runtime, "jax", stub)
    assert runtime.cpu_requested() is asked


def _run_without_platform_request(*argv):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO
    return subprocess.run([sys.executable, *argv], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_train_cli_without_accelerator_or_cpu_request_fails(tmp_path):
    """The README's single-host command on a machine whose accelerator
    does not answer: non-zero exit naming the cause, no training."""
    out = _run_without_platform_request(
        "-m", "distributed_training_tpu.train",
        f"run.output_dir={tmp_path}")
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not os.path.exists(tmp_path / "default" / "metrics.jsonl")


@pytest.mark.parametrize("ask_cpu", [True, False])
def test_bench_without_accelerator_reports_nothing(ask_cpu):
    """bench.py measures a chip: on a CPU — asked for or fallen back
    to — it exits non-zero and prints no result line (no value, no
    ``last_measured`` reprint of an older number)."""
    if ask_cpu:
        out = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                             capture_output=True, text=True, timeout=300)
    else:
        out = _run_without_platform_request("bench.py")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


# -- compile cache ----------------------------------------------------------


def test_compile_cache_env_dir_is_left_alone(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself: the
    helper returns it and sets no directory in code."""
    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(runtime.jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_default_is_one_fixed_checkout_path(monkeypatch):
    """Unset, every call lands on the same git-ignored directory inside
    the checkout — the path is part of what the cache is found by, so
    nothing in it may come from tempfile, a pid, a host or the clock."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = runtime.enable_compile_cache()
        assert runtime.enable_compile_cache() == first
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=REPO)
    if ignored.returncode != 128:  # 128: not a git checkout
        assert ignored.returncode == 0


# -- code for the installed jax ---------------------------------------------


def test_every_shard_map_site_matches_installed_signature():
    """Every ``shard_map(...)`` call in the package passes only
    keywords the installed ``jax.shard_map`` accepts (``auto=`` /
    ``check_rep=`` were the 0.9 construction failure), and nothing
    imports the deprecated experimental module."""
    deprecated = ".".join(("jax", "experimental", "shard_map"))
    accepted = set(inspect.signature(jax.shard_map).parameters)
    sites = 0
    for root in ("distributed_training_tpu", "benchmarks"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    src = f.read()
                assert deprecated not in src, path
                for node in ast.walk(ast.parse(src)):
                    if (isinstance(node, ast.Call)
                            and getattr(node.func, "id", None)
                            == "shard_map"):
                        sites += 1
                        bad = {k.arg for k in node.keywords} - accepted
                        assert not bad, (path, node.lineno, bad)
    assert sites >= 10
