"""Integration: local multi-process launch (the torchrun analogue).

The framework's counterpart of the reference playground's
``mp.spawn``-based CPU cluster simulation (src/playground/ddp_script.py:
244-256): two OS processes, each simulating a 2-device host, rendezvous
via ``jax.distributed`` at a local TCP coordinator and run the real CLI
end-to-end (config → runtime → data → trainer → checkpoint).
"""

import os

import pytest

from distributed_training_tpu.launch import local as launch_local_mod


@pytest.mark.slow
def test_two_process_training_run(tmp_path):
    log_dir = str(tmp_path / "logs")
    out_dir = str(tmp_path / "run")
    snap = str(tmp_path / "ckpt")
    procs = launch_local_mod.launch_local(
        [
            "-m", "distributed_training_tpu.train",
            f"run.output_dir={out_dir}",
            f"train.snapshot_path={snap}",
            "train.total_epochs=2",
            "train.dataset_size=64",
            "train.batch_size=8",
            "train.log_every=0",
            # exercise the COLLECTIVE consolidated export across
            # processes (every process must enter the gather; B6).
            "train.gather_on_save=true",
        ],
        num_processes=2,
        devices_per_process=2,
        log_dir=log_dir,
        # Children must not inherit the test process's platform pinning
        # in a way that conflicts; the launcher sets cpu + 2 fake devices.
        env={"JAX_PLATFORMS": "cpu"},
    )
    code = launch_local_mod.wait(procs, timeout=420)
    logs = "\n".join(
        open(p.log_path).read() for p in procs if p.log_path)
    assert code == 0, f"multi-process run failed:\n{logs[-4000:]}"
    # Both processes formed one 4-device cluster.
    assert "devices=4" in logs
    assert "processes=2" in logs
    # A checkpoint was written collectively.
    assert os.path.isdir(snap) and os.listdir(snap), (
        "no checkpoint written by multi-process run")
    consolidated = [f for f in os.listdir(snap)
                    if f.startswith("consolidated_")]
    assert consolidated, "collective export produced no artifact"
    from distributed_training_tpu.checkpoint import load_consolidated
    state, meta = load_consolidated(
        os.path.join(snap, sorted(consolidated)[-1]))
    assert "params" in state and "step" in meta


def test_wait_fail_fast(tmp_path):
    """A failing process kills the group (torchrun fail-fast)."""
    procs = launch_local_mod.launch_local(
        ["-c", "import sys,time,os; "
               "sys.exit(3) if os.environ['DTT_PROCESS_ID']=='0' "
               "else time.sleep(600)"],
        num_processes=2,
        log_dir=str(tmp_path),
    )
    code = launch_local_mod.wait(procs, timeout=60)
    assert code == 3


def test_refuses_accelerator_platform_with_several_processes():
    """A CPU simulation launcher: every child inherits the whole host,
    so N > 1 children on a TPU platform would each claim every chip —
    refused before anything is spawned. One process is fine."""
    with pytest.raises(ValueError, match="CPU simulation launcher"):
        launch_local_mod.launch_local(
            ["-c", "pass"], 2, env={"JAX_PLATFORMS": "tpu"})
