"""CLI: consolidate an existing Orbax checkpoint into one file.

Offline counterpart of ``train.gather_on_save`` — point it at a
checkpoint directory the trainer wrote and get the single portable
msgpack artifact (checkpoint/consolidate.py format) without
reconstructing the model or mesh. Single-process tool: it restores
shards to host memory, so it is meant for a workstation with enough
RAM, not a pod (use gather_on_save there — its gather stays sharded
until the collective).

    python -m distributed_training_tpu.checkpoint.export \
        --ckpt outputs/default/checkpoints --out model.msgpack
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def restore_step_local(ckpt_dir: str, step: int | None = None
                       ) -> tuple[dict, int]:
    """Restore one checkpoint step's full state onto the LOCAL default
    device via the checkpoint's own tree metadata — NOT the saved
    shardings, so a pod checkpoint opens on any topology (usually a
    single host). Returns (state, step); ``step=None`` → newest.
    Shared by the export CLI and the generation CLI."""
    import jax
    import orbax.checkpoint as ocp
    from jax.sharding import SingleDeviceSharding

    ckpt_dir = os.path.abspath(ckpt_dir)
    if step is None:
        steps = sorted(int(d) for d in os.listdir(ckpt_dir)
                       if d.isdigit())
        if not steps:
            raise FileNotFoundError(
                f"no checkpoint steps found under {ckpt_dir}")
        step = steps[-1]
    state_path = os.path.join(ckpt_dir, str(step), "state")
    if not os.path.isdir(state_path):
        raise FileNotFoundError(
            f"checkpoint step {step} not found in {ckpt_dir} "
            f"({state_path} does not exist)")

    dev = jax.devices()[0]
    ckptr = ocp.PyTreeCheckpointer()
    # Orbax API drift: PyTreeCheckpointer.metadata() returns the tree
    # metadata directly on the version pinned here; newer releases
    # wrap it in StepMetadata(item_metadata=...). Accept both.
    meta = ckptr.metadata(state_path)
    item = getattr(meta, "item_metadata", None)
    tree = getattr(item, "tree", item) if item is not None else meta
    restore_args = jax.tree.map(
        lambda _m: ocp.ArrayRestoreArgs(
            sharding=SingleDeviceSharding(dev)), tree)
    state = ckptr.restore(
        state_path,
        args=ocp.args.PyTreeRestore(restore_args=restore_args))
    return state, int(step)


def _plan_provenance(ckpt_dir: str, plan: str | None) -> dict | None:
    """The ``sharding_plan`` stamp for the artifact meta: the source
    run's plan NAME + FINGERPRINT, so a serving stack
    (serving/disagg.py WeightStore) can refuse to lay these weights
    out when the committed plan has been regenerated since export.

    ``plan``: None → auto-detect from the run's resolved_config.yaml
    (the directory above ``ckpt_dir``), absent/unpinned → no stamp
    (legacy shape — loads with a warning downstream); "none" →
    explicitly no stamp; anything else → that plan name/path."""
    import yaml

    name = plan
    if name is None:
        cfg_path = os.path.join(os.path.dirname(ckpt_dir),
                                "resolved_config.yaml")
        if not os.path.exists(cfg_path):
            return None
        with open(cfg_path) as f:
            resolved = yaml.safe_load(f) or {}
        name = (resolved.get("train") or {}).get("sharding_plan") or ""
        if not name:
            return None
    if name == "none":
        return None
    from distributed_training_tpu.parallel.planner import load_plan
    p = load_plan(name)
    return {"name": p.name, "fingerprint": p.fingerprint()}


# Public name: callers publishing weights at runtime (the hot-swap
# path — Engine.swap_weights provenance gate) need the same stamp the
# export CLI writes, from the same implementation, so the two can
# never disagree. The underscore name stays for the existing pins.
plan_provenance = _plan_provenance


def export(ckpt_dir: str, out_path: str, step: int | None = None,
           plan: str | None = None,
           quantize: str | None = None) -> dict:
    import jax

    if quantize not in (None, "int8"):
        raise ValueError(
            f"unsupported --quantize '{quantize}' (supported: int8)")
    ckpt_dir = os.path.abspath(ckpt_dir)
    state, step = restore_step_local(ckpt_dir, step)

    meta: dict = {}
    meta_file = os.path.join(ckpt_dir, str(step), "meta", "metadata")
    if os.path.exists(meta_file):
        with open(meta_file) as f:
            meta = json.load(f) or {}
    meta.setdefault("step", int(step))
    prov = _plan_provenance(ckpt_dir, plan)
    if prov is not None:
        meta["sharding_plan"] = prov

    state = jax.tree.map(jax.device_get, state)
    if quantize == "int8":
        # Weight-only int8 serving artifact: the params subtree goes
        # per-channel int8 (serving/disagg.py quantize_params_int8);
        # the stamp is load-bearing — WeightStore validates it and
        # the parity tests gate the layout against fp32 logits.
        from distributed_training_tpu.serving.disagg import (
            quantize_params_int8)
        if "params" in state:
            state = dict(state)
            state["params"] = quantize_params_int8(state["params"])
        else:
            state = quantize_params_int8(state)
        meta["quantization"] = "int8"

    from distributed_training_tpu.checkpoint.consolidate import (
        write_artifact,
    )
    n = write_artifact(out_path, state, meta)
    return {"out": out_path, "step": int(step), "bytes": n,
            "quantization": quantize or "none"}


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ckpt", required=True,
                   help="Orbax checkpoint directory (snapshot_path)")
    p.add_argument("--out", required=True, help="output .msgpack path")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: latest)")
    p.add_argument("--plan", default=None,
                   help="sharding-plan provenance to stamp into the "
                        "artifact meta (default: auto-detect the "
                        "run's train.sharding_plan; 'none' to skip)")
    p.add_argument("--quantize", default=None, choices=("int8",),
                   help="weight-only quantization for the exported "
                        "params (per-channel int8; stamped into the "
                        "artifact meta for WeightStore validation)")
    args = p.parse_args(argv)
    from distributed_training_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    print(json.dumps(export(args.ckpt, args.out, args.step,
                            plan=args.plan, quantize=args.quantize)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
