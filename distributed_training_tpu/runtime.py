"""Runtime layer: process environment + device mesh.

TPU-native replacement for the reference's ``DistributedEnvironment``
(reference: src/distributed_trainer.py:42-70) and its NCCL/Gloo process-group
bootstrap. Where the reference reads torchrun-injected RANK/LOCAL_RANK/
WORLD_SIZE and calls ``init_process_group`` (src/distributed_trainer.py:48-62),
here multi-host rendezvous is ``jax.distributed.initialize`` (auto-detected on
Cloud TPU pods) and the unit of parallelism is not a process rank but a
``jax.sharding.Mesh`` over all addressable devices, with logical axes:

- ``dp``   pure data parallelism (outermost; rides DCN across slices)
- ``fsdp`` parameter sharding (ZeRO-3 analogue; rides ICI)
- ``tp``   tensor/model parallelism (innermost, highest-bandwidth ICI)
- ``sp``   sequence/context parallelism (ring attention)
- ``pp``   pipeline stages

Collectives are never called imperatively at this layer: XLA emits
psum/all-gather/reduce-scatter/ppermute from sharding annotations on the
jitted train step (the compiled-collective counterpart of NCCL; SURVEY.md
§2.2/§2.4).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import time
from dataclasses import dataclass

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_training_tpu.config import Config, MeshConfig

logger = logging.getLogger(__name__)

# Canonical mesh axis order, outermost (slowest-varying, DCN-adjacent)
# to innermost (fastest ICI links).
AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_PP = "pp"
MESH_AXES = (AXIS_PP, AXIS_DP, AXIS_FSDP, AXIS_SP, AXIS_TP)

# The batch dimension is sharded over both data-parallel-like axes: FSDP
# shards data as well as params (torch-FSDP semantics, reference
# src/dist_strategy/fsdp_strategy.py), and dp adds pure replication groups.
BATCH_AXES = (AXIS_DP, AXIS_FSDP)


class RuntimeError_(RuntimeError):
    pass


@dataclass(frozen=True)
class MeshSpec:
    """Resolved (all-positive) mesh shape."""

    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1

    @property
    def total(self) -> int:
        return self.pp * self.dp * self.fsdp * self.sp * self.tp

    def as_dict(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in MESH_AXES}

    @staticmethod
    def resolve(cfg: MeshConfig, num_devices: int) -> "MeshSpec":
        """Fill at most one ``-1`` axis with the remaining device count."""
        sizes = {a: getattr(cfg, a) for a in MESH_AXES}
        bad = [a for a, s in sizes.items() if s != -1 and s < 1]
        if bad:
            raise RuntimeError_(
                f"mesh axis size must be -1 or >= 1; got "
                f"{ {a: sizes[a] for a in bad} }")
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise RuntimeError_(f"at most one mesh axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if num_devices % fixed != 0:
                raise RuntimeError_(
                    f"fixed mesh axes {sizes} (product {fixed}) do not divide "
                    f"device count {num_devices}")
            sizes[wild[0]] = num_devices // fixed
        elif fixed != num_devices:
            raise RuntimeError_(
                f"mesh {sizes} needs {fixed} devices but {num_devices} are "
                f"available")
        return MeshSpec(**sizes)


def build_mesh(spec: MeshSpec, devices: list | None = None) -> Mesh:
    """Build the device mesh.

    Uses ``mesh_utils.create_device_mesh`` so logical axes map onto the
    physical ICI torus sensibly (innermost logical axis → nearest
    neighbours). A topology-helper failure is an error: a mesh that
    ignores the physical layout must not pass for the planned one.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    shape = tuple(spec.as_dict()[a] for a in MESH_AXES)
    if math.prod(shape) != len(devices):
        raise RuntimeError_(
            f"mesh shape {shape} != device count {len(devices)}")
    dev_array = mesh_utils.create_device_mesh(
        shape, devices=devices, allow_split_physical_axes=True)
    return Mesh(dev_array, MESH_AXES)


@dataclass
class Runtime:
    """Everything a training program needs to know about where it runs.

    Interface parity with ``DistributedEnvironment`` (reference:
    src/distributed_trainer.py:42-70): ``process_index`` ↔ global rank,
    ``process_count`` ↔ world size (in units of hosts, as is natural on
    TPU where one process drives all local chips), ``is_coordinator`` ↔
    rank-0 checks used to gate logging/checkpointing.
    """

    mesh: Mesh
    spec: MeshSpec
    platform: str
    process_index: int
    process_count: int
    # Unix time captured right after a cross-host barrier at runtime
    # setup (initialize_runtime). Because every host leaves the barrier
    # at (nearly) the same instant, the per-host readings of this one
    # shared moment let the multi-host aggregator align the hosts'
    # wall clocks (telemetry/aggregate.py). None for runtimes built
    # without initialize_runtime (tests, dryruns) and for hosts whose
    # setup barrier failed — those merge with zero clock correction.
    clock_sync_unix: float | None = None

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def device_kind(self) -> str:
        """e.g. "TPU v5 lite" — feeds MFU's peak-FLOPs lookup."""
        return self.mesh.devices.flat[0].device_kind

    # -- shardings ---------------------------------------------------------

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def batch_sharding(self) -> NamedSharding:
        """Batch split across all data-parallel-like axes (dp, fsdp)."""
        return NamedSharding(self.mesh, P(BATCH_AXES))

    @property
    def data_shard_count(self) -> int:
        """Number of distinct data shards (≅ reference world_size for the
        DistributedSampler arithmetic)."""
        return self.spec.dp * self.spec.fsdp

    def clock_sync_record(self) -> dict:
        """Payload for this host's ``clock_sync`` telemetry event
        (docs/observability.md): the barrier-anchored timestamp plus
        process identity. ``t_sync`` is None when the runtime has no
        barrier-anchored reading (built without initialize_runtime, or
        the barrier failed): the aggregator only trusts numeric
        ``t_sync`` values, so these hosts merge with zero clock
        correction instead of a spurious one computed from startup
        skew."""
        return {
            "t_sync": self.clock_sync_unix,
            "process_index": self.process_index,
            "process_count": self.process_count,
        }

    def describe(self) -> str:
        return (f"platform={self.platform} devices={self.num_devices} "
                f"processes={self.process_count} mesh={self.spec.as_dict()}")


def _maybe_init_distributed() -> None:
    """Multi-host rendezvous.

    On Cloud TPU pods ``jax.distributed.initialize()`` auto-detects
    coordinator/process_id from the TPU metadata server (replacing the
    reference's torchrun + MASTER_ADDR:29500 rendezvous and the worker
    nc-probe loop, cloud-init.tftpl:18-32,61-77). Off-pod multi-process
    runs configure it with env vars; single-process runs skip it.
    """
    # NOTE: must not touch jax.devices()/process_count() before
    # jax.distributed.initialize() — that would initialize the local
    # backend and break pod formation. Decide from env vars only.
    coord = os.environ.get("DTT_COORDINATOR")
    nproc = os.environ.get("DTT_NUM_PROCESSES")
    pid = os.environ.get("DTT_PROCESS_ID")
    try:
        if coord and nproc and pid:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(nproc),
                process_id=int(pid),
            )
        elif os.environ.get("DTT_AUTO_DISTRIBUTED", "0") == "1":
            # TPU pod: everything auto-detected from the metadata server.
            jax.distributed.initialize()
    except RuntimeError as e:
        if "already" in str(e).lower():
            logger.info("jax.distributed already initialized by launcher")
        else:
            raise


# Default persistent compile cache: one fixed, git-ignored directory
# inside the checkout. The path is part of the cache key's lookup, so
# it must never move between runs (no tempfile, pid, host or clock).
_DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable;
    every entry point calls this before its first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set in code; otherwise the cache lives in the checkout's
    ``.jax_cache/``. Returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir",
                      _DEFAULT_COMPILE_CACHE_DIR)
    return _DEFAULT_COMPILE_CACHE_DIR


def cpu_requested() -> bool:
    """True when this process selected the CPU platform on purpose:
    ``JAX_PLATFORMS=cpu`` in the environment or ``train.device=cpu``
    (both land in ``jax_platforms``, whose first entry is the default
    backend — a trailing ``,cpu`` as in ``tpu,cpu`` is no request)."""
    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


def default_platform() -> str:
    """Platform of JAX's default backend. A CPU that nobody asked for
    means the accelerator did not answer and JAX fell back silently —
    that is an error here, never a slow run that looks healthy.
    Backend start-up errors propagate."""
    platform = jax.default_backend()
    if platform == "cpu" and not cpu_requested():
        raise RuntimeError_(
            "no accelerator: JAX fell back to the CPU backend. To run "
            "on the CPU on purpose set JAX_PLATFORMS=cpu or "
            "train.device=cpu")
    return platform


def initialize_runtime(cfg: Config) -> Runtime:
    """Build the runtime: rendezvous (if multi-host), pick devices per
    ``cfg.train.device``, resolve the mesh shape, and construct the
    mesh. ``auto`` means "the accelerator" (parity with the reference's
    device="auto" → cuda-if-available, src/distributed_trainer.py:
    53-58, minus its silent CPU fallback): it resolves to a CPU only
    when the CPU was asked for (``cpu_requested``)."""
    device_pref = cfg.train.device
    if device_pref == "cpu":
        # Select the CPU platform BEFORE anything (including
        # jax.distributed auto-detection below) can initialize a
        # backend: `device=cpu` (the reference's CPU/Gloo fallback,
        # src/distributed_trainer.py:55-61) must never depend on
        # accelerator health.
        jax.config.update("jax_platforms", "cpu")
    _maybe_init_distributed()

    if device_pref in ("auto", ""):
        default_platform()
        devices = jax.devices()
    else:
        try:
            devices = jax.devices(device_pref)
        except RuntimeError as e:
            raise RuntimeError_(
                f"requested device '{device_pref}' unavailable: {e}") from e

    spec = MeshSpec.resolve(cfg.mesh, len(devices))
    mesh = build_mesh(spec, devices)
    # Clock-sync sample for multi-host telemetry merging: every host
    # leaves this barrier at (to collective latency) the same instant,
    # so the per-host wall-clock readings of that one shared moment
    # give the offline aggregator each host's clock offset. Skipped
    # single-process — there is nothing to align.
    clock_sync_unix: float | None = time.time()
    if jax.process_count() > 1:
        try:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                "dtt_telemetry_clock_sync")
            clock_sync_unix = time.time()
        except Exception as e:  # noqa: BLE001 — a telemetry nicety
            # must never take down runtime setup (some backends, e.g.
            # multi-process CPU, lack cross-process computations). NO
            # t_sync is recorded for this host: an unsynced timestamp
            # would read as a barrier instant and the aggregator would
            # correct this host's timeline by what is actually startup
            # skew. Without one it merges with zero correction.
            clock_sync_unix = None
            logger.warning("telemetry clock-sync barrier failed "
                           "(%s); merged timelines will carry this "
                           "host's raw clock offset", e)
    rt = Runtime(
        mesh=mesh,
        spec=spec,
        platform=devices[0].platform,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        clock_sync_unix=clock_sync_unix,
    )
    logger.info("runtime initialized: %s", rt.describe())
    return rt


def runtime_for_mesh(mesh: Mesh) -> Runtime:
    """Wrap an externally-built mesh (tests, dryruns) in a Runtime."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    spec = MeshSpec(**{a: sizes.get(a, 1) for a in MESH_AXES})
    return Runtime(
        mesh=mesh,
        spec=spec,
        platform=mesh.devices.flat[0].platform,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
    )


def topology_runtime(num_devices: int = 4,
                     topology_name: str = "v5e:2x2",
                     **axis_sizes: int) -> Runtime:
    """A Runtime over DEVICE-LESS TPU topology descriptors
    (``jax.experimental.topologies``): the real TPU compiler (libtpu)
    compiles real SPMD programs for the named topology with no
    attached chips. Audit/AOT use only — the resulting mesh cannot
    hold data, so pair it with ``Trainer(..., abstract=True)`` and
    ShapeDtypeStruct inputs. This is how the repo inspects what the
    TPU backend (vs the CPU partitioner) compiles a sharded step into
    — e.g. whether FSDP's gradient sync becomes reduce-scatter
    (benchmarks/audit_collectives.py --tpu-topology)."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology_name)
    devices = list(topo.devices)
    if len(devices) < num_devices:
        raise RuntimeError_(
            f"topology {topology_name} has {len(devices)} devices, "
            f"need {num_devices}")
    devices = devices[:num_devices]
    cfg = MeshConfig(**{**{a: 1 for a in MESH_AXES}, "dp": -1,
                        **axis_sizes})
    spec = MeshSpec.resolve(cfg, num_devices)
    return dataclasses.replace(
        runtime_for_mesh(build_mesh(spec, devices)), platform="tpu",
        process_index=0, process_count=1)


def fake_cpu_runtime(num_devices: int = 8, **axis_sizes: int) -> Runtime:
    """Test/dryrun helper: a Runtime over CPU fake devices.

    The CPU analogue of the reference's Gloo fallback
    (src/distributed_trainer.py:55-61) — requires the process to have been
    started with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (tests/conftest.py does this).
    """
    devices = jax.devices("cpu")[:num_devices]
    if len(devices) < num_devices:
        raise RuntimeError_(
            f"need {num_devices} cpu devices, have {len(devices)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={num_devices}")
    cfg = MeshConfig(**{**{a: 1 for a in MESH_AXES}, "dp": -1, **axis_sizes})
    spec = MeshSpec.resolve(cfg, num_devices)
    return dataclasses.replace(
        runtime_for_mesh(build_mesh(spec, devices)), platform="cpu")
