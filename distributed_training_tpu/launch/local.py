"""Local multi-process launcher.

Spawns ``num_processes`` OS processes on this machine, each a full
"host" in a ``jax.distributed`` cluster rendezvousing at a local TCP
coordinator — the counterpart of ``mp.spawn(train, nprocs=ws)`` +
``MASTER_ADDR=localhost:12355`` in the reference playground
(src/playground/ddp_script.py:39-48,254-256) and of torchrun's local
mode. Each child gets ``DTT_COORDINATOR`` / ``DTT_NUM_PROCESSES`` /
``DTT_PROCESS_ID``, which ``runtime._maybe_init_distributed`` consumes.

Children default to the CPU platform with a configurable number of fake
devices per process, so an 8-"chip" 2-host pod is simulated as
``launch_local(["-m", "distributed_training_tpu.train"], 2,
devices_per_process=4)`` on any machine.

This is a CPU simulation launcher. Every child inherits the whole
host, so on an accelerator platform each of N > 1 children would claim
every chip — and a chip belongs to one process. That combination is
refused: on a multi-chip TPU host the trainer is ONE process driving
all local devices (``python -m distributed_training_tpu.train``).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import logging
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass

from distributed_training_tpu.resilience.elastic import GroupReport

logger = logging.getLogger(__name__)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Exported per spawn attempt (see ``run_group``): which port-retry
# attempt a child belongs to. Production children ignore it; tests use
# it to script a first-attempt bind failure.
ENV_PORT_ATTEMPT = "DTT_PORT_ATTEMPT"

# What a jax coordinator whose TCP port was stolen between our
# ``_free_port`` probe and its own bind prints before dying — the
# TOCTOU race ``run_group`` retries with a fresh port. Both the errno
# string (grpc/absl) and the grpc status text, either casing.
_BIND_FAILURE_MARKERS = ("Address already in use",
                         "ADDRESS_IN_USE",
                         "Failed to bind to address")


def _free_port(attempts: int = 8) -> int:
    """Pick a free TCP port (bounded retry).

    The bind-then-close probe is inherently TOCTOU — another process
    can take the port between our close and the coordinator child's
    bind seconds later. The retry here only covers probe-time failures
    (ephemeral-range exhaustion); the coordinator-side half of the
    race is handled by ``run_group``, which relaunches the group on a
    fresh port when the coordinator's log shows a bind failure."""
    last: OSError | None = None
    for attempt in range(attempts):
        try:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]
        except OSError as e:  # ephemeral ports exhausted: back off
            last = e
            time.sleep(0.05 * (attempt + 1))
    raise RuntimeError(
        f"could not acquire a coordinator port after {attempts} "
        f"attempts: {last}")


@dataclass
class LocalProcess:
    process_id: int
    proc: subprocess.Popen
    log_path: str | None


def launch_local(
    argv: list[str],
    num_processes: int,
    devices_per_process: int = 1,
    log_dir: str | None = None,
    env: dict[str, str] | None = None,
    coordinator_port: int | None = None,
) -> list[LocalProcess]:
    """Spawn the local process group; returns handles (non-blocking).

    ``argv`` is everything after ``python`` (e.g. ``["-m",
    "distributed_training_tpu.train", "train.total_epochs=2"]``).
    Per-process logs go to ``log_dir/proc_<i>.log`` when given —
    mirroring the reference playground's per-rank log files
    (ddp_script.py:74).
    """
    platform = (env or {}).get("JAX_PLATFORMS", "cpu")
    if platform != "cpu" and num_processes > 1:
        raise ValueError(
            f"launch_local is a CPU simulation launcher: "
            f"JAX_PLATFORMS={platform!r} with {num_processes} processes "
            f"would have every child claim every chip of this host. Run "
            f"one process for all local chips instead")
    port = coordinator_port or _free_port()
    procs: list[LocalProcess] = []
    for pid in range(num_processes):
        child_env = dict(os.environ)
        child_env.update(env or {})
        child_env.update({
            "DTT_COORDINATOR": f"127.0.0.1:{port}",
            "DTT_NUM_PROCESSES": str(num_processes),
            "DTT_PROCESS_ID": str(pid),
            "JAX_PLATFORMS": platform,
            "XLA_FLAGS": (
                child_env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count="
                  f"{devices_per_process}").strip(),
        })
        log_path = None
        stdout = None
        if log_dir is not None:
            os.makedirs(log_dir, exist_ok=True)
            log_path = os.path.join(log_dir, f"proc_{pid}.log")
            stdout = open(log_path, "w")
        try:
            proc = subprocess.Popen(
                [sys.executable, *argv], env=child_env,
                stdout=stdout,
                stderr=subprocess.STDOUT if stdout else None)
        finally:
            if stdout is not None:
                stdout.close()  # child holds its own descriptor
        procs.append(LocalProcess(pid, proc, log_path))
    return procs


# Set by _forward_signals' handler: the LAUNCHER itself was told to
# stop. The supervisor checks it so a preempted launcher tears down
# (clean child saves, then exit) instead of restarting the job the
# infrastructure just asked it to release.
_launcher_signaled: bool = False


@contextlib.contextmanager
def _forward_signals(procs: list[LocalProcess],
                     signums=(signal.SIGTERM, signal.SIGINT)):
    """While waiting, forward SIGTERM/SIGINT to the children instead
    of dying around them: when the LAUNCHER is preempted, the workers'
    ``PreemptionGuard`` must still fire (clean final save) — without
    the forward, the launcher exits and the orphaned workers never see
    the signal. The handler only forwards; teardown happens naturally
    when the (now cleanly exiting) children are reaped. No-op when not
    on the main thread (signal.signal would raise there)."""
    def handler(signum, frame):
        del frame
        global _launcher_signaled
        _launcher_signaled = True
        logger.warning("launcher got %s — forwarding to %d child "
                       "process(es)", signal.Signals(signum).name,
                       len(procs))
        for lp in procs:
            if lp.proc.poll() is None:
                try:
                    lp.proc.send_signal(signum)
                except (ProcessLookupError, OSError):
                    continue  # already reaped/exiting

    prev: dict[int, object] = {}
    try:
        for s in signums:
            prev[s] = signal.signal(s, handler)
    except ValueError:  # not the main thread: nothing to forward
        yield
        return
    try:
        yield
    finally:
        for s, p in prev.items():
            signal.signal(s, p)


def wait(procs: list[LocalProcess], timeout: float | None = None) -> int:
    """Wait for all processes; kill the group on first failure (the
    fail-fast behavior torchrun provides). Returns max exit code.
    SIGTERM/SIGINT delivered to the launcher while waiting are
    forwarded to the children first (see ``_forward_signals``)."""
    return wait_report(procs, timeout).returncode


def wait_report(procs: list[LocalProcess],
                timeout: float | None = None) -> GroupReport:
    """Like ``wait`` but returns the full ``GroupReport``: which
    processes failed on their own vs. were killed in the fail-fast
    sweep. The distinction is what lets the elastic supervisor tell
    "host 2 died under the others" (shrink and continue) from
    "everything crashed" (retry)."""
    with _forward_signals(procs):
        return _wait_inner(procs, timeout)


def _wait_inner(procs: list[LocalProcess],
                timeout: float | None = None) -> GroupReport:
    deadline = None if timeout is None else time.monotonic() + timeout
    pending = list(procs)
    worst = 0
    killed_ids: set[int] = set()
    self_failed: list[int] = []
    killed: list[int] = []
    completed: list[int] = []
    while pending:
        for lp in list(pending):
            budget = None
            if deadline is not None:
                budget = max(0.0, deadline - time.monotonic())
            try:
                code = lp.proc.wait(timeout=0.2 if budget is None
                                    else min(0.2, budget or 0.01))
            except subprocess.TimeoutExpired:
                if deadline is not None and time.monotonic() >= deadline:
                    for other in pending:
                        other.proc.kill()
                    raise TimeoutError(
                        f"local launch timed out after {timeout}s; "
                        f"pending={[p.process_id for p in pending]}")
                continue
            pending.remove(lp)
            if code == 0:
                completed.append(lp.process_id)
                continue
            if lp.process_id in killed_ids:
                # Died because WE killed it in the fail-fast sweep —
                # a consequence of the first failure, not a cause.
                killed.append(lp.process_id)
                continue
            self_failed.append(lp.process_id)
            if worst == 0:
                # Signal deaths are negative Popen returncodes; report
                # them as failures, not max(0, -11) == 0.
                worst = code if code > 0 else 128 - code
            logger.error(
                "process %d exited %d%s — killing group",
                lp.process_id, code,
                f" (log: {lp.log_path})" if lp.log_path else "")
            for other in pending:
                # Only count a process as launcher-killed if it was
                # still ALIVE at sweep time: in a whole-group crash
                # (every host hits the same fault) the siblings are
                # already dead with their own exit codes when the
                # first reap triggers the sweep, and marking them
                # "killed" would make the group read as a strict-
                # subset host loss — the elastic policy would shrink
                # around a crash that must burn retry budget.
                if other.proc.poll() is None:
                    killed_ids.add(other.process_id)
                    other.proc.kill()
    return GroupReport(returncode=worst, world_size=len(procs),
                       self_failed=tuple(sorted(self_failed)),
                       killed=tuple(sorted(killed)),
                       completed=tuple(sorted(completed)))


def coordinator_bind_failed(procs: list[LocalProcess]) -> bool:
    """Did this (failed) group die because the coordinator lost the
    ``_free_port`` TOCTOU race? Only readable when the group ran with
    a log_dir (the launcher paths all do). Scoped to PROCESS 0's log —
    the coordinator is the process that binds the port; a generic
    "address in use" string in some other child's crash traceback
    (e.g. an unrelated service port) must not be misread as the race
    and burn relaunch attempts on a deterministic crash."""
    lp = next((p for p in procs if p.process_id == 0), None)
    if lp is None or lp.log_path is None:
        return False
    try:
        with open(lp.log_path, errors="replace") as f:
            # A bind failure happens at STARTUP — the marker is in
            # the first lines; never slurp a long run's whole log.
            text = f.read(65536)
    except OSError:
        return False
    return any(m in text for m in _BIND_FAILURE_MARKERS)


def run_group(argv: list[str], num_processes: int,
              devices_per_process: int = 1,
              log_dir: str | None = None,
              env: dict[str, str] | None = None,
              timeout: float | None = None,
              port_attempts: int = 3,
              on_procs=None) -> GroupReport:
    """Launch + wait, retrying the whole group on a fresh port when
    the coordinator's bind lost the ``_free_port`` TOCTOU race —
    bounded, so a genuinely unbindable environment still fails. Every
    attempt exports ``DTT_PORT_ATTEMPT`` so a retry is observable (and
    scriptable by tests). ``on_procs`` (procs -> optional cleanup
    callable) lets a caller attach a watcher to the live group —
    the elastic grow watcher rides this."""
    report = GroupReport(returncode=1, world_size=num_processes)
    for attempt in range(max(1, port_attempts)):
        attempt_env = dict(env or {})
        attempt_env[ENV_PORT_ATTEMPT] = str(attempt)
        procs = launch_local(argv, num_processes, devices_per_process,
                             log_dir=log_dir, env=attempt_env)
        cleanup = on_procs(procs) if on_procs is not None else None
        try:
            report = wait_report(procs, timeout)
        finally:
            if cleanup is not None:
                cleanup()
        if report.returncode == 0:
            return report
        if (attempt + 1 >= max(1, port_attempts)
                or not coordinator_bind_failed(procs)):
            return report
        logger.warning(
            "coordinator port bind failed (TOCTOU race); retrying "
            "the group on a fresh port (attempt %d/%d)",
            attempt + 2, port_attempts)
    return report


def apply_overlap_flags_from_cmd(cmd: list[str],
                                 platform: str = "cpu") -> list[str]:
    """Scheduled comms/compute overlap for launched children: when
    the train command pins a sharding plan
    (``train.sharding_plan=<name|path>``), derive the plan's XLA
    latency-hiding flags (``parallel/overlap.py``) and append them to
    this process's ``XLA_FLAGS`` — ``launch_local`` builds every
    child's env from it, so the whole simulated pod compiles the
    scheduled program. Raw-JSON read, no planner import: a bad plan
    stays the CHILD CLI's loud failure, not a launcher crash. Returns
    the applied flag names (empty when no plan is pinned, the command
    disables ``train.xla_overlap_flags``, or everything was already
    set)."""
    import yaml
    plan_ref = None
    enabled = True
    for arg in cmd:
        if arg.startswith("train.sharding_plan="):
            plan_ref = arg.split("=", 1)[1]
        elif arg.startswith("train.xla_overlap_flags="):
            # Parse the override exactly as the child's config layer
            # will (yaml.safe_load — 'off'/'False'/'no' are False,
            # '0' is a falsy int the bool field keeps), and with the
            # same LAST-WINS semantics over repeated overrides: the
            # launcher must reach the same verdict the child's
            # resolved config does.
            try:
                enabled = bool(yaml.safe_load(arg.split("=", 1)[1]))
            except yaml.YAMLError:
                pass  # the child CLI owns the loud parse failure
    if not plan_ref or not enabled:
        return []
    path = plan_ref if os.path.exists(plan_ref) else os.path.join(
        _REPO, "conf", "plans", f"{plan_ref}.json")
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []  # child CLI owns the loud plan-load failure
    from distributed_training_tpu.parallel import overlap
    applied = overlap.apply_to_env(
        overlap.flags_for_plan_doc(doc, platform))
    if applied:
        logger.info("comms/compute overlap: applied XLA flags %s "
                    "for plan %s", applied, doc.get("name", path))
    return applied


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="dtt-launch-local",
        description="Simulate a multi-host TPU pod with local processes")
    p.add_argument("--nproc", type=int, default=2)
    p.add_argument("--devices-per-proc", type=int, default=4)
    p.add_argument("--log-dir", default="outputs/local_launch")
    p.add_argument("--summarize", default=None, metavar="RUN_DIR",
                   help="after a clean exit, render the run dir's "
                        "merged cross-host telemetry report (each "
                        "simulated host writes host_<i>/events.jsonl; "
                        "see docs/observability.md)")
    p.add_argument("--supervise", action="store_true",
                   help="restart dead training processes with backoff "
                        "(resilience/supervisor.py): exits are "
                        "classified (completed/preempted/watchdog-"
                        "abort/crash) and a restart that advances the "
                        "checkpoint refunds the retry budget, so a "
                        "crash-loop gives up fast — docs/robustness.md")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="retry budget between checkpoint advances")
    p.add_argument("--backoff-base-s", type=float, default=1.0,
                   help="first restart delay; doubles per consecutive "
                        "non-advancing failure (jittered, capped)")
    p.add_argument("--ckpt-dir", default=None, metavar="DIR",
                   help="checkpoint dir to watch for progress-based "
                        "budget refunds (pass the run's "
                        "train.snapshot_path; without it every "
                        "failure burns budget)")
    p.add_argument("--elastic", action="store_true",
                   help="with --supervise: on a lost or evicted host, "
                        "re-form the job at the surviving world size "
                        "(resharded restore + rescaled per-host batch "
                        "via train.global_batch_size) instead of "
                        "retrying at full size, then grow back at a "
                        "checkpoint boundary — docs/robustness.md "
                        "'Elastic runs'")
    p.add_argument("--elastic-min-world", type=int, default=1,
                   help="never shrink below this many processes")
    p.add_argument("--elastic-grow-after-ckpts", type=int, default=1,
                   help="checkpoints a shrunken world must commit "
                        "before growing back (doubles per flap)")
    p.add_argument("--elastic-no-grow", action="store_true",
                   help="stay at the shrunken size for the rest of "
                        "the run")
    p.add_argument("--no-overlap-flags", action="store_true",
                   help="do not derive XLA latency-hiding-scheduler "
                        "flags from a train.sharding_plan= override "
                        "in the command (docs/performance.md "
                        "'Scheduled comms/compute overlap')")
    p.add_argument("--metrics-port", type=int, default=0,
                   metavar="PORT",
                   help="serve the coordinator's live Prometheus "
                        "endpoint (/metrics, /healthz) on this port "
                        "— appends train.metrics_port=PORT to the "
                        "train command (coordinator-gated there; see "
                        "docs/observability.md)")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="-- followed by the python argv to run")
    args = p.parse_args(argv)
    cmd = [c for c in args.cmd if c != "--"]
    if not cmd:
        cmd = ["-m", "distributed_training_tpu.train"]
    if args.metrics_port:
        cmd = cmd + [f"train.metrics_port={args.metrics_port}"]
    if args.elastic and not args.supervise:
        p.error("--elastic requires --supervise")
    if not args.no_overlap_flags:
        # Children default to the CPU platform (launch_local) unless
        # the caller's env says otherwise.
        from distributed_training_tpu.parallel import overlap
        apply_overlap_flags_from_cmd(
            cmd, platform=overlap.platform_from_env("cpu"))
    if args.supervise:
        rc = _supervised_main(args, cmd)
    else:
        rc = run_group(cmd, args.nproc, args.devices_per_proc,
                       log_dir=args.log_dir).returncode
    if rc == 0 and args.summarize:
        from distributed_training_tpu.telemetry import summarize
        summarize.main([args.summarize])
    return rc


class _GrowWatcher:
    """Signals a SHRUNKEN incarnation down at a checkpoint boundary so
    the supervisor can re-form at full size — the grow-back half of
    elastic training. Polls the checkpoint dir; once ``needed`` NEW
    steps have been committed since the incarnation started (the
    hysteresis dwell the supervisor computed), delivers SIGTERM to the
    group: the PreemptionGuard clean-save path runs, the incarnation
    exits ``preempted``, and the relaunch at base size restores the
    just-saved checkpoint. Never an in-band kill."""

    def __init__(self, procs: list[LocalProcess], ckpt_dir: str,
                 needed: int, poll_s: float = 0.3):
        from distributed_training_tpu.resilience.integrity import (
            checkpoint_steps_on_disk)
        self._scan = checkpoint_steps_on_disk
        self.procs = procs
        self.ckpt_dir = ckpt_dir
        self.needed = max(1, needed)
        self.poll_s = poll_s
        self.triggered = False
        self._baseline = set(self._scan(ckpt_dir))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="elastic-grow",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            new = set(self._scan(self.ckpt_dir)) - self._baseline
            if len(new) >= self.needed:
                if any(lp.proc.poll() is not None
                       for lp in self.procs):
                    # The group is already exiting (the dwell was met
                    # by the run's FINAL checkpoint, or a failure is
                    # mid-teardown): signaling now would relabel a
                    # completed run as preempted and waste a grow
                    # incarnation — the supervisor handles whatever
                    # boundary this turns out to be.
                    return
                self.triggered = True
                logger.warning(
                    "elastic: capacity available and %d new "
                    "checkpoint(s) committed at reduced size — "
                    "signaling the group down for grow-back",
                    len(new))
                for lp in self.procs:
                    if lp.proc.poll() is None:
                        try:
                            lp.proc.send_signal(signal.SIGTERM)
                        except (ProcessLookupError, OSError):
                            continue
                return
            self._stop.wait(self.poll_s)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


def _supervised_main(args, cmd: list[str]) -> int:
    """``--supervise``: run incarnations of the local process group
    under the restart supervisor. Supervisor state (exit sentinels,
    its own event stream) lives under ``<log_dir>/supervisor/``; each
    incarnation's per-process logs go to ``<log_dir>/attempt_<i>/``,
    next to a ``summary.json`` recording its outcome and topology
    (world size, evicted hosts) for postmortems."""
    from distributed_training_tpu.resilience import elastic as elastic_mod
    from distributed_training_tpu.resilience import supervisor as sup
    from distributed_training_tpu.telemetry import Telemetry
    state_dir = os.path.join(args.log_dir, "supervisor")
    tel = Telemetry(
        events_jsonl=os.path.join(state_dir, "events.jsonl"),
        fresh=False)
    elastic_policy = None
    if args.elastic:
        elastic_policy = elastic_mod.ElasticPolicy(
            base_world=args.nproc,
            min_world=args.elastic_min_world,
            grow=not args.elastic_no_grow,
            grow_after_ckpts=args.elastic_grow_after_ckpts)

    def run_incarnation(extra_env: dict[str, str]):
        attempt = extra_env.get(sup.ENV_RESTART_COUNT, "0")
        nproc = int(extra_env.get(elastic_mod.ENV_WORLD)
                    or args.nproc)
        grow_after = extra_env.get(elastic_mod.ENV_GROW_AFTER_CKPTS)
        watchers: list[_GrowWatcher] = []

        def on_procs(procs):
            if grow_after is None or not args.ckpt_dir:
                return None
            w = _GrowWatcher(procs, args.ckpt_dir, int(grow_after))
            watchers.append(w)
            return w.stop

        report = run_group(
            cmd, nproc, args.devices_per_proc,
            log_dir=os.path.join(args.log_dir, f"attempt_{attempt}"),
            env=extra_env, on_procs=on_procs)
        if any(w.triggered for w in watchers):
            report = dataclasses.replace(report, grow_requested=True)
        return report

    def on_incident(incident: sup.Incident) -> None:
        # Per-attempt summary next to its process logs: outcome +
        # resolved topology, so a postmortem can read the world-size
        # history straight off the attempt dirs.
        d = os.path.join(args.log_dir,
                         f"attempt_{incident.incarnation}")
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, "summary.json.tmp")
        with open(tmp, "w") as f:
            json.dump(dataclasses.asdict(incident), f, indent=1)
        os.replace(tmp, os.path.join(d, "summary.json"))

    try:
        result = sup.supervise(
            run_incarnation,
            policy=sup.RestartPolicy(
                max_restarts=args.max_restarts,
                backoff_base_s=args.backoff_base_s),
            state_dir=state_dir,
            ckpt_dir=args.ckpt_dir,
            telemetry=tel,
            should_stop=lambda: _launcher_signaled,
            elastic=elastic_policy,
            on_incident=on_incident)
    finally:
        tel.close()
    return result.returncode


if __name__ == "__main__":
    sys.exit(main())
