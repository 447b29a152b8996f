"""Training entrypoint.

Usage (parity with the reference's Hydra CLI,
``python src/distributed_trainer.py train.batch_size=64 ...``,
src/distributed_trainer.py:243-276):

    python -m distributed_training_tpu.train [key=value ...]
    python -m distributed_training_tpu.train --config-dir conf model=gpt2

Also exposed under the reference's historical entrypoint name via
``multigpu_multi_node.py`` at the repo root (the name the reference's
cloud bootstrap launches — which didn't exist there; SURVEY.md §8 B1).
One process per host on TPU pods; ``jax.distributed`` handles rendezvous.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

logger = logging.getLogger(__name__)


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dtt-train",
        description="TPU-native distributed training")
    p.add_argument("--config-dir", default=None,
                   help="config root (default: <repo>/conf)")
    p.add_argument("--config-name", default="config")
    p.add_argument("overrides", nargs="*",
                   help="key.path=value config overrides")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_argparser().parse_args(argv)

    from distributed_training_tpu.config import load_config, save_resolved
    from distributed_training_tpu.runtime import (enable_compile_cache,
                                                  initialize_runtime)
    from distributed_training_tpu.utils.logging import setup_logging

    enable_compile_cache()
    cfg = load_config(args.config_dir, args.config_name, args.overrides)

    run_dir = os.path.join(cfg.run.output_dir, cfg.run.experiment_name)
    os.makedirs(run_dir, exist_ok=True)

    plan = None
    applied_overlap_flags: list[str] = []
    if cfg.train.sharding_plan:
        # Pinned auto-parallelism plan (parallel/planner.py): the mesh
        # is DERIVED from it — model-sharding axes pinned to the
        # plan's extents, dp as the -1 wildcard so elastic
        # incarnations (PR 7 shrink/grow) re-form around the same
        # planned layout at a different data-parallel width. The
        # Trainer re-validates the resolved mesh against the plan.
        from distributed_training_tpu.parallel import planner
        plan = planner.apply_plan_to_config(cfg)
        if cfg.train.xla_overlap_flags:
            # Scheduled comms/compute overlap: the plan's XLA
            # latency-hiding flags must land in XLA_FLAGS BEFORE the
            # first backend init (initialize_runtime below), or the
            # compiler schedules without them. Platform must be known
            # without touching the backend — the env/device config is
            # authoritative; "auto" with no env stays unflagged (a
            # log line says so) rather than guessing wrong and
            # tripping an unknown-flag abort on another backend.
            from distributed_training_tpu.parallel import overlap
            platform = overlap.platform_from_env(
                cfg.train.device if cfg.train.device != "auto"
                else "")
            applied_overlap_flags = overlap.apply_to_env(
                plan.xla_overlap_flags(platform))

    rt = initialize_runtime(cfg)
    setup_logging(cfg.run.log_level,
                  os.path.join(run_dir, cfg.run.log_file),
                  rt.process_index)
    if plan is not None:
        # After setup_logging, or the line never reaches the run log.
        logger.info("sharding plan %s@%s: mesh derived %s",
                    plan.name, plan.fingerprint(), plan.mesh)
        if applied_overlap_flags:
            logger.info("comms/compute overlap: applied XLA flags %s",
                        applied_overlap_flags)
        elif cfg.train.xla_overlap_flags:
            logger.info("comms/compute overlap: no flags applied "
                        "(already set, platform unknown, or nothing "
                        "to hide on this mesh)")
    from distributed_training_tpu.resilience import elastic
    if cfg.train.global_batch_size:
        # Elastic contract: the GLOBAL batch is world-size-invariant;
        # the per-shard batch is derived from however many data shards
        # this incarnation's mesh resolved to (a shrunken world gets a
        # proportionally larger per-shard batch). Fails loudly on an
        # uneven split — silently changing the effective batch would
        # change the optimization trajectory.
        cfg.train.batch_size = elastic.per_shard_batch(
            cfg.train.global_batch_size, rt.data_shard_count)
        logger.info("global batch %d over %d shard(s) -> per-shard "
                    "batch %d", cfg.train.global_batch_size,
                    rt.data_shard_count, cfg.train.batch_size)
    # Topology this incarnation inherited from the elastic supervisor
    # (empty outside --elastic runs); recorded in the resume event so
    # postmortems can read the world-size history off the run stream.
    evicted_hosts = elastic.evicted_from_env()
    if not cfg.train.metrics_jsonl:
        cfg.train.metrics_jsonl = os.path.join(run_dir, "metrics.jsonl")
    # Multi-host: every process records its OWN event stream under
    # <run_dir>/host_<i>/ (a central writer would put a network hop in
    # the instrumentation path, and a dead coordinator would take all
    # evidence with it). The summarizer auto-detects the layout and
    # merges (telemetry/aggregate.py). Single-process runs keep the
    # flat <run_dir>/events.jsonl — EXCEPT under an elastic
    # supervisor: a run shrunk all the way to world 1 must keep
    # appending to host_0/events.jsonl, or the aggregate's recovery
    # table (which reads the coordinator's per-host stream) silently
    # loses the final incarnations of the topology history.
    elastic_incarnation = os.environ.get(elastic.ENV_WORLD) is not None
    host_dir = (run_dir
                if rt.process_count == 1 and not elastic_incarnation
                else os.path.join(run_dir, f"host_{rt.process_index}"))
    if not cfg.train.events_jsonl:
        cfg.train.events_jsonl = os.path.join(host_dir, "events.jsonl")
    logger.info("config loaded; %s", rt.describe())
    if rt.is_coordinator:
        save_resolved(cfg, os.path.join(run_dir, "resolved_config.yaml"))

    from distributed_training_tpu import telemetry as telemetry_lib
    from distributed_training_tpu.checkpoint import Checkpointer
    from distributed_training_tpu.data import (ShardedDataLoader,
                                               build_dataset)
    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.train.trainer import Trainer

    # Deterministic fault injection (resilience/faults.py): hooks in
    # the step loop, the data loader, and the checkpoint manager; the
    # per-host ledger makes faults one-shot across supervisor
    # restarts. Empty plan → no injector, zero overhead.
    fault_injector = None
    if cfg.train.fault_plan:
        from distributed_training_tpu.resilience import faults
        plan = faults.parse_fault_plan(cfg.train.fault_plan)
        # Source-level kinds need the streaming loader's per-document
        # hook; scheduling them against the sharded loader would be a
        # drill that silently never fires.
        faults.check_plan_hooks(plan, bool(cfg.train.data_sources))
        fault_injector = faults.FaultInjector(
            plan,
            ledger_path=os.path.join(host_dir, "faults_fired.json"),
            ckpt_dir=cfg.train.snapshot_path,
            host=rt.process_index)

    eval_loader = None
    if cfg.train.data_sources:
        # Multi-source exactly-once streaming pipeline (data/
        # stream.py): the loader's whole position rides the
        # checkpoint, so restarts and elastic resizes resume
        # mid-epoch without replaying or skipping a sample.
        from distributed_training_tpu.data import (StreamingDataLoader,
                                                   build_stream_sources)
        if cfg.train.eval_fraction > 0:
            raise ValueError(
                "train.eval_fraction is not supported with "
                "train.data_sources (the stream has no held-out "
                "split); set eval_fraction=0")
        sources = build_stream_sources(
            cfg.train.data_sources,
            defaults={"size": cfg.train.dataset_size,
                      "seed": cfg.train.seed})
        loader = StreamingDataLoader(
            sources, rt,
            batch_size=cfg.train.batch_size,
            pack_len=cfg.train.pack_seq_len,
            shuffle=cfg.train.shuffle,
            seed=cfg.train.seed,
            steps_per_epoch=cfg.train.max_steps_per_epoch,
            data_retries=cfg.train.data_retries,
            fault_injector=fault_injector,
        )
    else:
        dataset = build_dataset(
            cfg.train.dataset,
            _defaults={"size": cfg.train.dataset_size,
                       "seed": cfg.train.seed},
            **cfg.train.dataset_kwargs,
        )
        if cfg.train.eval_fraction > 0:
            from distributed_training_tpu.data.datasets import (
                train_eval_split,
            )
            dataset, eval_ds = train_eval_split(
                dataset, cfg.train.eval_fraction, seed=cfg.train.seed,
                multiple_of=cfg.train.batch_size * rt.data_shard_count)
            eval_loader = ShardedDataLoader(
                eval_ds, rt, batch_size=cfg.train.batch_size,
                shuffle=False, seed=cfg.train.seed)
        loader = ShardedDataLoader(
            dataset, rt,
            batch_size=cfg.train.batch_size,
            shuffle=cfg.train.shuffle,
            seed=cfg.train.seed,
            drop_last=cfg.train.drop_last,
            max_steps_per_epoch=cfg.train.max_steps_per_epoch,
            data_retries=cfg.train.data_retries,
            fault_injector=fault_injector,
        )
    model_kwargs = dict(cfg.model.kwargs)
    # model-level dtype override wins over the training compute dtype
    model_dtype = model_kwargs.pop("dtype", cfg.train.dtype)
    model = build_model(cfg.model.name, loss=cfg.train.loss,
                        dtype=model_dtype, **model_kwargs)

    from distributed_training_tpu.resilience import supervisor as sup
    from distributed_training_tpu.utils.preemption import PreemptionGuard
    guard = PreemptionGuard.install()

    # Context-managed checkpointer: __exit__ runs wait() + close() on
    # EVERY exit path — preemption, watchdog stop, fault-injected
    # crash — so an in-flight async save is never dropped.
    with Checkpointer(cfg.train.snapshot_path,
                      fault_injector=fault_injector) as checkpointer:
        # Telemetry: an event stream on EVERY process (multi-host runs
        # write per-host streams the aggregator merges; docs/
        # observability.md), hang watchdog on every process too (hangs
        # are host-specific; each host writes its own postmortem
        # bundle).
        resumed = checkpointer.latest_step() is not None
        restart_count = int(os.environ.get(
            sup.ENV_RESTART_COUNT, "0") or 0)
        # Restored events for the anomaly detector's baseline replay,
        # read BEFORE the Telemetry below opens the stream (a fresh
        # run truncates it; a resumed run appends a new run_start —
        # either way the pre-restart records must be captured first).
        restored_events: list = []
        if (cfg.train.anomaly_detect and rt.is_coordinator
                and (resumed or restart_count > 0)):
            from distributed_training_tpu.telemetry.summarize import (
                load_jsonl)
            restored_events = load_jsonl(cfg.train.events_jsonl)
        # fresh only on a genuinely first incarnation: a supervised
        # restart that found NO checkpoint (crash before the first
        # save) must APPEND — truncating would destroy the crashed
        # segment's events and the recovery table's evidence.
        tel = telemetry_lib.install(telemetry_lib.Telemetry(
            events_jsonl=cfg.train.events_jsonl,
            enabled=True,
            fresh=not (resumed or restart_count > 0),
            start_step=checkpointer.latest_step() or 0,
            host_id=(rt.process_index
                     if rt.process_count > 1 or elastic_incarnation
                     else None)))
        # Clock-sync record: the runtime captured one barrier-anchored
        # timestamp per host at setup; emitting it into each stream is
        # what lets the offline aggregator put N host clocks on one
        # axis.
        tel.event("clock_sync", **rt.clock_sync_record())
        # Closed-loop diagnostics (telemetry/anomaly.py + incident.py),
        # coordinator-only: the online detector keeps rolling
        # median/MAD baselines over the event stream (pure host-side
        # observer — zero new device syncs), a sustained step-time
        # regression arms one in-run profile capture via the
        # profile_now drop file, and the incident recorder snapshots
        # the flight-recorder ring buffer into
        # <run_dir>/incidents/<ts>/ on anomaly / watchdog abort /
        # preemption. Baselines are rebuilt deterministically from the
        # restored stream on resume.
        detector = None
        incidents = None
        if cfg.train.anomaly_detect and rt.is_coordinator:
            from distributed_training_tpu.telemetry.anomaly import (
                AnomalyDetector)
            from distributed_training_tpu.telemetry.incident import (
                IncidentRecorder)
            detector = AnomalyDetector(
                telemetry=tel, run_dir=run_dir,
                window=cfg.train.anomaly_window,
                min_samples=cfg.train.anomaly_min_samples,
                threshold=cfg.train.anomaly_threshold,
                sustain=cfg.train.anomaly_sustain,
                autoprofile=cfg.train.anomaly_autoprofile,
                host=rt.process_index)
            if restored_events:
                n = detector.replay(restored_events)
                logger.info("anomaly baselines rebuilt from %d "
                            "restored event(s)", n)
            incidents = IncidentRecorder(
                run_dir, telemetry=tel, detector=detector,
                cooldown_s=cfg.train.incident_cooldown_s)
            tel.add_observer(detector.observe)
            tel.add_observer(incidents.observe)
        watchdog = None
        if cfg.train.watchdog_timeout_s > 0:
            watchdog = telemetry_lib.HangWatchdog(
                cfg.train.watchdog_timeout_s,
                os.path.join(host_dir, "postmortem"),
                telemetry=tel, abort=cfg.train.watchdog_abort)

        # In-run profiler capture + attribution (telemetry/
        # attribution.py): scheduled steps from train.profile_at plus
        # the drop-a-file trigger (<run_dir>/profile_now) for
        # already-running jobs. Coordinator-gated — the trace and the
        # attribution event are process-local, and one host's
        # timeline answers the fleet's question.
        from distributed_training_tpu.telemetry.attribution import (
            ProfileCapture)
        profile_capture = ProfileCapture(
            run_dir, at_steps=cfg.train.profile_at,
            n_steps=cfg.train.profile_steps,
            enabled=rt.is_coordinator)

        # Live metrics endpoint (telemetry/metrics_server.py),
        # coordinator-only: Prometheus exposition + /healthz off the
        # same Telemetry sink that writes events.jsonl. The bound
        # port is recorded in <run_dir>/metrics.port for tooling.
        metrics_server = None
        if cfg.train.metrics_port > 0 and rt.is_coordinator:
            from distributed_training_tpu.telemetry.metrics_server \
                import MetricsServer
            ds = getattr(loader, "dataset", None)
            tokens_per_sample = (getattr(ds, "seq_len", None)
                                 or cfg.train.pack_seq_len or 1)
            metrics_server = MetricsServer(
                cfg.train.metrics_port, telemetry=tel,
                tokens_per_step=loader.global_batch
                * tokens_per_sample,
                stall_timeout_s=cfg.train.watchdog_timeout_s,
                info={"world_size": rt.process_count,
                      "incarnation": restart_count}).start()
            if metrics_server is not None:
                with open(os.path.join(run_dir, "metrics.port"),
                          "w", encoding="utf-8") as pf:
                    pf.write(f"{metrics_server.port}\n")

        trainer = Trainer(cfg, rt, model, loader, checkpointer,
                          preemption_guard=guard,
                          eval_loader=eval_loader,
                          watchdog=watchdog,
                          fault_injector=fault_injector,
                          profile_capture=profile_capture)
        if (trainer.epochs_run > 0 or trainer.global_step > 0
                or restart_count > 0):
            # Recovery evidence: which step this incarnation picked up
            # from, and which supervisor incarnation it is (the
            # summarizer's recovery table joins these with run_start
            # markers to compute steps-lost and time-to-recover).
            # Emitted even on a fresh start when this IS a restart
            # incarnation (crash before the first checkpoint) — the
            # recovery table must not undercount those.
            # Cursor evidence (docs/data.md): the restored pipeline
            # position + realized mixture ride the resume event, so
            # the summarizer's recovery table can PROVE exactly-once
            # (samples replayed = step*global_batch - samples_consumed
            # must be 0, and 0 the other way for skips).
            cursor_info = {}
            if hasattr(loader, "state_dict"):
                data_state = loader.state_dict()
                cursor_info = {
                    "samples_consumed":
                        data_state.get("samples_consumed"),
                    "global_batch": loader.global_batch,
                    "data_skips": data_state.get("skipped", 0),
                }
                # Mixture evidence only once something was consumed:
                # a fresh-start restart incarnation (crash before the
                # first save) has realized weights of all zeros, and
                # the summarizer would render that as a large bogus
                # mixture drift on a zero-consumption incident.
                if data_state.get("samples_consumed"):
                    for k in ("realized_mixture", "target_mixture"):
                        if data_state.get(k):
                            cursor_info[k] = data_state[k]
            tel.event("resume", step=trainer.global_step,
                      epoch=trainer.epochs_run,
                      restarts=restart_count,
                      world_size=rt.process_count,
                      evicted_hosts=evicted_hosts,
                      **cursor_info)
        try:
            if cfg.train.profile_dir:
                from distributed_training_tpu.utils import profiler
                with profiler.trace(cfg.train.profile_dir,
                                    host_only_on_coordinator=True,
                                    process_index=rt.process_index):
                    summary = trainer.train()
            else:
                summary = trainer.train()
        finally:
            if incidents is not None and guard.should_stop:
                # Preemption incident: the drain path saved a final
                # checkpoint; the bundle records what the run looked
                # like when the platform pulled the machine.
                incidents.record(
                    "preemption",
                    reason="preemption/stop signal observed; "
                           "stopping at a checkpoint boundary")
            if watchdog is not None:
                watchdog.stop()
            if metrics_server is not None:
                metrics_server.stop()
            profile_capture.abort()  # run ended mid-capture window
            tel.close()
    if rt.is_coordinator:
        logger.info("training done: %s", summary)
    # Exit-status sentinel for the restart supervisor: a preempted run
    # exits 0 after its final save just like a completed one — only
    # this record tells the supervisor to relaunch vs. stand down. A
    # coordinated eviction also exits 0; its host_lost sentinel names
    # the evictee so the elastic supervisor shrinks around it.
    # No-op when unsupervised (no DTT_EXIT_SENTINEL in env).
    evict = trainer.straggler.evict_request
    if evict is not None:
        sup.write_exit_status(
            sup.HOST_LOST, step=trainer.global_step,
            epochs_run=trainer.epochs_run,
            lost_host=evict["host"], reason=evict.get("reason"))
    else:
        sup.write_exit_status(
            sup.PREEMPTED if guard.should_stop else sup.COMPLETED,
            step=trainer.global_step, epochs_run=trainer.epochs_run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
