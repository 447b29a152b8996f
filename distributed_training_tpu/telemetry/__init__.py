"""Telemetry: the observability subsystem.

One instrumentation surface, four consumers:

- ``span()``/``event()`` (events.py) — the structured ``events.jsonl``
  stream, doubling as XProf trace annotations;
- ``GoodputLedger`` (goodput.py) — wall-clock decomposed into
  compile/data_wait/step/checkpoint/eval/idle, goodput% + MFU;
- ``HangWatchdog`` (watchdog.py) — per-step hang detection with
  faulthandler/memory-stats/event-tail postmortem bundles;
- ``HBMSampler`` (hbm.py) — periodic ``device.memory_stats()``
  samples cross-checked against utils/memory.py estimates;
- ``StragglerDetector`` (straggler.py) — on-cadence cross-host
  step/data_wait exchange flagging persistently slow hosts;
- ``audit_hlo_text`` (collectives.py) — static collective-traffic
  accounting of a compiled SPMD step (counts + bytes per mesh axis);
- ``ProfileCapture`` (attribution.py) — in-run ``jax.profiler``
  capture at configured steps (or a drop-file trigger) decomposed
  into compute / collective / host+data + overlap %, and the static
  schedule-overlap audit the analysis gate ratchets; trace parsing
  lives in xplane.py (stdlib XSpace reader, shared with
  benchmarks/analyze_trace.py);
- ``MetricsServer`` (metrics_server.py) — the coordinator's live
  Prometheus endpoint + /healthz, fed from this sink (plus the
  tenant-labeled serving latency histograms);
- ``AnomalyDetector`` (anomaly.py) — online median/MAD anomaly
  detection over the same event stream (registered through
  ``add_observer`` like the metrics server: pure host-side, zero
  device syncs), arming an in-run profile capture on sustained
  step-time regressions;
- ``IncidentRecorder``/``write_incident_bundle`` (incident.py) —
  flight-recorder incident bundles (event tail + anomaly verdict +
  latest attribution + serving snapshot) written atomically under
  ``<run_dir>/incidents/``; watchdog postmortems share the format;
- the offline doctor (doctor.py) — rule-based classification of a
  run dir or incident bundle (``--doctor``);
- ``analyze_traces`` (serving_trace.py) — per-tenant SLO ledger
  reconstructed offline from the serving engine's ``serving_trace``
  request-lifecycle records (``--serving-report``);
- the multi-host aggregator (aggregate.py) — merges per-host
  ``host_<i>/events.jsonl`` streams into one clock-aligned report.

``python -m distributed_training_tpu.telemetry <run_dir>`` renders it
all (summarize.py; multi-host run dirs get the merged report). Event
schema and bucket definitions: docs/observability.md.
"""

from distributed_training_tpu.telemetry.anomaly import (  # noqa: F401
    AnomalyDetector,
)
from distributed_training_tpu.telemetry.attribution import (  # noqa: F401
    ProfileCapture,
    hlo_overlap_report,
)
from distributed_training_tpu.telemetry.collectives import (  # noqa: F401
    audit_hlo_text,
)
from distributed_training_tpu.telemetry.events import (  # noqa: F401
    Telemetry,
    current,
    event,
    install,
    phase,
    span,
    uninstall,
)
from distributed_training_tpu.telemetry.goodput import (  # noqa: F401
    GoodputLedger,
)
from distributed_training_tpu.telemetry.hbm import (  # noqa: F401
    HBMSampler,
)
from distributed_training_tpu.telemetry.incident import (  # noqa: F401
    IncidentRecorder,
    write_incident_bundle,
)
from distributed_training_tpu.telemetry.metrics_server import (  # noqa: F401
    MetricsServer,
)
from distributed_training_tpu.telemetry.serving_trace import (  # noqa: F401
    analyze_traces,
    render_serving_lines,
    slo_attainment,
)
from distributed_training_tpu.telemetry.straggler import (  # noqa: F401
    StragglerDetector,
    flag_stragglers,
)
from distributed_training_tpu.telemetry.watchdog import (  # noqa: F401
    HangWatchdog,
    write_postmortem,
)
