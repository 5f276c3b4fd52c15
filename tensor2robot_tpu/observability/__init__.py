"""Unified telemetry layer: metrics registry, spans, goodput, run files.

The measurement substrate every perf/reliability PR builds on (ISSUE 3):

  * ``TelemetryRegistry`` (`registry.py`) — process-wide, thread-safe
    counters/gauges/fixed-bucket histograms with labeled series; flat
    ``scalars()`` for the TensorBoard writer, structured ``snapshot()``
    (+ ``snapshot_delta``) for jsonl export. ``get_registry()`` is the
    default instance the built-in layers report to.
  * ``span`` / ``event`` (`spans.py`) — context-manager/decorator timing
    regions into ``span/<name>`` histograms AND into one bounded,
    always-on in-memory ring of records (id, parent, thread, start, end,
    attributes) on one clock for every thread; ``span_records()`` reads
    it. Captures tie it to the device trace by a clock marker
    (`autoprofiler.py`), not by host-tracer annotations.
  * ``GoodputTracker`` (`goodput.py`) — every trainer-loop second
    charged to productive / data / checkpoint / retry; fractions sum to
    1.0 by construction.
  * ``TelemetryLogger`` (`telemetry_file.py`) — append-only
    ``telemetry.jsonl`` + atomically-replaced ``heartbeat.json`` under
    ``model_dir``; ``bin/t2r_telemetry`` tails and summarizes them.

Performance forensics (ISSUE 4) closes the loop from those numbers to
answers:

  * ``Watchdog`` (`watchdog.py`) — rolling-baseline anomaly detection
    (step-time regression, goodput drop, recompiles, HBM growth,
    heartbeat staleness) over the registry at the trainer's log cadence.
  * ``AutoProfiler`` (`autoprofiler.py`) — budgeted, rate-limited
    profiler capture windows triggered by the watchdog (static
    ``profile_steps`` windows stay supported); every window ends as a
    structured ``forensics/<step>.json`` report.
  * `signals.py` — ``jax.monitoring`` compile-event listeners and
    device-HBM/host-RSS watermark sampling into the registry.
  * `forensics.py` — the report builder (top-k ops via `utils/xplane`,
    collective stats via `parallel/hlo_analysis`, goodput attribution);
    degrades to warnings on torn captures, never raises in the trainer.
  * `doctor.py` — ranked offline diagnosis from telemetry.jsonl +
    forensics reports (``bin/t2r_telemetry doctor``; jax-free).

Pipeline X-ray (ISSUE 7) makes the host->device data path a measured,
per-stage quantity:

  * `pipeline_xray.py` — the stage model (read/decode/batch/transfer/
    device), source-side ``StageMeter`` counters every data layer
    reports into, the windowed ``PipelineXray`` bottleneck attribution
    (``t2r.pipeline.v1`` records in telemetry.jsonl), the
    ``attribute_stages`` rule, and the pipeline anomaly
    kinds (``pipeline_stall`` / ``worker_starvation`` /
    ``transfer_regression``) feeding the capture loop.

Fleet observatory (ISSUE 9) lifts all of it from one process to a
fleet:

  * `fleet.py` — per-host stream federation (``telemetry.<i>.jsonl``
    merged into aligned step-time/goodput series, fleet goodput as the
    min across hosts), the FleetWatchdog (``straggler`` /
    ``host_dead`` anomalies into the same capture loop), the live
    FleetObserver (``t2r.fleet.v1`` records from per-host heartbeats),
    and the preemption recovery timeline (``t2r.recovery.v1``,
    ``preemption_recovery_seconds``).
  * `fleet_sim.py` — the jax-free simulated-host writer fleet tests,
    ``bin/check_fleet_doctor``, and the MULTICHIP fleet phase share.

Roofline observatory (ISSUE 19) turns the measured-ms tables into
bound-class evidence and makes MFU a live signal:

  * `roofline.py` — the per-``device_kind`` peaks table, the
    ``t2r.roofline.v1`` record builder (measured op-family ms joined
    with the `parallel/hlo_analysis` per-op FLOPs/bytes cost model:
    arithmetic intensity, compute/memory/ragged bound class, % peak,
    fusion headroom; CPU degrades to intensity-only), and the
    ``perf/mfu`` / ``perf/hbm_bw_util`` gauges the trainer publishes
    every log window from `parallel/hlo_analysis.program_cost`. The
    watchdog's ``mfu_regression`` kind and doctor's roofline verdict
    (naming the gating memory-bound family) read them.

Metric name catalog, forensics report schema, and goodput definitions:
docs/observability.md.
"""

from tensor2robot_tpu.observability.autoprofiler import AutoProfiler
from tensor2robot_tpu.observability.fleet import (
    FLEET_RECORD_SCHEMA,
    FleetConfig,
    FleetObserver,
    FleetWatchdog,
    RECOVERY_SCHEMA,
    align_train_series,
    fleet_summary,
    read_fleet,
)
from tensor2robot_tpu.observability.forensics import (
    FORENSICS_DIRNAME,
    attribute_goodput,
    build_report,
    read_reports,
    split_collective_wait,
    write_report,
)
from tensor2robot_tpu.observability.goodput import (
    CATEGORIES as GOODPUT_CATEGORIES,
    GoodputTracker,
)
from tensor2robot_tpu.observability.pipeline_xray import (
    PIPELINE_RECORD_SCHEMA,
    PipelineXray,
    StageMeter,
    XrayConfig,
    attribute_stages,
)
from tensor2robot_tpu.observability.roofline import (
    HBM_BW_GAUGE,
    MFU_GAUGE,
    ROOFLINE_SCHEMA,
    build_record as build_roofline_record,
    classify_bound,
    device_peaks,
    publish_perf_gauges,
)
from tensor2robot_tpu.observability.signals import (
    host_identity,
    install_jax_listeners,
    sample_memory,
    uninstall_jax_listeners,
)
from tensor2robot_tpu.observability.watchdog import (
    Anomaly,
    Watchdog,
    WatchdogConfig,
)
from tensor2robot_tpu.observability.registry import (
    Counter,
    DEFAULT_LATENCY_BUCKETS_MS,
    DEFAULT_SECONDS_BUCKETS,
    Gauge,
    Histogram,
    SLO_LATENCY_BUCKETS_MS,
    TelemetryRegistry,
    exponential_buckets,
    get_registry,
    set_registry,
    snapshot_delta,
)
from tensor2robot_tpu.observability.spans import (
    event,
    records as span_records,
    span,
)
from tensor2robot_tpu.observability.telemetry_file import (
    HEARTBEAT_FILENAME,
    TELEMETRY_FILENAME,
    TelemetryLogger,
    discover_hosts,
    read_heartbeat,
    read_telemetry,
)

__all__ = [
    'Anomaly',
    'AutoProfiler',
    'Counter',
    'DEFAULT_LATENCY_BUCKETS_MS',
    'DEFAULT_SECONDS_BUCKETS',
    'FLEET_RECORD_SCHEMA',
    'FORENSICS_DIRNAME',
    'FleetConfig',
    'FleetObserver',
    'FleetWatchdog',
    'Gauge',
    'GOODPUT_CATEGORIES',
    'GoodputTracker',
    'HBM_BW_GAUGE',
    'HEARTBEAT_FILENAME',
    'Histogram',
    'MFU_GAUGE',
    'ROOFLINE_SCHEMA',
    'PIPELINE_RECORD_SCHEMA',
    'PipelineXray',
    'RECOVERY_SCHEMA',
    'SLO_LATENCY_BUCKETS_MS',
    'StageMeter',
    'TELEMETRY_FILENAME',
    'TelemetryLogger',
    'TelemetryRegistry',
    'Watchdog',
    'WatchdogConfig',
    'XrayConfig',
    'align_train_series',
    'attribute_goodput',
    'attribute_stages',
    'build_report',
    'build_roofline_record',
    'classify_bound',
    'device_peaks',
    'discover_hosts',
    'event',
    'exponential_buckets',
    'fleet_summary',
    'get_registry',
    'host_identity',
    'install_jax_listeners',
    'publish_perf_gauges',
    'read_fleet',
    'read_heartbeat',
    'read_reports',
    'read_telemetry',
    'sample_memory',
    'set_registry',
    'snapshot_delta',
    'span',
    'span_records',
    'split_collective_wait',
    'uninstall_jax_listeners',
    'write_report',
]
