"""Pipeline X-ray: per-stage host->device dataflow tracing + attribution.

Without stage-level throughput, queue-occupancy or backpressure signals
a regression in the host->device input path only shows up as a
mysterious goodput ``data`` fraction. This module is the stage model
every data layer reports into (docs/observability.md "Pipeline X-ray"):

  * ``read``     — record I/O: the C++ loader's reader thread
                   (record_loader.cc stats export) or the Python
                   TFRecord interleave (data/pipeline.py).
  * ``decode``   — proto parse + JPEG decode: the C++ worker pool
                   (per-pool busy/idle seconds, worker count) or the
                   Python ExampleParser fallback.
  * ``batch``    — batch assembly/handoff: the generators' prefetch
                   producers (data/input_generators.py); the native
                   stream's pack cost is the ``data.pack`` span's
                   histogram (busy-only — its rows are already counted
                   by the decode stage).
  * ``transfer`` — the host->device hop: ``data/device_feed.py``
                   (bytes, busy seconds, double-buffer occupancy).
  * ``device``   — the jitted step: derived from the trainer's goodput
                   ``productive`` seconds, no extra instrumentation.

Sources write MONOTONIC counters (``pipeline/<stage>/{examples,bytes,
busy_seconds}``); :class:`PipelineXray` windows them at the trainer's
log cadence into per-stage CAPACITY estimates
(``examples_processed / busy_seconds``, worker-count-normalized for the
decode pool). Capacity — not raw throughput — is the attributable
quantity: in steady state every stage's throughput equals the e2e rate
by construction, but busy-time-derived capacity names the stage that
would gate if everything upstream were infinite
(:func:`attribute_stages` is the attribution rule).

Each ``observe()`` yields a ``t2r.pipeline.v1`` record (written to
``telemetry.jsonl`` as kind ``pipeline``) naming the gating stage and
its headroom vs. the device rate, plus watchdog-style anomalies that
feed the symptom->capture->attribution loop (docs/observability.md):

  * ``pipeline_stall``       — the e2e flow rate collapsed below the
    rolling baseline while the trainer was data-starved: something in
    the host path stopped producing (detail names the gating stage).
  * ``worker_starvation``    — the decode pool sat mostly idle while
    the trainer starved: the stage UPSTREAM of the workers (record
    I/O) cannot feed them. Like every windowed detection here, it
    fires on the window in which the evidence lands — a wait that is
    still in progress commits its idle seconds when it returns, so a
    hard starvation is attributed on the first window after flow
    resumes (a TOTAL stall blocks the trainer loop itself, and is the
    ``pipeline_stall`` / heartbeat-staleness territory).
  * ``transfer_regression``  — the measured host->device MB/s fell
    below its rolling baseline (link contention, pathological batch).

Like the watchdog, anomalous windows never fold into the baselines, all
timing is ``time.perf_counter`` windows upstream, and ``observe()`` is
a pure in-memory pass — no threads, no I/O.
"""

from __future__ import annotations

import collections
import statistics
from typing import Deque, Dict, List, Optional, Tuple

from tensor2robot_tpu.observability import registry as registry_lib
from tensor2robot_tpu.observability.watchdog import ANOMALY_COUNTER, Anomaly

__all__ = [
    'PIPELINE_RECORD_SCHEMA',
    'PIPELINE_STALL',
    'WORKER_STARVATION',
    'TRANSFER_REGRESSION',
    'STAGES',
    'StageMeter',
    'XrayConfig',
    'PipelineXray',
    'attribute_stages',
    'stage_counter_names',
]

PIPELINE_RECORD_SCHEMA = 't2r.pipeline.v1'

# New watchdog anomaly kinds (counted into watchdog/anomalies like the
# step-time/goodput/recompile/hbm kinds from observability/watchdog.py).
PIPELINE_STALL = 'pipeline_stall'
WORKER_STARVATION = 'worker_starvation'
TRANSFER_REGRESSION = 'transfer_regression'

# Canonical stage order, upstream -> downstream.
STAGES = ('read', 'decode', 'batch', 'transfer', 'device')

# Decode-pool size gauge (data/native_loader.py sets it; 0/absent means
# the single-threaded Python parser, normalized as 1 worker).
DECODE_WORKERS_GAUGE = 'pipeline/decode/workers'
DECODE_IDLE_COUNTER = 'pipeline/decode/idle_seconds'


def stage_counter_names(stage: str) -> Tuple[str, str, str]:
  """(examples, bytes, busy_seconds) counter names for one stage."""
  prefix = 'pipeline/' + stage + '/'
  return (prefix + 'examples', prefix + 'bytes', prefix + 'busy_seconds')


class StageMeter:
  """Source-side instrument bundle for one pipeline stage.

  Resolve once (construction registers the three counters), then
  ``add`` from the hot path — three lock-protected float adds, no
  allocation. Every example must be counted by AT MOST ONE call site
  per stage; busy seconds are the host seconds that stage actually
  spent processing (for a worker pool: summed across workers — the
  X-ray normalizes by the ``pipeline/decode/workers`` gauge).
  """

  __slots__ = ('stage', '_examples', '_bytes', '_busy')

  def __init__(self, stage: str,
               registry: Optional[registry_lib.TelemetryRegistry] = None):
    registry = registry or registry_lib.get_registry()
    examples, nbytes, busy = stage_counter_names(stage)
    self.stage = stage
    self._examples = registry.counter(examples)
    self._bytes = registry.counter(nbytes)
    self._busy = registry.counter(busy)

  def add(self, examples: float = 0.0, nbytes: float = 0.0,
          busy_s: float = 0.0) -> None:
    if examples:
      self._examples.inc(examples)
    if nbytes:
      self._bytes.inc(nbytes)
    if busy_s > 0.0:
      self._busy.inc(busy_s)


def attribute_stages(rates: Dict[str, Optional[float]]
                     ) -> Dict[str, object]:
  """Names the gating stage from per-stage examples/sec rates.

  :class:`PipelineXray` feeds it live busy-time capacity estimates.
  Stages with missing/non-positive rates are skipped (an unmeasured
  stage is unknown, not infinitely fast — but it must not win the argmin
  by defaulting to zero). Ties break deterministically toward the
  lexicographically first stage name.

  Returns ``{'bottleneck': <stage|None>, 'headroom_vs_device': <float|
  None>, 'rates': {stage: rate}}`` where headroom is the gating stage's
  rate as a fraction of the device rate (1.0 == device-bound; < 1 means
  the pipeline, not the chip, caps end-to-end throughput).
  """
  valid = {stage: float(rate) for stage, rate in rates.items()
           if rate is not None and rate > 0.0}
  if not valid:
    return {'bottleneck': None, 'headroom_vs_device': None, 'rates': {}}
  bottleneck = min(sorted(valid), key=lambda stage: valid[stage])
  device = valid.get('device')
  headroom = (valid[bottleneck] / device) if device else None
  return {'bottleneck': bottleneck, 'headroom_vs_device': headroom,
          'rates': valid}


class XrayConfig:
  """Thresholds for the pipeline anomaly detections.

  Ratios follow the watchdog posture (docs/observability.md): fire on
  sustained ~2x collapses, not single-window jitter. The transfer
  detection additionally requires the transfer stage to be a
  non-negligible share of the window (``transfer_min_busy_fraction``) —
  a 100 us hop's MB/s estimate is pure jitter and could never gate the
  pipeline anyway.
  """

  def __init__(self,
               min_baseline_windows: int = 3,
               baseline_windows: int = 16,
               stall_ratio: float = 2.0,
               stall_data_fraction: float = 0.5,
               starvation_idle_fraction: float = 0.75,
               starvation_data_fraction: float = 0.5,
               transfer_regression_ratio: float = 2.0,
               transfer_min_busy_fraction: float = 0.05,
               min_stage_busy_seconds: float = 1e-3):
    if stall_ratio <= 1.0 or transfer_regression_ratio <= 1.0:
      raise ValueError('regression ratios must exceed 1.0; got {} / {}.'
                       .format(stall_ratio, transfer_regression_ratio))
    if not 0.0 < starvation_idle_fraction < 1.0:
      raise ValueError('starvation_idle_fraction must be in (0, 1); got {}.'
                       .format(starvation_idle_fraction))
    self.min_baseline_windows = int(min_baseline_windows)
    self.baseline_windows = int(baseline_windows)
    self.stall_ratio = float(stall_ratio)
    self.stall_data_fraction = float(stall_data_fraction)
    self.starvation_idle_fraction = float(starvation_idle_fraction)
    self.starvation_data_fraction = float(starvation_data_fraction)
    self.transfer_regression_ratio = float(transfer_regression_ratio)
    self.transfer_min_busy_fraction = float(transfer_min_busy_fraction)
    self.min_stage_busy_seconds = float(min_stage_busy_seconds)


class PipelineXray:
  """Windows the pipeline counters into live bottleneck attribution.

  The trainer calls ``observe(step, examples, window_seconds,
  goodput_seconds)`` once per log window; each call returns the
  ``t2r.pipeline.v1`` record for ``telemetry.jsonl`` plus any fired
  anomalies (handled exactly like watchdog detections: logged, recorded,
  and answered with a budgeted capture). ``last_record`` feeds the
  forensics report's ``pipeline`` stage table.
  """

  def __init__(self, config: Optional[XrayConfig] = None,
               registry: Optional[registry_lib.TelemetryRegistry] = None):
    self.config = config or XrayConfig()
    self._registry = registry
    # Seed the counter baseline at construction: the registry is
    # process-wide, so a prior Trainer/eval phase in the same
    # process may already hold pipeline counters — diffing the first
    # window against zero would fold that whole history into one
    # window's rates (busy fractions over 1.0, garbage capacities).
    try:
      self._last_counters: Optional[Dict[str, float]] = dict(
          self.registry.snapshot().get('counters', {}))
    except Exception:  # noqa: BLE001 — never fail trainer construction
      self._last_counters = None
    self._last_goodput: Optional[Dict[str, float]] = None
    self._windows_seen = 0
    self._rate_baseline: Deque[float] = collections.deque(
        maxlen=self.config.baseline_windows)
    self._transfer_baseline: Deque[float] = collections.deque(
        maxlen=self.config.baseline_windows)
    self.last_record: Optional[Dict[str, object]] = None

  @property
  def registry(self) -> registry_lib.TelemetryRegistry:
    return self._registry or registry_lib.get_registry()

  # -- internals -------------------------------------------------------------

  def _snapshot(self) -> Tuple[Dict[str, float], Dict[str, float]]:
    snapshot = self.registry.snapshot()
    return (dict(snapshot.get('counters', {})),
            dict(snapshot.get('gauges', {})))

  def _stage_window(self, counters: Dict[str, float], stage: str
                    ) -> Dict[str, float]:
    last = self._last_counters or {}
    out = {}
    for key, name in zip(('examples', 'bytes', 'busy_seconds'),
                         stage_counter_names(stage)):
      out[key] = counters.get(name, 0.0) - last.get(name, 0.0)
    return out

  # -- the log-cadence pass --------------------------------------------------

  def observe(self, step: int, examples: float, window_seconds: float,
              goodput_seconds: Optional[Dict[str, float]] = None
              ) -> Tuple[Dict[str, object], List[Anomaly]]:
    """One window: (t2r.pipeline.v1 record, fired anomalies).

    ``examples`` is the count the trainer consumed this window (the e2e
    flow meter); ``goodput_seconds`` the tracker's CUMULATIVE seconds
    (differenced here, like the watchdog). All durations upstream come
    from ``time.perf_counter`` windows.
    """
    self._windows_seen += 1
    window_seconds = max(float(window_seconds), 1e-9)
    counters, gauges = self._snapshot()
    registry = self.registry

    # Goodput window: the data fraction is the starvation evidence.
    data_fraction = 0.0
    productive_s = None
    if goodput_seconds is not None:
      last = self._last_goodput or {}
      window = {k: goodput_seconds.get(k, 0.0) - last.get(k, 0.0)
                for k in goodput_seconds}
      self._last_goodput = dict(goodput_seconds)
      total = sum(window.values())
      if total > 0.0:
        data_fraction = window.get('data', 0.0) / total
        productive_s = window.get('productive', 0.0)

    workers = max(gauges.get(DECODE_WORKERS_GAUGE, 0.0), 1.0)
    min_busy = self.config.min_stage_busy_seconds
    stages: Dict[str, Dict[str, object]] = {}
    capacities: Dict[str, Optional[float]] = {}
    for stage in ('read', 'decode', 'batch', 'transfer'):
      window = self._stage_window(counters, stage)
      if not any(window.values()):
        continue  # stage not instrumented in this topology
      busy = window['busy_seconds']
      parallelism = workers if stage == 'decode' else 1.0
      capacity = None
      if window['examples'] > 0 and busy > min_busy:
        capacity = window['examples'] * parallelism / busy
      mb_per_sec = (window['bytes'] / busy / 1e6
                    if window['bytes'] > 0 and busy > min_busy else None)
      stages[stage] = {
          'examples': window['examples'],
          'bytes': window['bytes'],
          'busy_seconds': busy,
          'busy_fraction': busy / (window_seconds * parallelism),
          'examples_per_sec_capacity': capacity,
          'mb_per_sec': mb_per_sec,
      }
      capacities[stage] = capacity
    # Device stage: examples over the window's productive seconds — the
    # dispatch+compute rate with every host-side wait excluded.
    device_capacity = None
    if productive_s is not None and productive_s > min_busy and examples > 0:
      device_capacity = examples / productive_s
      stages['device'] = {
          'examples': float(examples),
          'busy_seconds': productive_s,
          'busy_fraction': productive_s / window_seconds,
          'examples_per_sec_capacity': device_capacity,
      }
    capacities['device'] = device_capacity

    attribution = attribute_stages(
        {stage: capacity for stage, capacity in capacities.items()})
    e2e_rate = float(examples) / window_seconds

    # Queue evidence: the prefetch-depth gauges at sample time.
    queues = {name: value for name, value in gauges.items()
              if name.startswith('data/prefetch_queue_depth')
              or name.endswith('buffer_occupancy')}

    anomalies = self._detect(step, e2e_rate, data_fraction, counters,
                             stages, attribution)

    # Derived per-stage gauges for TensorBoard (raw counters stay the
    # source of truth; these are the human-readable windowed view).
    for stage, info in stages.items():
      capacity = info.get('examples_per_sec_capacity')
      if capacity is not None:
        registry.gauge_family('pipeline/examples_per_sec', ('stage',)) \
            .series(stage).set(capacity)
      registry.gauge_family('pipeline/busy_fraction', ('stage',)) \
          .series(stage).set(float(info['busy_fraction']))
    if attribution['headroom_vs_device'] is not None:
      registry.gauge('pipeline/headroom_vs_device').set(
          attribution['headroom_vs_device'])

    record: Dict[str, object] = {
        'schema': PIPELINE_RECORD_SCHEMA,
        'window_seconds': window_seconds,
        'examples_per_sec': e2e_rate,
        'data_fraction': data_fraction,
        'stages': stages,
        'queues': queues,
        'bottleneck': attribution['bottleneck'],
        'headroom_vs_device': attribution['headroom_vs_device'],
        'anomalies': [anomaly.kind for anomaly in anomalies],
    }
    self.last_record = record

    if anomalies:
      family = registry.counter_family(ANOMALY_COUNTER, ('kind',))
      for anomaly in anomalies:
        family.series(anomaly.kind).inc()
    self._last_counters = counters
    return record, anomalies

  # -- detections ------------------------------------------------------------

  def _detect(self, step: int, e2e_rate: float, data_fraction: float,
              counters: Dict[str, float], stages: Dict[str, Dict[str, object]],
              attribution: Dict[str, object]) -> List[Anomaly]:
    config = self.config
    anomalies: List[Anomaly] = []

    # pipeline_stall: flow collapsed vs the healthy baseline while the
    # trainer starved on data — the host path stopped producing.
    rate_baseline = (statistics.median(self._rate_baseline)
                     if len(self._rate_baseline)
                     >= config.min_baseline_windows else None)
    stalled = (rate_baseline is not None and rate_baseline > 0.0
               and e2e_rate < rate_baseline / config.stall_ratio
               and data_fraction > config.stall_data_fraction)
    if stalled:
      gate = attribution.get('bottleneck') or 'unknown'
      anomalies.append(Anomaly(
          PIPELINE_STALL, step,
          'pipeline flow fell to {:.1f} ex/s ({:.1f}x below the {:.1f} ex/s '
          'baseline) with {:.0%} of the window lost to data; gating stage: '
          '{}'.format(e2e_rate, rate_baseline / max(e2e_rate, 1e-9),
                      rate_baseline, data_fraction, gate),
          {'examples_per_sec': e2e_rate, 'baseline': rate_baseline,
           'data_fraction': data_fraction, 'stage': gate}))
    else:
      self._rate_baseline.append(e2e_rate)

    # worker_starvation: the decode pool idled while the trainer starved
    # — record I/O (or upstream backpressure) cannot feed the workers.
    last = {} if self._last_counters is None else self._last_counters
    decode = stages.get('decode')
    if decode is not None:
      idle = (counters.get(DECODE_IDLE_COUNTER, 0.0)
              - last.get(DECODE_IDLE_COUNTER, 0.0))
      busy = float(decode['busy_seconds'])
      active = idle + busy
      if active > config.min_stage_busy_seconds:
        idle_fraction = idle / active
        if (idle_fraction > config.starvation_idle_fraction
            and data_fraction > config.starvation_data_fraction):
          anomalies.append(Anomaly(
              WORKER_STARVATION, step,
              'decode workers idled {:.0%} of their window while {:.0%} of '
              'trainer time was lost to data: the read stage cannot feed '
              'the pool'.format(idle_fraction, data_fraction),
              {'worker_idle_fraction': idle_fraction,
               'data_fraction': data_fraction}))

    # transfer_regression: host->device MB/s fell below its baseline.
    transfer = stages.get('transfer')
    if transfer is not None and transfer.get('mb_per_sec') is not None:
      busy_fraction = float(transfer['busy_fraction'])
      mb_per_sec = float(transfer['mb_per_sec'])
      if busy_fraction >= config.transfer_min_busy_fraction:
        baseline = (statistics.median(self._transfer_baseline)
                    if len(self._transfer_baseline)
                    >= config.min_baseline_windows else None)
        if baseline is not None and \
            mb_per_sec < baseline / config.transfer_regression_ratio:
          anomalies.append(Anomaly(
              TRANSFER_REGRESSION, step,
              'host->device transfer fell to {:.1f} MB/s ({:.1f}x below '
              'the {:.1f} MB/s baseline)'.format(
                  mb_per_sec, baseline / max(mb_per_sec, 1e-9), baseline),
              {'mb_per_sec': mb_per_sec, 'baseline': baseline}))
        else:
          self._transfer_baseline.append(mb_per_sec)
    return anomalies
