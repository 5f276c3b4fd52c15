"""Anomaly detection over the telemetry registry: symptom -> trigger.

PR 3 made the trainer *record* step times, goodput splits, and
reliability counters; this watchdog is the component that *reads* them
at the trainer's log cadence and decides "this run just got slower /
hungrier / recompile-happy" against its own rolling baseline — the
Podracer (arXiv:2104.06272) posture of treating utilization regressions
as monitored failures, not graphs someone may eyeball later. Detections
(docs/observability.md):

  * ``step_time_regression`` — the current log window's mean step time
    exceeds ``regression_ratio`` x the rolling-median baseline of recent
    healthy windows. Anomalous windows are NOT folded into the baseline,
    so a sustained slowdown keeps firing instead of normalizing itself.
  * ``goodput_drop`` — the window's productive fraction fell more than
    ``goodput_drop`` below the baseline median productive fraction.
  * ``recompile`` — ``recompiles/train_step`` (the trainer's jit-cache
    size) grew past its post-warmup value, or the device feed reports
    more than one distinct batch shape signature: the shape-stability
    invariant of data/device_feed.py, asserted instead of commented.
  * ``hbm_growth`` — a device's ``memory/device_bytes_in_use`` gauge
    grew monotonically for ``hbm_growth_windows`` consecutive windows by
    more than ``hbm_growth_bytes`` total: the leak signature (a stable
    training step reuses buffers; a watermark that climbs every window
    is retained state, not noise).
  * ``mfu_regression`` — the live ``perf/mfu`` gauge (published by the
    trainer every log window from the shared cost model,
    observability/roofline.py) fell below ``mfu_regression_ratio`` x
    its rolling-median healthy baseline. Same healthy-windows-only
    folding as step time — a sustained utilization collapse keeps
    firing. Hosts with no peaks entry (CPU) never publish the gauge, so
    the check is trivially quiet there instead of noisily wrong.
  * ``heartbeat_stale`` — out-of-process only (``check_heartbeat``):
    the heartbeat file's age exceeds ``heartbeat_stale_secs``. In-process
    the trainer loop IS the heartbeat writer, so staleness is checked by
    ``t2r_telemetry doctor`` / external monitors, not ``observe()``.

Three further kinds — ``pipeline_stall``, ``worker_starvation``, and
``transfer_regression`` — are detected by the pipeline X-ray
(observability/pipeline_xray.py) over the ``pipeline/<stage>/...``
counters and flow through the same ``watchdog/anomalies`` counter
family, telemetry ``anomaly`` records, and capture-request loop. Two
FLEET kinds — ``straggler`` (one host's step time >= 2x the fleet
median) and ``host_dead`` (one host's heartbeat stale while others
advance) — are detected by ``observability/fleet.py``'s FleetWatchdog
over the per-host heartbeat streams and flow through the same loop.

The watchdog holds no threads and does no I/O: ``observe()`` is a pure
in-memory pass the trainer calls at its log cadence, and every duration
it consumes comes from ``time.perf_counter`` windows upstream — the
monotonic-clock discipline tests/test_no_wallclock.py enforces.
"""

from __future__ import annotations

import collections
import statistics
from typing import Deque, Dict, List, Optional

from tensor2robot_tpu.observability import registry as registry_lib
# Writer of the MFU gauge this watchdog reads (stdlib-only import).
from tensor2robot_tpu.observability.roofline import MFU_GAUGE

__all__ = ['Anomaly', 'Watchdog', 'WatchdogConfig',
           'ANOMALY_COUNTER', 'RECOMPILE_GAUGE', 'FEED_SHAPES_GAUGE',
           'DEVICE_BYTES_GAUGE', 'MFU_GAUGE', 'MFU_REGRESSION',
           'STRAGGLER', 'HOST_DEAD', 'check_heartbeat']

# Metric names this watchdog reads (writers: trainer + data/device_feed +
# observability/signals.py) and writes (the anomaly counter family).
ANOMALY_COUNTER = 'watchdog/anomalies'
RECOMPILE_GAUGE = 'recompiles/train_step'
FEED_SHAPES_GAUGE = 'data/feed_shape_signatures'
DEVICE_BYTES_GAUGE = 'memory/device_bytes_in_use'

STEP_TIME_REGRESSION = 'step_time_regression'
GOODPUT_DROP = 'goodput_drop'
RECOMPILE = 'recompile'
HBM_GROWTH = 'hbm_growth'
MFU_REGRESSION = 'mfu_regression'
HEARTBEAT_STALE = 'heartbeat_stale'
# Fleet kinds, detected by observability/fleet.py (FleetWatchdog):
STRAGGLER = 'straggler'
HOST_DEAD = 'host_dead'


class Anomaly:
  """One detection: what fired, at which step, with the evidence."""

  __slots__ = ('kind', 'step', 'message', 'detail')

  def __init__(self, kind: str, step: int, message: str,
               detail: Optional[Dict[str, object]] = None):
    self.kind = kind
    self.step = int(step)
    self.message = message
    self.detail = dict(detail or {})

  def to_record(self) -> Dict[str, object]:
    """The telemetry.jsonl / forensics-report payload form."""
    return {'kind': self.kind, 'step': self.step, 'message': self.message,
            'detail': self.detail}

  def __repr__(self):
    return 'Anomaly({}, step={}, {!r})'.format(self.kind, self.step,
                                               self.message)


class WatchdogConfig:
  """Thresholds; defaults tuned to fire on sustained 2x regressions, not
  single-window jitter."""

  def __init__(self,
               regression_ratio: float = 1.8,
               min_baseline_windows: int = 3,
               baseline_windows: int = 16,
               goodput_drop: float = 0.25,
               hbm_growth_windows: int = 4,
               hbm_growth_bytes: float = 64 * 2**20,
               recompile_warmup_windows: int = 1,
               heartbeat_stale_secs: float = 300.0,
               mfu_regression_ratio: float = 0.75):
    if regression_ratio <= 1.0:
      raise ValueError('regression_ratio must exceed 1.0; got {}.'.format(
          regression_ratio))
    if not 0.0 < goodput_drop < 1.0:
      raise ValueError('goodput_drop must be a fraction in (0, 1); got {}.'
                       .format(goodput_drop))
    if not 0.0 < mfu_regression_ratio < 1.0:
      raise ValueError('mfu_regression_ratio must be a fraction in (0, 1); '
                       'got {}.'.format(mfu_regression_ratio))
    self.regression_ratio = float(regression_ratio)
    self.min_baseline_windows = int(min_baseline_windows)
    self.baseline_windows = int(baseline_windows)
    self.goodput_drop = float(goodput_drop)
    self.hbm_growth_windows = int(hbm_growth_windows)
    self.hbm_growth_bytes = float(hbm_growth_bytes)
    self.recompile_warmup_windows = int(recompile_warmup_windows)
    self.heartbeat_stale_secs = float(heartbeat_stale_secs)
    self.mfu_regression_ratio = float(mfu_regression_ratio)


class Watchdog:
  """Rolling-baseline anomaly detector over one training run."""

  def __init__(self, config: Optional[WatchdogConfig] = None,
               registry: Optional[registry_lib.TelemetryRegistry] = None):
    self.config = config or WatchdogConfig()
    self._registry = registry
    self._step_times: Deque[float] = collections.deque(
        maxlen=self.config.baseline_windows)
    self._productive: Deque[float] = collections.deque(
        maxlen=self.config.baseline_windows)
    self._last_goodput_seconds: Optional[Dict[str, float]] = None
    self._mfu: Deque[float] = collections.deque(
        maxlen=self.config.baseline_windows)
    self._windows_seen = 0
    self._recompile_baseline: Optional[float] = None
    self._shapes_reported = 1.0  # highest signature count already reported
    # device label -> consecutive-growth count and last watermark.
    self._hbm_last: Dict[str, float] = {}
    self._hbm_streak: Dict[str, int] = {}
    self._hbm_streak_bytes: Dict[str, float] = {}

  @property
  def registry(self) -> registry_lib.TelemetryRegistry:
    return self._registry or registry_lib.get_registry()

  # -- in-process detections -------------------------------------------------

  def observe(self, step: int, step_time_s: Optional[float],
              goodput_seconds: Optional[Dict[str, float]] = None
              ) -> List[Anomaly]:
    """One log-cadence pass; returns (and counts) fired anomalies.

    ``step_time_s`` is the window's mean seconds/step; ``goodput_seconds``
    the tracker's CUMULATIVE seconds (the watchdog differences
    consecutive calls itself, so callers just pass ``tracker.seconds()``).
    """
    anomalies: List[Anomaly] = []
    self._windows_seen += 1
    if step_time_s is not None:
      anomalies.extend(self._observe_step_time(step, float(step_time_s)))
    if goodput_seconds is not None:
      anomalies.extend(self._observe_goodput(step, dict(goodput_seconds)))
    anomalies.extend(self._observe_recompiles(step))
    anomalies.extend(self._observe_hbm(step))
    anomalies.extend(self._observe_mfu(step))
    if anomalies:
      family = self.registry.counter_family(ANOMALY_COUNTER, ('kind',))
      for anomaly in anomalies:
        family.series(anomaly.kind).inc()
    return anomalies

  def _observe_step_time(self, step: int, step_time_s: float
                         ) -> List[Anomaly]:
    baseline = (statistics.median(self._step_times)
                if len(self._step_times) >= self.config.min_baseline_windows
                else None)
    if baseline is not None and baseline > 0.0 and \
        step_time_s > self.config.regression_ratio * baseline:
      return [Anomaly(
          STEP_TIME_REGRESSION, step,
          'step time {:.1f} ms/step is {:.1f}x the rolling baseline '
          '{:.1f} ms/step'.format(step_time_s * 1e3,
                                  step_time_s / baseline, baseline * 1e3),
          {'step_time_s': step_time_s, 'baseline_s': baseline,
           'ratio': step_time_s / baseline})]
    # Healthy window: fold into the baseline (anomalous ones stay out so a
    # sustained regression cannot normalize itself away).
    self._step_times.append(step_time_s)
    return []

  def _observe_goodput(self, step: int, seconds: Dict[str, float]
                       ) -> List[Anomaly]:
    last = self._last_goodput_seconds
    self._last_goodput_seconds = seconds
    if last is None:
      return []
    window = {k: seconds.get(k, 0.0) - last.get(k, 0.0) for k in seconds}
    total = sum(window.values())
    if total <= 0.0:
      return []
    productive = window.get('productive', 0.0) / total
    baseline = (statistics.median(self._productive)
                if len(self._productive) >= self.config.min_baseline_windows
                else None)
    if baseline is not None and \
        productive < baseline - self.config.goodput_drop:
      lost = {k: v / total for k, v in window.items()
              if k != 'productive' and v > 0.0}
      top = max(lost, key=lost.get) if lost else 'unknown'
      return [Anomaly(
          GOODPUT_DROP, step,
          'productive fraction {:.0%} fell below baseline {:.0%} - {:.0%}; '
          'largest loss: {} ({:.0%})'.format(
              productive, baseline, self.config.goodput_drop, top,
              lost.get(top, 0.0)),
          {'productive_fraction': productive, 'baseline_fraction': baseline,
           'window_fractions': {k: v / total for k, v in window.items()}})]
    self._productive.append(productive)
    return []

  def _observe_recompiles(self, step: int) -> List[Anomaly]:
    anomalies = []
    # The shape-stability invariant is independent of the cache-size
    # probe (which is absent on some jax versions): check it even while
    # the recompile gauge is still 0. Latched like the cache-size path —
    # one stale signature must not re-fire every window for the rest of
    # the run (burning the capture budget on a long-past incident).
    shapes = self.registry.gauge(FEED_SHAPES_GAUGE).value
    if shapes > self._shapes_reported and shapes > 1.0:
      anomalies.append(Anomaly(
          RECOMPILE, step,
          'device feed emitted {:g} distinct batch shape signatures; the '
          'dense post-unpack batch must be shape-stable'.format(shapes),
          {'shape_signatures': shapes}))
      self._shapes_reported = shapes
    gauge = self.registry.gauge(RECOMPILE_GAUGE)
    value = gauge.value
    if value <= 0.0:
      return anomalies  # trainer has not sampled its jit cache yet
    if self._windows_seen <= self.config.recompile_warmup_windows or \
        self._recompile_baseline is None:
      # The first compile lands during warmup; lock the baseline there.
      self._recompile_baseline = value
      return anomalies
    if value > self._recompile_baseline:
      anomalies.append(Anomaly(
          RECOMPILE, step,
          'train step recompiled: jit cache grew {:g} -> {:g} (shape-'
          'unstable batch reached the compiled step)'.format(
              self._recompile_baseline, value),
          {'cache_size': value, 'baseline': self._recompile_baseline}))
      self._recompile_baseline = value  # report each growth once
    return anomalies

  def _observe_hbm(self, step: int) -> List[Anomaly]:
    family = self.registry.gauge_family(DEVICE_BYTES_GAUGE, ('device',))
    anomalies = []
    for labels, gauge in family.items():
      device = labels[0]
      value = gauge.value
      last = self._hbm_last.get(device)
      self._hbm_last[device] = value
      if last is None or value <= last:
        self._hbm_streak[device] = 0
        self._hbm_streak_bytes[device] = 0.0
        continue
      self._hbm_streak[device] = self._hbm_streak.get(device, 0) + 1
      self._hbm_streak_bytes[device] = \
          self._hbm_streak_bytes.get(device, 0.0) + (value - last)
      if self._hbm_streak[device] >= self.config.hbm_growth_windows and \
          self._hbm_streak_bytes[device] >= self.config.hbm_growth_bytes:
        anomalies.append(Anomaly(
            HBM_GROWTH, step,
            'device {} HBM in use grew {} windows in a row (+{:.1f} MiB, '
            'now {:.1f} MiB): leak signature'.format(
                device, self._hbm_streak[device],
                self._hbm_streak_bytes[device] / 2**20, value / 2**20),
            {'device': device, 'windows': self._hbm_streak[device],
             'growth_bytes': self._hbm_streak_bytes[device],
             'bytes_in_use': value}))
        # Re-arm: keep watching, but don't fire every subsequent window.
        self._hbm_streak[device] = 0
        self._hbm_streak_bytes[device] = 0.0
    return anomalies

  def _observe_mfu(self, step: int) -> List[Anomaly]:
    # Published by the trainer from the shared cost model only on hosts
    # with a device-peaks entry; <= 0 means "not applicable", not "0%
    # utilized" — skip, never baseline it.
    value = self.registry.gauge(MFU_GAUGE).value
    if value <= 0.0:
      return []
    baseline = (statistics.median(self._mfu)
                if len(self._mfu) >= self.config.min_baseline_windows
                else None)
    if baseline is not None and baseline > 0.0 and \
        value < self.config.mfu_regression_ratio * baseline:
      return [Anomaly(
          MFU_REGRESSION, step,
          'MFU {:.1%} fell below {:.0%} of the rolling baseline {:.1%}: '
          'the device step is doing the same flops slower'.format(
              value, self.config.mfu_regression_ratio, baseline),
          {'mfu': value, 'baseline_mfu': baseline,
           'ratio': value / baseline})]
    # Healthy window: fold in (anomalous ones stay out, same rationale
    # as step time).
    self._mfu.append(value)
    return []

  # -- out-of-process detections ---------------------------------------------

  def check_heartbeat(self, heartbeat: Optional[Dict[str, object]],
                      now: float) -> List[Anomaly]:
    """Staleness of a run's heartbeat.json, for doctor/external monitors.

    ``now`` must come from the same clock as the heartbeat's ``time``
    field (wall clock — heartbeats cross process boundaries, so the
    monotonic discipline cannot apply; the comparison is best-effort by
    nature and documented as such).
    """
    if heartbeat is None:
      return [Anomaly(HEARTBEAT_STALE, -1,
                      'no heartbeat.json: the run never started its '
                      'telemetry, or the file was removed', {})]
    age = float(now) - float(heartbeat.get('time', 0.0))
    if age > self.config.heartbeat_stale_secs:
      step = heartbeat.get('step')
      step = -1 if step is None else int(step)  # step 0 is a real step
      return [Anomaly(
          HEARTBEAT_STALE, step,
          'heartbeat is {:.0f}s old (threshold {:.0f}s): process wedged, '
          'killed, or telemetry disabled'.format(
              age, self.config.heartbeat_stale_secs),
          {'age_seconds': age, 'pid': heartbeat.get('pid'),
           'hostname': heartbeat.get('hostname')})]
    return []


def check_heartbeat(heartbeat: Optional[Dict[str, object]], now: float,
                    stale_secs: float = 300.0) -> List[Anomaly]:
  """Module-level convenience for doctor: one-off staleness check."""
  return Watchdog(WatchdogConfig(heartbeat_stale_secs=stale_secs)) \
      .check_heartbeat(heartbeat, now)
