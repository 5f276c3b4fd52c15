"""Budgeted, watchdog-triggered profiler capture windows.

The trainer used to bracket ONE static ``profile_steps`` window chosen
before the run — useless for the regression that shows up at step 40k of
a job someone launched Friday night. The ``AutoProfiler`` closes the
loop: the watchdog names a symptom, this class decides whether a capture
is allowed (budget + rate limit, so a flapping anomaly cannot turn the
profiler into the slowdown it was meant to explain), brackets a
``window_steps``-long ``jax.profiler`` trace, and on stop feeds the raw
xplane through `observability/forensics.py` into ``forensics/<step>.json``
— symptom -> capture -> attribution with no human in the loop.

Static windows stay supported (the ``profile_steps`` trainer arg maps to
``static_window``) and do not consume the triggered-capture budget: a
deliberate pre-planned capture and an incident response are different
budgets.

Every capture of a device runs with the profiler's host and Python
tracers OFF: on a TPU host they record one event per chunk of every
host-to-device copy (4.5 million for three 63 MB batches, a 148 MB trace
and a loop 50 times slower: PERF.md, Findings PR 24), so a capture with
them on IS the slowdown. (Only on the CPU backend, where XLA's thunks are
themselves host-tracer events and nothing else would be captured, the
host tracer stays on; the Python tracer never is.) What the host did
during a capture comes from the program's own span ring (`spans.py`),
tied to the trace's clock by a marker: a tiny program run and waited for
right after ``start_trace``, whose end the host notes on the ring's clock
and the device records on the trace's.

All timing here is ``time.perf_counter`` (rate limiting is a duration,
and tests/test_no_wallclock.py enforces the monotonic discipline). All
jax imports are deferred and failures disable the profiler for the rest
of the run (``broken``) instead of raising into the train loop —
profiling is evidence collection, never a liveness risk.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from tensor2robot_tpu.observability import forensics
from tensor2robot_tpu.observability import registry as registry_lib
from tensor2robot_tpu.observability import spans
from tensor2robot_tpu.observability.spans import span

__all__ = ['AutoProfiler', 'CAPTURE_COUNTER']

CAPTURE_COUNTER = 'profiler/captures'

_logv = None


def _log(msg: str, *args) -> None:
  global _logv
  if _logv is None:
    from absl import logging as _absl_logging  # deferred: absl optional
    _logv = _absl_logging.info
  _logv(msg, *args)


def _clock_marker(x):
  return x + 1


# The jitted program carries this name in the trace's ``XLA Modules`` line,
# which is how ``forensics`` finds its end there.
_clock_marker.__name__ = forensics.CLOCK_MARKER


class AutoProfiler:
  """Owns profiler trace windows for one model_dir: static + triggered."""

  def __init__(self,
               model_dir: str,
               static_window: Optional[Sequence[int]] = None,
               window_steps: int = 5,
               max_captures: int = 2,
               min_interval_secs: float = 600.0,
               emit_reports: bool = True,
               registry: Optional[registry_lib.TelemetryRegistry] = None):
    """max_captures / min_interval_secs bound TRIGGERED captures only:
    the budget caps a run's total profiling overhead, the rate limit
    keeps a flapping watchdog from capturing back-to-back windows of the
    same incident. ``emit_reports=False`` leaves raw protos (the
    pre-forensics behavior) for callers that post-process elsewhere."""
    self.model_dir = model_dir
    self._static = tuple(static_window) if static_window else None
    self._window_steps = max(1, int(window_steps))
    self._max_captures = int(max_captures)
    self._min_interval_secs = float(min_interval_secs)
    self._emit_reports = emit_reports
    self._registry = registry
    # Callbacks the trainer wires after compile / at train() start.
    self.hlo_text_fn: Optional[Callable[[], Optional[str]]] = None
    self.context_fn: Optional[Callable[[], Dict[str, object]]] = None

    self._active = False
    self._broken = False
    self._pending: Optional[Tuple[str, Dict[str, object], int]] = None
    self._reason: Optional[str] = None
    self._trigger: Dict[str, object] = {}
    self._start_step = 0
    self._stop_step = 0
    self._start_walltime: Optional[float] = None
    self._start_snapshot: Optional[Dict[str, Dict[str, object]]] = None
    self._start_pipeline: Optional[Dict[str, object]] = None
    self._captures_taken = 0
    self._last_capture_end: Optional[float] = None
    self.last_report_path: Optional[str] = None
    # The clock marker: (jitted program, operand), compiled before the
    # first trace starts; and, per capture, the ring-clock times of the
    # capture's start and of the marker's end.
    self._marker = None
    self._start_ns = 0
    self.marker_done_ns: Optional[int] = None

  @property
  def registry(self) -> registry_lib.TelemetryRegistry:
    return self._registry or registry_lib.get_registry()

  @property
  def active(self) -> bool:
    return self._active

  @property
  def broken(self) -> bool:
    return self._broken

  @property
  def captures_taken(self) -> int:
    """Triggered captures completed (static windows not counted)."""
    return self._captures_taken

  # -- trigger side ----------------------------------------------------------

  def request_capture(self, reason: str, step: int,
                      detail: Optional[Dict[str, object]] = None) -> bool:
    """Asks for a window at the next loop iteration. Returns whether the
    request was accepted (budget, rate limit, and no window already
    open/pending — rejections are silent-by-design: the anomaly itself
    is already counted and logged by the watchdog path)."""
    if self._broken or self._active or self._pending is not None:
      return False
    if self._captures_taken >= self._max_captures:
      return False
    if self._last_capture_end is not None and \
        time.perf_counter() - self._last_capture_end \
        < self._min_interval_secs:
      return False
    self._pending = (reason, dict(detail or {}), int(step))
    return True

  # -- loop side -------------------------------------------------------------

  def maybe_profile(self, step: int) -> Optional[str]:
    """Trainer calls this once per iteration, BEFORE dispatching the
    step. Starts pending/static windows, stops finished ones; returns
    the forensics report path when a window just closed (else None)."""
    if self._broken:
      return None
    if self._active:
      if step >= self._stop_step:
        return self._stop(step)
      return None
    if self._static is not None:
      start, stop = self._static
      if step >= stop:
        self._static = None  # window already behind us (restored run)
      elif step >= start:
        self._static = None
        self._start(step, 'static', {}, stop_step=stop)
        return None
    if self._pending is not None:
      reason, detail, requested_step = self._pending
      self._pending = None
      detail.setdefault('requested_step', requested_step)
      self._start(step, reason, detail,
                  stop_step=step + self._window_steps)
    return None

  def finish(self, step: int) -> Optional[str]:
    """Run ended while a window was open: close it WITH a report."""
    if self._active:
      return self._stop(step)
    return None

  def abort(self) -> None:
    """Failure-path cleanup: stop any open trace, no report. A dangling
    trace breaks the next start_trace, so this must run on every unwind
    (the trainer's finally block)."""
    self._pending = None
    if not self._active:
      return
    self._active = False
    try:
      import jax

      jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001 — already unwinding
      _log('Profiler stop on failure path failed: %s', e)

  # -- internals -------------------------------------------------------------

  def _start(self, step: int, reason: str, trigger: Dict[str, object],
             stop_step: int) -> None:
    try:
      import jax
      import jax.numpy as jnp

      if self._marker is None:
        marker = jax.jit(_clock_marker)
        operand = jnp.zeros((), jnp.float32)
        jax.block_until_ready(marker(operand))  # compiled before any trace
        self._marker = (marker, operand)
      options = jax.profiler.ProfileOptions()
      options.python_tracer_level = 0
      # The CPU backend's ops ARE host-tracer events (module docstring).
      if jax.default_backend() != 'cpu':
        options.host_tracer_level = 0
      self._start_ns = time.perf_counter_ns()
      # start_trace appends plugins/profile/<run> itself — pass the
      # logdir root so TensorBoard's profile plugin finds the trace.
      jax.profiler.start_trace(self.model_dir, profiler_options=options)
    except Exception as e:  # noqa: BLE001 — profiling is best-effort
      _log('Profiler unavailable (%s); disabling capture for this run.', e)
      self._broken = True
      return
    # The marker queues behind the steps in flight, so waiting for it
    # also drains the host's lead: a capture starts from a synced device.
    self.marker_done_ns = None
    try:
      marker, operand = self._marker
      jax.block_until_ready(marker(operand))
      self.marker_done_ns = time.perf_counter_ns()
    except Exception as e:  # noqa: BLE001 — the capture is still worth having
      _log('Clock marker failed (%s); idle gaps will not be named.', e)
    self._active = True
    self._reason = reason
    self._trigger = trigger
    self._start_step = step
    self._stop_step = max(stop_step, step + 1)
    # wall-clock on purpose: compared against xplane file st_mtime, which
    # is wall time too — never used as a duration or deadline.
    self._start_walltime = time.time()  # wall-clock: mtime filter
    try:
      self._start_snapshot = self.registry.snapshot()
    except Exception:  # noqa: BLE001
      self._start_snapshot = None
    # The pipeline X-ray record is INCIDENT evidence: snapshot it as the
    # window opens (one iteration after the anomaly fired, before the
    # next log-cadence observe). By window close the newest record
    # describes the capture's own overhead window — profiler start/stop
    # is seconds on some backends — not the stall it answers.
    self._start_pipeline = None
    if self.context_fn is not None:
      try:
        self._start_pipeline = (self.context_fn() or {}).get('pipeline')
      except Exception as e:  # noqa: BLE001
        _log('Forensics context callback at window open failed: %s', e)
    self.registry.counter_family(CAPTURE_COUNTER, ('trigger',)) \
        .series(reason).inc()
    _log('Profiler window [%d, %d) opened (%s).', step, self._stop_step,
         reason)

  def _stop(self, step: int) -> Optional[str]:
    self._active = False
    try:
      import jax

      jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001
      _log('Profiler stop failed: %s', e)
      self._broken = True
      return None
    if self._reason != 'static':
      self._captures_taken += 1
      # Static windows are a separate budget AND a separate rate limit:
      # a pre-planned capture must not delay the first incident response.
      self._last_capture_end = time.perf_counter()
    _log('Profiler trace written under %s', self.model_dir)
    if not self._emit_reports:
      return None
    try:
      with span('forensics.report'):
        return self._emit_report(step)
    except Exception as e:  # noqa: BLE001 — never raise into the loop
      _log('Forensics report for step %d failed: %s', step, e)
      return None

  def _emit_report(self, step: int) -> str:
    context: Dict[str, object] = {}
    if self.context_fn is not None:
      try:
        context = dict(self.context_fn() or {})
      except Exception as e:  # noqa: BLE001
        _log('Forensics context callback failed: %s', e)
    counters_delta: Dict[str, float] = {}
    if self._start_snapshot is not None:
      try:
        delta = registry_lib.snapshot_delta(self._start_snapshot,
                                            self.registry.snapshot())
        counters_delta = {name: value
                          for name, value in delta['counters'].items()
                          if value}
      except Exception:  # noqa: BLE001
        counters_delta = {}
    xplane_path = forensics.find_latest_xplane(
        self.model_dir, newer_than=self._start_walltime)
    # The ring's records of the captured interval (a span still open now,
    # like the loop iteration this runs in, is not in the ring yet). The
    # capture's own thread is the one that dispatches the steps.
    host_trace = {
        'records': [r for r in spans.records()
                    if r.end_ns >= self._start_ns],
        'marker_done_ns': self.marker_done_ns,
        'thread': threading.current_thread().name,
    } if self.marker_done_ns is not None else None
    report = forensics.build_report(
        step=step,
        reason=self._reason or 'static',
        trigger=self._trigger,
        window={'start_step': self._start_step, 'stop_step': step,
                'n_steps': max(step - self._start_step, 1)},
        xplane_path=xplane_path,
        n_steps=max(step - self._start_step, 1),
        hlo_text_fn=self.hlo_text_fn,
        goodput_fractions=context.get('goodput'),
        counters_delta=counters_delta,
        registry=self.registry,
        tuned_config=context.get('tuned_config'),
        pipeline=self._start_pipeline,
        host=context.get('host'),
        host_trace=host_trace)
    path = forensics.write_report(self.model_dir, step, report)
    self.last_report_path = path
    _log('Forensics report: %s (top op: %s)', path,
         report['top_ops'][0]['name'] if report['top_ops'] else 'n/a')
    return path
