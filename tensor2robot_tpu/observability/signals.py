"""New registry signal sources: XLA compile events + memory watermarks.

Two classes of signals the PR 3 registry could not see:

  * **Compilations.** ``jax.monitoring`` fires named events around every
    jaxpr trace and backend (XLA) compile. One module-level dispatcher is
    registered ONCE per process (jax's listener list has no unregister in
    its public API) and routes into whatever ``get_registry()`` currently
    is, gated by an enabled flag — so tests that swap registries or call
    ``uninstall_jax_listeners`` need no private-API surgery. A silent
    recompile mid-run (a shape-unstable batch reaching a jitted step) was
    previously invisible until someone noticed the step-time graph; now
    it is ``jax/compiles`` + ``jax/compile_ms`` landing in TensorBoard
    and telemetry.jsonl, and the watchdog's ``recompile`` trigger. The
    same dispatcher writes each phase into the span ring (`spans.py`)
    with the function's name, under the program span that caused it:
    ``compile.trace``, ``compile.lower``, ``compile.backend`` (below).
  * **Memory watermarks.** ``device.memory_stats()`` per accelerator
    (None on CPU — skipped, not faked) and host RSS from /proc (fallback
    ``resource.getrusage``), sampled by the trainer at its log cadence.
    A monotonically climbing ``memory/device_bytes_in_use`` is the leak
    signature the watchdog's ``hbm_growth`` detection consumes.

Everything here degrades to a no-op on hosts without jax (the doctor CLI
imports the observability package; it must stay jax-free), so jax is
imported lazily and failures are swallowed where noted.
"""

from __future__ import annotations

import os
import resource
import socket
import threading
import time
from typing import Dict, Optional

from tensor2robot_tpu.observability import registry as registry_lib
from tensor2robot_tpu.observability import spans

__all__ = [
    'COMPILE_COUNTER', 'COMPILE_MS_HISTOGRAM', 'TRACE_MS_HISTOGRAM',
    'CACHE_MISS_COUNTER', 'CACHE_HIT_COUNTER', 'HOST_RSS_GAUGE',
    'HOST_PEAK_RSS_GAUGE',
    'DEVICE_BYTES_GAUGE', 'DEVICE_PEAK_BYTES_GAUGE',
    'install_jax_listeners', 'uninstall_jax_listeners', 'sample_memory',
    'host_identity',
]

COMPILE_COUNTER = 'jax/compiles'
COMPILE_MS_HISTOGRAM = 'jax/compile_ms'
TRACE_MS_HISTOGRAM = 'jax/trace_ms'
CACHE_MISS_COUNTER = 'jax/compilation_cache_misses'
CACHE_HIT_COUNTER = 'jax/compilation_cache_hits'

HOST_RSS_GAUGE = 'memory/host_rss_bytes'
HOST_PEAK_RSS_GAUGE = 'memory/host_peak_rss_bytes'
DEVICE_BYTES_GAUGE = 'memory/device_bytes_in_use'
DEVICE_PEAK_BYTES_GAUGE = 'memory/device_peak_bytes'

# jax._src.dispatch event names (unknown events are
# simply never matched, so a rename degrades to "no signal", not a crash).
_BACKEND_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'
_JAXPR_TRACE_EVENT = '/jax/core/compile/jaxpr_trace_duration'
_LOWER_EVENT = '/jax/core/compile/jaxpr_to_mlir_module_duration'
_CACHE_MISS_EVENT = '/jax/compilation_cache/cache_misses'
_CACHE_HIT_EVENT = '/jax/compilation_cache/cache_hits'
_CACHE_READ_EVENT = '/jax/compilation_cache/cache_retrieval_time_sec'

# The three phases of a compile request, as ring records. jax announces a
# phase at its start (a scalar) and times it at its end (a duration), both
# on the compiling thread and both with ``fun_name`` (dispatch.py,
# ``LogElapsedTimeContextManager``); ``backend_compile_duration`` wraps
# ``compile_or_get_cached``, so on a cache hit it is the read and the load.
_PHASE_RECORDS = {_JAXPR_TRACE_EVENT: 'compile.trace',
                  _LOWER_EVENT: 'compile.lower',
                  _BACKEND_COMPILE_EVENT: 'compile.backend'}

_installed = False
_enabled = False


class _Phase:
  """A phase jax has announced and not yet timed, and what fired inside."""

  __slots__ = ('event', 'inner', 'from_cache', 'cache_read_ms')

  def __init__(self, event: str):
    self.event = event
    self.inner = 0  # phase events folded into this one
    self.from_cache = 0  # a persistent-cache hit fired inside it
    self.cache_read_ms = 0.0


class _OpenPhases(threading.local):
  """Per thread: its open phases, outermost first. Kept whether or not the
  dispatcher is enabled, so that enabling it inside a trace cannot leave a
  phase open for ever."""

  def __init__(self):
    self.stack = []


_PHASES = _OpenPhases()


def _on_scalar(event: str, value, **kwargs) -> None:
  if event in _PHASE_RECORDS:
    _PHASES.stack.append(_Phase(event))


def _close_phase(event: str, name: str, duration_secs: float, fun) -> None:
  """One ring record per OUTERMOST phase of a thread. A jitted function
  traced while another is traced or lowered (every inner ``jax.jit``, every
  jitted ``jax.numpy`` function, what Mosaic traces while lowering a
  kernel) fires events of its own: those are counted in the outer record's
  ``inner`` and write nothing, so a set-up writes tens of records and their
  seconds add up. A backend compile is ALWAYS a record (their count is
  ``jax/compiles``); one inside a trace lies inside that trace's record on
  the same thread, which is how a reader takes its seconds out."""
  end_ns = time.perf_counter_ns()
  stack = _PHASES.stack
  # jax's phases nest, so the newest announcement is this phase's own; one
  # announced before the listeners were registered has none.
  phase = stack.pop() if stack else None
  if phase is None or phase.event != event:
    phase = _Phase(event)
  if not _enabled:
    return
  start_ns = end_ns - max(0, int(duration_secs * 1e9))
  if event == _BACKEND_COMPILE_EVENT:
    spans.interval(name, start_ns, end_ns, fun=fun,
                   from_cache=phase.from_cache,
                   cache_read_ms=phase.cache_read_ms)
  elif stack:
    stack[-1].inner += 1 + phase.inner
  else:
    spans.interval(name, start_ns, end_ns, fun=fun, inner=phase.inner)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
  name = _PHASE_RECORDS.get(event)
  if name is not None:
    _close_phase(event, name, duration_secs, kwargs.get('fun_name', ''))
  elif event == _CACHE_READ_EVENT and _PHASES.stack:
    _PHASES.stack[-1].cache_read_ms = duration_secs * 1e3
  if not _enabled:
    return
  registry = registry_lib.get_registry()
  if event == _BACKEND_COMPILE_EVENT:
    registry.counter(COMPILE_COUNTER).inc()
    registry.histogram(
        COMPILE_MS_HISTOGRAM,
        bounds=registry_lib.DEFAULT_LATENCY_BUCKETS_MS).record(
            duration_secs * 1e3)
  elif event == _JAXPR_TRACE_EVENT:
    registry.histogram(
        TRACE_MS_HISTOGRAM,
        bounds=registry_lib.DEFAULT_LATENCY_BUCKETS_MS).record(
            duration_secs * 1e3)


def _on_event(event: str, **kwargs) -> None:
  if not _enabled:
    return
  if event == _CACHE_MISS_EVENT:
    registry_lib.get_registry().counter(CACHE_MISS_COUNTER).inc()
  elif event == _CACHE_HIT_EVENT:
    registry_lib.get_registry().counter(CACHE_HIT_COUNTER).inc()
    if _PHASES.stack:
      _PHASES.stack[-1].from_cache = 1


def install_jax_listeners() -> bool:
  """Enables compile-event accounting; returns False on jax-free hosts.

  Idempotent: the dispatcher is registered with jax.monitoring exactly
  once per process; repeat calls only flip the enabled flag back on.
  """
  global _installed, _enabled
  try:
    from jax import monitoring
  except Exception:  # noqa: BLE001 — jax-free host (doctor CLI)
    return False
  if not _installed:
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    monitoring.register_scalar_listener(_on_scalar)
    _installed = True
  _enabled = True
  return True


def uninstall_jax_listeners() -> None:
  """Disables the dispatcher (registration with jax remains; it is a
  no-op while disabled). Test hook."""
  global _enabled
  _enabled = False


def host_identity() -> Dict[str, object]:
  """This process's fleet identity: the ``host_meta`` dict every
  per-host telemetry record is stamped with (ISSUE 9).

  ``{'process_index', 'process_count', 'device_kind', 'device_count',
  'hostname'}`` — process coordinates from ``jax.distributed``'s view
  of the world, device kind + local chip count from the local device
  list (the roofline/MFU consumers need BOTH: per-device program flops
  are per-chip, the peaks table is per-``device_kind``). Degrades to
  the single-process identity (``0 of 1``, ``device_kind='unknown'``,
  ``device_count=0``) on jax-free hosts so the doctor/fleet tooling can
  call it too.
  """
  identity: Dict[str, object] = {
      'process_index': 0,
      'process_count': 1,
      'device_kind': 'unknown',
      'device_count': 0,
      'hostname': socket.gethostname(),
  }
  try:
    import jax

    identity['process_index'] = int(jax.process_index())
    identity['process_count'] = int(jax.process_count())
    local = jax.local_devices()
    identity['device_count'] = len(local)
    if local:
      identity['device_kind'] = str(
          getattr(local[0], 'device_kind', 'unknown'))
  except Exception:  # noqa: BLE001 — jax-free or uninitialized backend
    pass
  return identity


def _host_rss_bytes() -> Optional[float]:
  """Current resident set size; /proc first, portable-ish fallback."""
  try:
    with open('/proc/self/statm') as f:
      pages = int(f.read().split()[1])
    return float(pages * os.sysconf('SC_PAGE_SIZE'))
  except (OSError, ValueError, IndexError):
    pass
  try:
    # ru_maxrss is the PEAK (kilobytes on linux), not current — better
    # than nothing on /proc-less hosts; the peak gauge below is exact.
    return float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024)
  except Exception:  # noqa: BLE001
    return None


def sample_memory(
    registry: Optional[registry_lib.TelemetryRegistry] = None
) -> Dict[str, float]:
  """Samples device + host memory watermarks into gauges; returns them.

  Device stats come from ``device.memory_stats()`` (PJRT; ``None`` on
  the CPU backend — those devices are skipped so dashboards never show a
  fake 0-byte TPU). Gauge names: ``memory/device_bytes_in_use/<device>``,
  ``memory/device_peak_bytes/<device>``, ``memory/host_rss_bytes``,
  ``memory/host_peak_rss_bytes``.
  """
  registry = registry or registry_lib.get_registry()
  out: Dict[str, float] = {}
  try:
    import jax
    devices = jax.devices()
  except Exception:  # noqa: BLE001 — jax-free or uninitialized backend
    devices = []
  in_use = registry.gauge_family(DEVICE_BYTES_GAUGE, ('device',))
  peak = registry.gauge_family(DEVICE_PEAK_BYTES_GAUGE, ('device',))
  for device in devices:
    try:
      stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — backend without the PJRT API
      stats = None
    if not stats:
      continue
    label = str(device.id)
    value = float(stats.get('bytes_in_use', 0.0))
    in_use.series(label).set(value)
    out['{}/{}'.format(DEVICE_BYTES_GAUGE, label)] = value
    peak_value = float(stats.get('peak_bytes_in_use', 0.0))
    if peak_value:
      peak.series(label).set(peak_value)
      out['{}/{}'.format(DEVICE_PEAK_BYTES_GAUGE, label)] = peak_value
  rss = _host_rss_bytes()
  if rss is not None:
    registry.gauge(HOST_RSS_GAUGE).set(rss)
    out[HOST_RSS_GAUGE] = rss
  try:
    peak_rss = float(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024)
    registry.gauge(HOST_PEAK_RSS_GAUGE).set(peak_rss)
    out[HOST_PEAK_RSS_GAUGE] = peak_rss
  except Exception:  # noqa: BLE001
    pass
  return out
