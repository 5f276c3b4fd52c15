"""What the driver of any kind of traffic needs: the model named in a
configuration file, the device check, the compile cache, counters, the
profiler window."""

import glob
import importlib
import os
import time

import numpy as np



class Refused(SystemExit):
  """The cell cannot be measured here; the process exits non-zero and prints
  no result line."""

  def __init__(self, message):
    print('benchmark refused: ' + message, flush=True)
    super().__init__(5)


def log(message, *args):
  print('[bench {:8.2f}s] {}'.format(
      time.perf_counter() - _T0, message.format(*args)), flush=True)


_T0 = time.perf_counter()


def seed31(seed, salt=0):
  """A 31-bit seed from any whole number (the driver's are over 2**31)."""
  state = np.random.SeedSequence([seed % (2**32), seed // (2**32), salt])
  return int(state.generate_state(1)[0] & 0x7FFFFFFF)


def _tuples(value):
  if isinstance(value, list):
    return tuple(_tuples(v) for v in value)
  if isinstance(value, dict):
    return {k: _tuples(v) for k, v in value.items()}
  return value


def build_model(spec, **overrides):
  """{'class': 'module:Class', 'kwargs': {...}} -> the program's model."""
  module_name, _, class_name = spec['class'].partition(':')
  cls = getattr(importlib.import_module(module_name), class_name)
  kwargs = dict(_tuples(spec.get('kwargs', {})))
  kwargs.update(overrides)
  return cls(**kwargs)


def claim_devices(cell):
  """The devices this cell runs on, or a refusal: the platform must be the
  one the configuration names (the TPU, unless a test's tiny configuration
  says otherwise) and hold as many chips as the cell asks for."""
  import jax

  wanted = cell.config.get('platform', 'tpu')
  platform = jax.default_backend()
  if platform != wanted:
    raise Refused('JAX backend is {!r}; configuration {!r} is measured on '
                  '{!r} only'.format(platform, cell.config_name, wanted))
  devices = jax.devices()
  if len(devices) < cell.chips:
    raise Refused('cell {!r} needs {} chips, JAX reports {}'.format(
        cell.name, cell.chips, len(devices)))
  return devices[:cell.chips]


def device_report(devices, peak_bytes):
  import jax

  return {'platform': devices[0].platform, 'kind': devices[0].device_kind,
          'count': len(jax.devices()), 'memory_peak_bytes': int(peak_bytes)}


def memory_peak_bytes(devices):
  """Peak bytes on the fullest of ``devices``, read while the cell's program
  is still loaded (at the window's close).

  ``peak_bytes_in_use`` counts the buffers the process holds (state, batches
  in flight, outputs) but not a running program's temporaries: those the
  runtime keeps apart as ``bytes_reserved`` (a program with 1.61 GB of
  temporaries moved the first by 1.5 MB and the second by 1.61 GB, my chip
  run, PR 24). So the peak is the larger of the buffers' own peak and the
  buffers held now plus the temporaries reserved now. 0 where the backend
  does not say, as on the CPU of the tests."""
  peaks = []
  for device in devices:
    stats = device.memory_stats() or {}
    peaks.append(max(stats.get('peak_bytes_in_use', 0),
                     stats.get('bytes_in_use', 0) +
                     stats.get('bytes_reserved', 0)))
  return max(peaks) if peaks else 0


def enable_caches():
  """JAX's persistent cache where the program puts it (``runtime.cache_root``:
  ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), holding every
  program however small or quick, so that a second run compiles nothing."""
  import jax

  from tensor2robot_tpu import runtime
  from tensor2robot_tpu.observability import install_jax_listeners

  root = runtime.enable_compile_cache()
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
  jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
  install_jax_listeners()
  return root


COUNTERS = ('jax/compiles', 'jax/compilation_cache_hits',
            'pipeline/transfer/busy_seconds', 'pipeline/transfer/bytes',
            'pipeline/transfer/examples', 'pipeline/decode/busy_seconds',
            'pipeline/decode/examples', 'pipeline/read/bytes')
SPANS = ('data.next', 'data.put_batch', 'train.step')


def snapshot_counters():
  """{name: value} of the program's counters and span sums the metric
  readers use, read at the source (the program's registry)."""
  from tensor2robot_tpu.observability import get_registry
  from tensor2robot_tpu.observability.spans import SPAN_BUCKETS_MS

  registry = get_registry()
  out = {name: float(registry.counter(name).value) for name in COUNTERS}
  for name in SPANS:
    histogram = registry.histogram('span/' + name, bounds=SPAN_BUCKETS_MS)
    out['span/' + name + '/seconds'] = float(histogram.sum) / 1e3
    out['span/' + name + '/count'] = float(histogram.count)
  out['pipeline/decode/workers'] = float(
      registry.gauge('pipeline/decode/workers').value)
  return out


class ProfilerWindow:
  """A profiler trace inside the run, reduced by ``harness.trace``.

  The host tracer is OFF: on this installation it records one event per
  chunk of every host-to-device copy (4.5 million for three 63 MB batches,
  my chip run, PR 24), which slows the copies many times over and makes the
  trace hundreds of megabytes. So that idle gaps can still be named, the
  drivers ``note`` their own host spans on the host's clock, and the two
  clocks are tied together by a marker: a tiny program run and waited for
  right after the profiler starts, whose end the host sees within about
  0.1 ms of the device's own record of it.
  """

  MARKER = 'bench_marker'

  def __init__(self, directory):
    self.directory = directory
    self.active = False
    self.reduced = None
    self._spans = []
    self._marker_done_s = None

  def note(self, name, start_s, end_s):
    """A host span by ``time.perf_counter``; kept only while tracing."""
    if self.active:
      self._spans.append((name, start_s, end_s))

  def start(self):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    marker = jax.jit(lambda x: x + 1)
    marker.__wrapped__.__name__ = self.MARKER
    operand = jnp.zeros((), jnp.float32)
    jax.block_until_ready(marker(operand))  # compiled before the trace
    jax.profiler.start_trace(self.directory, profiler_options=options)
    jax.block_until_ready(marker(operand))
    self._marker_done_s = time.perf_counter()
    self.active = True

  def stop(self):
    import jax

    from benchmark.harness import trace

    jax.profiler.stop_trace()
    self.active = False
    paths = glob.glob(os.path.join(self.directory, '**', '*.xplane.pb'),
                      recursive=True)
    if not paths:
      return
    log('trace written: {} bytes', os.path.getsize(paths[0]))
    loaded = trace.align_host_spans(trace.load_xplane(paths[0]), self.MARKER,
                                    self._marker_done_s, self._spans)
    self.reduced = trace.reduce_trace(loaded)
