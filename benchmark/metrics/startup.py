"""Layers "platform and compiled-program store" and "train step": what
``setup_s`` is made of, read from the program's own span ring.

Since PR 36 the program's one ``jax.monitoring`` dispatcher
(``tensor2robot_tpu/observability/signals.py``) writes every OUTERMOST
phase of a compile request into the ring with the function's name, on the
compiling thread, under the program span open there: ``compile.trace``
(Python tracing to a jaxpr), ``compile.lower`` (jaxpr to MLIR, the Pallas
kernels' Mosaic lowering with it) and ``compile.backend`` (``from_cache``
1: the persistent cache answered and the record is the read, the
deserialising and the load; 0: XLA and Mosaic compiled). ``Trainer.train``
opens ``train.startup`` at its entry, with ``train.first_batch`` and
``train.init_state`` inside it.

``setup_trace_s`` / ``setup_lower_s``: seconds of the ``compile.trace`` /
``compile.lower`` records that END before the window, each less the
``compile.backend`` records that lie inside it on its thread (an eager
operation compiled while a function is traced), so that the four sums count
no second twice. ``setup_executable_load_s`` / ``setup_backend_compile_s``:
seconds of the ``compile.backend`` records before the window with
``from_cache`` 1 / 0. ``setup_train_to_first_step_s``: from the start of
``train.startup`` to the first ``train.step_done`` after it (the moment the
first step finished on the device): the in-program twin of the harness's
"train() to the end of the first step".

The window is ``program_trace.find_window``'s. On earlier lines: the sums
split by the span each record lies under (``none``: code outside the
program, here the harness's references), the five longest records, how much
of the first ``train.step`` its records cover, and every ``compile.*``
record that ends INSIDE the window with its function and parent: the name
behind a non-zero ``window_compiles``. A ring that dropped records, a window
that cannot be found and a program that writes no such records (the parent
of PR 36) read ``None``, and a line says which.
"""

from benchmark.harness.common import log
from benchmark.metrics import program_trace

_PHASES = ('compile.trace', 'compile.lower', 'compile.backend')
_SUMS = ('setup_trace_s', 'setup_lower_s', 'setup_executable_load_s',
         'setup_backend_compile_s')
_FIRST_STEP = 'setup_train_to_first_step_s'


def _seconds(record):
  return (record.end_ns - record.start_ns) / 1e9


def _kind(record):
  """Which of the four sums a ``compile.*`` record belongs to."""
  if record.name == 'compile.backend':
    return _SUMS[2] if record.attrs.get('from_cache') else _SUMS[3]
  return _SUMS[0] if record.name == 'compile.trace' else _SUMS[1]


def _label(record, by_id):
  """The span a record lies under, with its step where it has one."""
  parent = by_id.get(record.parent)
  if parent is None:
    return 'none' if record.parent == 0 else 'a span not in the ring'
  if 'step' in parent.attrs:
    return '{} of step {}'.format(parent.name, parent.attrs['step'])
  return parent.name


def self_seconds(compiles):
  """{record id: seconds}: a trace or lowering less the backend compiles
  that lie inside it on its thread; a backend compile whole."""
  backends = [r for r in compiles if r.name == 'compile.backend']
  out = {}
  for r in compiles:
    out[r.id] = _seconds(r)
    if r.name != 'compile.backend':
      out[r.id] -= sum(_seconds(b) for b in backends if b.thread == r.thread
                       and r.start_ns <= b.start_ns and b.end_ns <= r.end_ns)
  return out


def reduce_startup(records, start_ns, end_ns, requests=None):
  """{metric name: seconds} from the ring's ``records`` and the window
  [start_ns, end_ns]; logs the split. ``requests`` is the harness's count of
  compile requests before the window, for the line that compares."""
  compiles = [r for r in records if r.name in _PHASES]
  startups = [r for r in records
              if r.name == 'train.startup' and r.start_ns < start_ns]
  if not compiles and not startups:
    log('start-up: the ring holds no compile.* record and no train.startup '
        'span (a program from before they were written); its metrics are '
        'left out')
    return {}
  by_id = {r.id: r for r in records}
  seconds = self_seconds(compiles)
  before = [r for r in compiles if r.end_ns <= start_ns]
  out = dict.fromkeys(_SUMS, 0.0)
  split = {}
  for r in before:
    out[_kind(r)] += seconds[r.id]
    row = split.setdefault(_label(r, by_id), dict.fromkeys(_SUMS, 0.0))
    row[_kind(r)] += seconds[r.id]
  backends = [r for r in before if r.name == 'compile.backend']
  log('start-up: {} compile.* records end before the window ({} trace, {} '
      'lower, {} backend of which {} from the cache{}): trace {:.3f} s, '
      'lower {:.3f} s, executable load {:.3f} s, backend compile {:.3f} s',
      len(before), sum(r.name == 'compile.trace' for r in before),
      sum(r.name == 'compile.lower' for r in before), len(backends),
      sum(bool(r.attrs.get('from_cache')) for r in backends),
      '' if requests is None else '; the harness counted {:.0f} '
      'requests'.format(requests), *(out[name] for name in _SUMS))
  log('start-up: by the span they lie under, trace / lower / load / compile '
      'seconds: {}', '; '.join(
          '{} {}'.format(label, ' / '.join(
              '{:.3f}'.format(row[name]) for name in _SUMS))
          for label, row in sorted(split.items(),
                                   key=lambda item: -sum(item[1].values()))))
  log('start-up: the longest records: {}', '; '.join(
      '{} {!r} {:.3f} s under {}{}'.format(
          r.name, r.attrs.get('fun'), seconds[r.id], _label(r, by_id),
          ', from the cache (read {:.0f} ms)'.format(
              r.attrs.get('cache_read_ms', 0.0))
          if r.attrs.get('from_cache') else '')
      for r in sorted(before, key=lambda r: -seconds[r.id])[:5]))
  for r in compiles:
    if start_ns < r.end_ns <= end_ns:
      log('start-up: INSIDE the window: {} {!r} {:.1f} ms under {}', r.name,
          r.attrs.get('fun'), _seconds(r) * 1e3, _label(r, by_id))

  if startups:
    startup = max(startups, key=lambda r: r.start_ns)
    done = sorted((r for r in records if r.name == 'train.step_done'
                   and r.end_ns >= startup.start_ns),
                  key=lambda r: r.end_ns)
    steps = sorted((r for r in records if r.name == 'train.step'
                    and r.thread == startup.thread
                    and r.start_ns >= startup.end_ns),
                   key=lambda r: r.start_ns)
    if done:
      out[_FIRST_STEP] = (done[0].end_ns - startup.start_ns) / 1e9
      first = steps[0] if steps else None
      covered = sum(seconds[r.id] for r in compiles
                    if first is not None and r.parent == first.id)
      log('start-up: train.startup to the first train.step_done {:.3f} s: '
          'train.startup {:.3f} s; the first train.step {:.3f} s, its '
          'compile.* records {:.3f} s of it; from its end to the step done '
          'on the device {:.3f} s', out[_FIRST_STEP], _seconds(startup),
          _seconds(first) if first else 0.0, covered,
          (done[0].end_ns - first.end_ns) / 1e9 if first else 0.0)
  return out


def _reduced(obs):
  if 'startup' not in obs:
    obs['startup'] = _reduce(obs)
  return obs['startup']


def _reduce(obs):
  counters, ring = obs.get('counters'), program_trace.read_ring()
  if ring is None or not counters or not obs.get('window_s') or \
      'span/train.step/count' not in counters['after']:
    log('start-up: no span ring or no counters to find the window by; its '
        'metrics are left out')
    return {}
  records, dropped = ring
  if dropped:
    log('start-up: the span ring dropped {} records; its metrics are left '
        'out', dropped)
    return {}
  found, why = program_trace.find_window(
      records, int(counters['before']['span/train.step/count']),
      int(counters['after']['span/train.step/count']), obs['window_s'])
  if found is None:
    log('start-up: {}; its metrics are left out', why)
    return {}
  return reduce_startup(records, found[0], found[1],
                        counters['before'].get('jax/compiles'))


def _reader(name):
  return lambda obs: _reduced(obs).get(name)


METRICS = {name: _reader(name) for name in _SUMS + (_FIRST_STEP,)}
