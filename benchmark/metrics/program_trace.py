"""Layers "input: read, decode, batch", "train step" and "device", read from
the program's own span ring (``tensor2robot_tpu/observability/spans.py``):
records of every thread on one clock, kept in memory over the whole timed
window with no profiler.

What is read (the thread it is recorded on in brackets):

``data_next_wait_share``: seconds inside ``data.next`` [training thread] over
the window: the half of ``input_wait_share`` that goes with an idle device.
``producer_loader_wait_share`` / ``producer_pack_share`` /
``producer_backpressure_share``: seconds inside ``data.ring_wait`` (the C++
loader has no whole batch ready), ``data.pack`` (slices, the owned copy, spec
validation) and ``data.handoff_wait`` (the prefetch queue is full: input is
hidden) [``t2r-prefetch``] over the window; together they cover that thread.
``reader_busy_share`` / ``decode_busy_share``: the C++ loader's own counters,
as the per-batch deltas of the ``data.loader_stats`` events [``t2r-prefetch``]:
``reader_busy_s`` over the window (near 1: the one reader thread gates) and
``worker_busy_s`` over workers x window.
``train_loop_overhead_share``: time of ``train.iteration`` [training thread]
that none of its ``data.next``, ``data.put_batch``, ``train.step`` and
``train.hooks`` children covers, over the window: the loop's own bookkeeping.
Hooks are left out because they are the caller's code: here the benchmark's
own window hook, whose closing sync waits about six steps for the device.
``step_done_interval_ms``: median time between consecutive ``train.step_done``
events [``t2r-step-watch``: the moment a step finished on the device], per
step an event covers. ``host_lead_steps``: median, at each ``train.step`` end
(step dispatched), of steps dispatched and not yet done.
``device_starved_share``: sum over the window's steps of
``max(0, dispatched(n) - done(n-1))``, the time the device had nothing to
run, over the window.

The window is found in the ring itself: ``observations['counters']`` holds
``span/train.step/count`` before and after the timed window, a span is
appended to the ring in the same ``__exit__`` that bumps that count, and the
hook that takes each snapshot returns straight into the loop's next
``data.next``. So the window runs from the start of the ``data.next`` that
follows ``train.step`` number ``before`` to the start of the one that follows
number ``after``. That length has to agree with ``window_s`` within 20 ms and
the ring must have dropped nothing; otherwise every reader returns ``None``
and says why on an earlier line: a wrong window is never read silently. A
program without the ring (the parent of the PR that added it) reads ``None``.
"""

import statistics

from benchmark.harness.common import log

WINDOW_TOLERANCE_S = 0.020
_CHILDREN = ('data.next', 'data.put_batch', 'train.step', 'train.hooks')
_PRODUCER = {'producer_loader_wait_share': 'data.ring_wait',
             'producer_pack_share': 'data.pack',
             'producer_backpressure_share': 'data.handoff_wait'}


def read_ring():
  """(records, dropped) of the program's ring, or None where it has none."""
  try:
    from tensor2robot_tpu.observability import spans
  except ImportError:
    return None
  if not hasattr(spans, 'records'):
    return None
  return spans.records(), spans.dropped()


def find_window(records, before, after, window_s):
  """(start_ns, end_ns, training thread) or (None, why)."""
  steps = [r for r in records if r.name == 'train.step']
  if not 0 < before < after <= len(steps):
    return None, ('the ring holds {} train.step records, the window runs '
                  'from number {} to {}'.format(len(steps), before, after))
  thread = steps[before - 1].thread
  nexts = sorted((r for r in records
                  if r.name == 'data.next' and r.thread == thread),
                 key=lambda r: r.start_ns)
  edges = []
  for step in (steps[before - 1], steps[after - 1]):
    following = [r.start_ns for r in nexts if r.start_ns >= step.end_ns]
    if not following:
      return None, 'no data.next follows train.step of step {}'.format(
          step.attrs.get('step'))
    edges.append(following[0])
  length_s = (edges[1] - edges[0]) / 1e9
  if abs(length_s - window_s) > WINDOW_TOLERANCE_S:
    return None, ('the window found in the ring is {:.4f} s long, the '
                  'harness timed {:.4f} s'.format(length_s, window_s))
  return (edges[0], edges[1], thread), None


def reduce_window(records, start_ns, end_ns, thread):
  """{metric name: value} over [start_ns, end_ns]; a metric with nothing to
  read is left out."""
  window_ns = float(end_ns - start_ns)

  def inside(name, on_thread=None):
    """Seconds of the named spans that lie in the window."""
    total = 0
    for r in records:
      if r.name == name and (on_thread is None or r.thread == on_thread):
        total += max(0, min(r.end_ns, end_ns) - max(r.start_ns, start_ns))
    return total

  out = {'data_next_wait_share': inside('data.next', thread) / window_ns}
  for metric, name in _PRODUCER.items():
    if any(r.name == name for r in records):
      out[metric] = inside(name) / window_ns
  iterations = inside('train.iteration', thread)
  if iterations:
    covered = sum(inside(name, thread) for name in _CHILDREN)
    out['train_loop_overhead_share'] = (iterations - covered) / window_ns

  stats = [r.attrs for r in records if r.name == 'data.loader_stats'
           and start_ns <= r.end_ns <= end_ns]
  if stats:
    out['reader_busy_share'] = (
        sum(a['reader_busy_s'] for a in stats) * 1e9 / window_ns)
    workers = max(a['workers'] for a in stats)
    if workers:
      out['decode_busy_share'] = (sum(a['worker_busy_s'] for a in stats) *
                                  1e9 / (workers * window_ns))

  done = sorted((r for r in records if r.name == 'train.step_done'),
                key=lambda r: r.end_ns)
  in_window = [r for r in done if start_ns <= r.end_ns <= end_ns]
  intervals = [(b.end_ns - a.end_ns) / 1e6 / b.attrs['steps_covered']
               for a, b in zip(in_window[:-1], in_window[1:])]
  if intervals:
    out['step_done_interval_ms'] = statistics.median(intervals)
  dispatched = [r for r in records if r.name == 'train.step' and
                r.thread == thread and start_ns <= r.end_ns <= end_ns]
  if done and dispatched:
    done_ns = {r.attrs['step']: r.end_ns for r in done}
    leads, starved, cursor, newest_done = [], 0, 0, 0
    for step in sorted(dispatched, key=lambda r: r.end_ns):
      while cursor < len(done) and done[cursor].end_ns <= step.end_ns:
        newest_done = max(newest_done, done[cursor].attrs['step'])
        cursor += 1
      n = step.attrs['step']
      if newest_done:
        leads.append(n - newest_done)
      if n - 1 in done_ns:
        starved += max(0, step.end_ns - max(done_ns[n - 1], start_ns))
    if leads:
      out['host_lead_steps'] = statistics.median(leads)
    out['device_starved_share'] = starved / window_ns
  return out


def _reduced(obs):
  """The window's numbers, reduced once per run and kept in ``obs``."""
  if 'program_trace' not in obs:
    obs['program_trace'] = _reduce(obs)
  return obs['program_trace']


def _reduce(obs):
  counters, ring = obs.get('counters'), read_ring()
  if ring is None or not counters or not obs.get('window_s') or \
      'span/train.step/count' not in counters['after']:
    return {}
  records, dropped = ring
  if dropped:
    log('program trace: the span ring dropped {} records; its metrics are '
        'left out', dropped)
    return {}
  found, why = find_window(
      records, int(counters['before']['span/train.step/count']),
      int(counters['after']['span/train.step/count']), obs['window_s'])
  if found is None:
    log('program trace: {}; its metrics are left out', why)
    return {}
  start_ns, end_ns, thread = found
  log('program trace: window of {:.4f} s found in the ring ({:+.1f} ms '
      'against the harness), {} records, training thread {!r}',
      (end_ns - start_ns) / 1e9,
      ((end_ns - start_ns) / 1e9 - obs['window_s']) * 1e3, len(records),
      thread)
  return reduce_window(records, start_ns, end_ns, thread)


def _reader(name):
  return lambda obs: _reduced(obs).get(name)


METRICS = {name: _reader(name) for name in (
    'data_next_wait_share', 'producer_loader_wait_share',
    'producer_pack_share', 'producer_backpressure_share',
    'reader_busy_share', 'decode_busy_share', 'train_loop_overhead_share',
    'step_done_interval_ms', 'host_lead_steps', 'device_starved_share')}
