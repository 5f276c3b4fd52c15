"""The readers of ``benchmark/metrics/program_trace.py`` on hand-made ring
records, the tiny cell printing all ten metrics, and the manifests that
list them."""

import collections
import importlib.util
import os

import pytest

import bench_helpers as helpers
from benchmark.harness import cells

TINY_TRACE = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                          'BENCHMARK_program_trace.json')
NEW = ('data_next_wait_share', 'producer_loader_wait_share',
       'producer_pack_share', 'producer_backpressure_share',
       'reader_busy_share', 'decode_busy_share', 'train_loop_overhead_share',
       'step_done_interval_ms', 'host_lead_steps', 'device_starved_share')
Record = collections.namedtuple(
    'Record', 'id parent name thread start_ns end_ns attrs')
MS = 1_000_000


def _module():
  spec = importlib.util.spec_from_file_location(
      'program_trace_under_test',
      os.path.join(cells.METRICS_DIR, 'program_trace.py'))
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


class Loop:
  """A hand-made run: one training thread, one producer, one watcher. Each
  step is ``next`` + ``put`` + ``dispatch`` + ``hooks`` on the training
  thread; the device takes ``device`` ms a step and runs steps in order."""

  def __init__(self, next_ms, put_ms, dispatch_ms, device_ms, steps,
               producer, lead=0):
    self.records, self.ids = [], iter(range(1, 10**6))
    now = 0
    device_free = 0
    done = {}
    # An iteration: put, dispatch, hooks, next (the next step's batch).
    for step in range(1, steps + 1):
      # The host cannot lead the device by more than ``lead`` steps: the
      # put blocks on the device's queue.
      if lead and step - lead - 1 in done:
        now = max(now, done[step - lead - 1])
      start = now
      put_end = start + put_ms * MS
      step_end = put_end + dispatch_ms * MS
      begin = max(step_end, device_free)
      device_free = begin + device_ms * MS
      done[step] = device_free
      hooks_end = step_end + MS // 10
      next_end = hooks_end + next_ms * MS
      iteration = next(self.ids)
      for name, a, b, attr in (
          ('data.put_batch', start, put_end, step),
          ('train.step', put_end, step_end, step),
          ('train.hooks', step_end, hooks_end, step),
          ('data.next', hooks_end, next_end, step + 1)):
        self.records.append(Record(next(self.ids), iteration, name,
                                   'MainThread', a, b, {'step': attr}))
      self.records.append(Record(iteration, 0, 'train.iteration',
                                 'MainThread', start, next_end,
                                 {'step': step}))
      now = next_end
    self.end = now
    for step, at in done.items():
      self.records.append(Record(next(self.ids), 0, 'train.step_done',
                                 't2r-step-watch', at, at,
                                 {'step': step, 'steps_covered': 1}))
    # The producer thread: (ring_wait, pack, handoff_wait) ms per batch,
    # with the loader's counters published after each pack.
    at = 0
    batch = 0
    while at < self.end:
      for name, ms in zip(('data.ring_wait', 'data.pack',
                           'data.handoff_wait'), producer['spans']):
        self.records.append(Record(next(self.ids), 0, name, 't2r-prefetch',
                                   at, at + ms * MS, {'batch': batch}))
        at += ms * MS
        if name == 'data.pack':
          self.records.append(Record(
              next(self.ids), 0, 'data.loader_stats', 't2r-prefetch', at, at,
              dict(producer['stats'], workers=4)))
      batch += 1

  def observations(self, before, after):
    """As the harness hands them over: the window runs from the data.next
    after train.step number ``before`` to the one after number ``after``."""
    nexts = sorted((r for r in self.records if r.name == 'data.next'),
                   key=lambda r: r.start_ns)
    window_s = (nexts[after - 1].start_ns - nexts[before - 1].start_ns) / 1e9
    return {'window_s': window_s, 'counters': {
        'before': {'span/train.step/count': float(before)},
        'after': {'span/train.step/count': float(after)}}}


def _read(module, monkeypatch, loop, obs, dropped=0):
  monkeypatch.setattr(module, 'read_ring', lambda: (loop.records, dropped))
  return {name: module.METRICS[name](obs) for name in NEW}


def test_a_device_bound_loop(monkeypatch):
  """The step takes 100 ms on the device; the host needs 30 ms a step and
  is held to three steps beyond the one the device runs; the producer makes
  a batch in 40 ms and waits."""
  module = _module()
  loop = Loop(next_ms=1, put_ms=19.9, dispatch_ms=9, device_ms=100, steps=60,
              lead=3, producer={
                  'spans': (25, 15, 60),
                  'stats': {'reader_busy_s': 0.010, 'worker_busy_s': 0.080}})
  obs = loop.observations(10, 50)
  got = _read(module, monkeypatch, loop, obs)
  assert set(got) == set(NEW) and None not in got.values()
  assert obs['window_s'] == pytest.approx(4.0, rel=0.01)
  assert got['step_done_interval_ms'] == pytest.approx(100.0)
  assert got['host_lead_steps'] == 4  # three queued and the one running
  assert got['device_starved_share'] == 0.0
  assert got['data_next_wait_share'] == pytest.approx(0.01, rel=0.02)
  assert got['producer_loader_wait_share'] == pytest.approx(0.25, rel=0.02)
  assert got['producer_pack_share'] == pytest.approx(0.15, rel=0.02)
  assert got['producer_backpressure_share'] == pytest.approx(0.60, rel=0.02)
  assert got['reader_busy_share'] == pytest.approx(0.10, rel=0.03)
  assert got['decode_busy_share'] == pytest.approx(0.20, rel=0.03)
  # The put waits for the device's queue, which is no overhead of the loop.
  assert 0 <= got['train_loop_overhead_share'] < 1e-9


def test_an_input_bound_loop(monkeypatch):
  """A batch takes 90 ms to come, the device 40 ms a step: it starves."""
  module = _module()
  loop = Loop(next_ms=80, put_ms=6, dispatch_ms=3.9, device_ms=40, steps=60,
              producer={
                  'spans': (70, 19, 1),
                  'stats': {'reader_busy_s': 0.085, 'worker_busy_s': 0.120}})
  obs = loop.observations(10, 50)
  got = _read(module, monkeypatch, loop, obs)
  assert obs['window_s'] == pytest.approx(3.6, rel=0.01)
  assert got['step_done_interval_ms'] == pytest.approx(90.0)
  assert got['host_lead_steps'] == 1  # the one just dispatched
  # Every step the device waits 90 - 40 = 50 ms for the next dispatch.
  assert got['device_starved_share'] == pytest.approx(50 / 90, rel=0.02)
  assert got['data_next_wait_share'] == pytest.approx(80 / 90, rel=0.02)
  assert got['producer_loader_wait_share'] == pytest.approx(70 / 90, rel=0.02)
  assert got['producer_backpressure_share'] == pytest.approx(1 / 90, rel=0.1)
  assert got['reader_busy_share'] == pytest.approx(0.085 / 0.09, rel=0.03)
  assert got['decode_busy_share'] == pytest.approx(0.12 / 0.36, rel=0.03)
  assert 0 <= got['train_loop_overhead_share'] < 1e-9


def test_loop_overhead_is_what_no_child_covers(monkeypatch):
  module = _module()
  loop = Loop(next_ms=1, put_ms=5, dispatch_ms=3.9, device_ms=5, steps=30,
              producer={'spans': (1, 1, 8), 'stats': {
                  'reader_busy_s': 0.001, 'worker_busy_s': 0.001}})
  # 5 ms of every iteration under no child: a log window, a checkpoint.
  for i, r in enumerate(loop.records):
    if r.name == 'data.next':
      loop.records[i] = r._replace(start_ns=r.start_ns + 5 * MS,
                                   end_ns=r.end_ns + 5 * MS)
    if r.name == 'train.iteration':
      loop.records[i] = r._replace(end_ns=r.end_ns + 5 * MS)
  got = _read(module, monkeypatch, loop, loop.observations(5, 25))
  assert got['train_loop_overhead_share'] == pytest.approx(5 / 10, rel=0.1)


@pytest.mark.parametrize('why', ['window_disagrees', 'ring_dropped',
                                 'too_few_steps', 'no_ring', 'no_counters'])
def test_a_window_that_cannot_be_trusted_reads_none(monkeypatch, capsys, why):
  module = _module()
  loop = Loop(next_ms=1, put_ms=19.9, dispatch_ms=9, device_ms=100, steps=60,
              lead=3, producer={'spans': (25, 15, 60), 'stats': {
                  'reader_busy_s': 0.01, 'worker_busy_s': 0.08}})
  obs = loop.observations(10, 50)
  dropped = 0
  if why == 'window_disagrees':
    obs['window_s'] += 0.021
  elif why == 'ring_dropped':
    dropped = 3
  elif why == 'too_few_steps':
    obs['counters']['after']['span/train.step/count'] = 61.0
  elif why == 'no_counters':
    obs['counters'] = None
  if why == 'no_ring':
    monkeypatch.setattr(module, 'read_ring', lambda: None)
    got = {name: module.METRICS[name](obs) for name in NEW}
  else:
    got = _read(module, monkeypatch, loop, obs, dropped=dropped)
  assert got == dict.fromkeys(NEW)
  said = capsys.readouterr().out
  if why in ('window_disagrees', 'ring_dropped', 'too_few_steps'):
    assert said.count('program trace:') == 1 and 'left out' in said
  # Within the tolerance the same window is read.
  if why == 'window_disagrees':
    obs = loop.observations(10, 50)
    obs['window_s'] += 0.019
    assert None not in _read(module, monkeypatch, loop, obs).values()


def test_the_reader_finds_the_programs_ring():
  from tensor2robot_tpu.observability import spans

  module = _module()
  spans.event('bench.test')
  records, dropped = module.read_ring()
  assert records[-1].name == 'bench.test' and dropped == spans.dropped()


def test_the_tiny_cell_traced_prints_all_ten(tmp_path):
  # Long enough for several completions inside the window on a loaded
  # machine: the interval between them needs two.
  result = helpers.run_cell(tmp_path, 'tiny_train', trace=1, seconds=6,
                            manifest=TINY_TRACE)
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  line = helpers.last_json_line(result.stdout)
  assert line['correct'] is True
  assert set(NEW) <= set(line['metrics'])
  metrics = {name: line['metrics'][name]['value'] for name in NEW}
  assert 'program trace: window of' in result.stdout
  assert metrics['step_done_interval_ms'] > 0
  assert metrics['host_lead_steps'] >= 1
  for name in NEW:
    if name.endswith('_share'):
      assert 0 <= metrics[name] <= 1.02, name
  producer = sum(metrics[name] for name in (
      'producer_loader_wait_share', 'producer_pack_share',
      'producer_backpressure_share'))
  assert 0.9 <= producer <= 1.02
  assert metrics['data_next_wait_share'] <= \
      line['metrics']['input_wait_share']['value'] + 1e-9
  units = {name: line['metrics'][name]['unit'] for name in NEW}
  assert units['step_done_interval_ms'] == 'ms'
  assert units['host_lead_steps'] == 'count'


@pytest.mark.parametrize('path', [helpers.REAL, TINY_TRACE],
                         ids=['real', 'tiny_program_trace'])
def test_the_manifests_list_the_ten_with_just_the_contracts_keys(path):
  manifest = cells.load_json(path)
  readers = cells.metric_readers()
  entries = {m['name']: m for m in manifest['per_layer']}
  assert [m['name'] for m in manifest['per_layer']][-10:] == list(NEW)
  for name in NEW:
    entry = entries[name]
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves'}
    assert entry['source'] == 'program_span'
    assert entry['moves'] == 'train_examples_per_s_per_chip'
    assert name in readers
  for cell in manifest['workloads']:
    assert set(NEW) <= set(
        cells.Cell(path, cell['name']).metric_names('per_layer'))


def test_the_tiny_manifest_of_its_own_differs_by_the_new_entries_only():
  tiny, traced = cells.load_json(helpers.TINY), cells.load_json(TINY_TRACE)
  for key in ('command', 'paths', 'run_seconds', 'configs', 'workloads',
              'end_to_end'):
    assert tiny[key] == traced[key]
  assert traced['per_layer'][:-10] == tiny['per_layer']
