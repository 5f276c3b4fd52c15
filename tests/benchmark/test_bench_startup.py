"""The readers of ``benchmark/metrics/startup.py`` on hand-made ring records,
the tiny cell printing all five metrics, and the manifests that list them."""

import collections
import importlib.util
import os

import pytest

import bench_helpers as helpers
from benchmark.harness import cells

TINY_TRACE = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                          'BENCHMARK_program_trace.json')
TINY_STARTUP = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                            'BENCHMARK_startup.json')
NEW = ('setup_trace_s', 'setup_lower_s', 'setup_executable_load_s',
       'setup_backend_compile_s', 'setup_train_to_first_step_s')
Record = collections.namedtuple(
    'Record', 'id parent name thread start_ns end_ns attrs')
MS = 1_000_000


def _module():
  spec = importlib.util.spec_from_file_location(
      'startup_under_test', os.path.join(cells.METRICS_DIR, 'startup.py'))
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


class Run:
  """A hand-made run on one training thread, in ms: a reference compiled by
  code outside the program; ``train.init_state`` with its program loaded
  from the cache; ``train.startup``; a first ``train.step`` that traces,
  lowers and compiles, with an eager operation compiled inside its trace;
  then steps of 10 ms, every one followed by its ``data.next``."""

  def __init__(self, steps=40, compile_in_step=None, program_records=True):
    self.records, self.ids = [], iter(range(1, 10**6))
    add = self._add
    if program_records:
      # Outside the program (parent 0): 300 traced, 200 lowered, 1,000
      # compiled.
      add('compile.trace', 0, 300, fun='reference', inner=40)
      add('compile.lower', 300, 500, fun='jit(reference)', inner=0)
      add('compile.backend', 500, 1500, fun='jit(reference)', from_cache=0,
          cache_read_ms=0.0)
      init_state = next(self.ids)
      add('compile.trace', 1600, 1700, init_state, fun='init', inner=12)
      add('compile.lower', 1700, 1750, init_state, fun='jit(init)', inner=3)
      add('compile.backend', 1750, 2150, init_state, fun='jit(init)',
          from_cache=1, cache_read_ms=120.0)
      self._add('train.init_state', 1550, 2200, id_=init_state, restored=0)
      self.startup = add('train.startup', 2300, 2400, start_step=0)
    step_start = 2400
    step_end = 2400 + 2000
    first_step = next(self.ids)
    if program_records:
      # The trace is 900 long and holds an eager operation's compile of 100
      # (a record of its own): 800 of its own.
      add('compile.trace', 2410, 3310, first_step, fun='step', inner=300)
      add('compile.backend', 2600, 2700, first_step, fun='jit(iota)',
          from_cache=1, cache_read_ms=5.0)
      add('compile.lower', 3310, 3610, first_step, fun='jit(step)', inner=9)
      add('compile.backend', 3610, 4310, first_step, fun='jit(step)',
          from_cache=1, cache_read_ms=300.0)
    now = step_start
    self.next_starts = []
    for step in range(1, steps + 1):
      end = step_end if step == 1 else now + 10
      id_ = first_step if step == 1 else next(self.ids)
      if step == compile_in_step:
        add('compile.trace', now + 1, now + 3, id_, fun='step', inner=2)
        add('compile.backend', now + 3, now + 8, id_, fun='jit(step)',
            from_cache=0, cache_read_ms=0.0)
      self._add('train.step', now, end, id_=id_, step=step)
      self.next_starts.append(end)
      add('data.next', end, end + 1, step=step + 1)
      self._add('train.step_done', end + 50, end + 50,
                thread='t2r-step-watch', step=step, steps_covered=1)
      now = end + 1

  def _add(self, name, start, end, parent=0, id_=None, thread='MainThread',
           **attrs):
    record = Record(id_ or next(self.ids), parent, name, thread, start * MS,
                    end * MS, attrs)
    self.records.append(record)
    return record

  def observations(self, before=10, after=30, requests=5.0):
    window_s = (self.next_starts[after - 1] -
                self.next_starts[before - 1]) / 1e3
    return {'window_s': window_s, 'counters': {
        'before': {'span/train.step/count': float(before),
                   'jax/compiles': requests},
        'after': {'span/train.step/count': float(after)}}}


def _read(module, monkeypatch, run, obs, dropped=0):
  monkeypatch.setattr(module.program_trace, 'read_ring',
                      lambda: (run.records, dropped))
  return {name: module.METRICS[name](obs) for name in NEW}


def test_the_four_sums_and_the_time_to_the_first_step(monkeypatch, capsys):
  module = _module()
  run = Run()
  got = _read(module, monkeypatch, run, run.observations())
  assert got['setup_trace_s'] == pytest.approx(0.3 + 0.1 + 0.8)
  assert got['setup_lower_s'] == pytest.approx(0.2 + 0.05 + 0.3)
  assert got['setup_executable_load_s'] == pytest.approx(0.4 + 0.1 + 0.7)
  assert got['setup_backend_compile_s'] == pytest.approx(1.0)
  # train.startup opens at 2,300; step 1 is done on the device at 4,450.
  assert got['setup_train_to_first_step_s'] == pytest.approx(2.15)
  said = capsys.readouterr().out
  assert '10 compile.* records end before the window (3 trace, 3 lower, 4 ' \
      'backend of which 3 from the cache; the harness counted 5 requests)' \
      in said
  # Split by the span each lies under; the rows add up to the sums.
  split = next(line for line in said.splitlines()
               if 'by the span they lie under' in line)
  assert 'train.step of step 1 0.800 / 0.300 / 0.800 / 0.000' in split
  assert 'none 0.300 / 0.200 / 0.000 / 1.000' in split
  assert 'train.init_state 0.100 / 0.050 / 0.400 / 0.000' in split
  longest = next(line for line in said.splitlines()
                 if 'the longest records' in line)
  assert longest.index("'jit(reference)' 1.000 s under none") < \
      longest.index("'step' 0.800 s under train.step of step 1")
  assert 'from the cache (read 300 ms)' in longest
  assert 'the first train.step 2.000 s, its compile.* records 1.900 s' in said
  assert 'INSIDE the window' not in said


def test_the_split_by_parent_adds_up_to_the_sums(monkeypatch, capsys):
  module = _module()
  run = Run()
  got = _read(module, monkeypatch, run, run.observations())
  split = next(line for line in capsys.readouterr().out.splitlines()
               if 'by the span they lie under' in line)
  rows = [[float(x) for x in row.rsplit(' ', 7)[-7::2]]
          for row in split.split(': ', 1)[1].split('; ')]
  assert len(rows) == 3
  for column, name in enumerate(NEW[:4]):
    assert sum(row[column] for row in rows) == pytest.approx(got[name])


def test_a_compile_inside_the_window_is_named_on_a_line(monkeypatch, capsys):
  module = _module()
  run = Run(compile_in_step=20)
  got = _read(module, monkeypatch, run, run.observations())
  said = capsys.readouterr().out
  assert "INSIDE the window: compile.backend 'jit(step)' 5.0 ms under " \
      'train.step of step 20' in said
  assert "INSIDE the window: compile.trace 'step' 2.0 ms" in said
  # It ends after the window opened: not part of the set-up's sums.
  assert got['setup_backend_compile_s'] == pytest.approx(1.0)


@pytest.mark.parametrize('why', ['ring_dropped', 'a_program_without_records',
                                 'no_ring', 'window_disagrees',
                                 'no_counters'])
def test_what_cannot_be_read_reads_none_and_a_line_says_why(
    monkeypatch, capsys, why):
  module = _module()
  run = Run(program_records=why != 'a_program_without_records')
  obs = run.observations()
  dropped = 0
  if why == 'ring_dropped':
    dropped = 7
  elif why == 'window_disagrees':
    obs['window_s'] += 0.021
  elif why == 'no_counters':
    obs['counters'] = None
  if why == 'no_ring':
    monkeypatch.setattr(module.program_trace, 'read_ring', lambda: None)
    got = {name: module.METRICS[name](obs) for name in NEW}
  else:
    got = _read(module, monkeypatch, run, obs, dropped=dropped)
  assert got == dict.fromkeys(NEW)
  said = capsys.readouterr().out
  assert said.count('start-up:') == 1 and 'left out' in said
  assert {'ring_dropped': 'dropped 7 records',
          'a_program_without_records': 'no compile.* record and no '
                                       'train.startup',
          'no_ring': 'no span ring or no counters',
          'window_disagrees': 'the harness timed',
          'no_counters': 'no span ring or no counters'}[why] in said


def test_the_window_is_program_traces_not_a_copy():
  module = _module()
  from benchmark.metrics import program_trace

  assert module.program_trace is program_trace
  assert not hasattr(module, 'find_window')


def test_the_tiny_cell_traced_prints_all_five(tmp_path):
  result = helpers.run_cell(tmp_path, 'tiny_train', trace=1,
                            manifest=TINY_STARTUP)
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  line = helpers.last_json_line(result.stdout)
  assert line['correct'] is True
  assert set(NEW) <= set(line['metrics'])
  metrics = {name: line['metrics'][name]['value'] for name in NEW}
  assert {line['metrics'][name]['unit'] for name in NEW} == {'s'}
  assert metrics['setup_backend_compile_s'] + \
      metrics['setup_executable_load_s'] > 0
  assert metrics['setup_trace_s'] > 0 and metrics['setup_lower_s'] > 0
  assert metrics['setup_train_to_first_step_s'] > 0
  said = result.stdout
  # One backend record a compile request, by the harness's own count.
  requests = int(line['metrics']['compile_requests']['value'])
  assert '{} backend of which'.format(requests) in said
  assert 'the harness counted {} requests'.format(requests) in said
  assert 'under train.step of step 1' in said
  assert 'under train.init_state' in said
  assert 'INSIDE the window' not in said
  # The harness's stamp for the same interval, from outside.
  stamp = float(said.split('train() to the end of the first step (compile '
                           'or cache load, run) ')[1].split(';')[0])
  assert metrics['setup_train_to_first_step_s'] == pytest.approx(stamp,
                                                                 abs=1.0)


@pytest.mark.parametrize('path', [helpers.REAL, TINY_STARTUP],
                         ids=['real', 'tiny_startup'])
def test_the_manifests_hold_the_five_by_name_with_just_the_contracts_keys(
    path):
  manifest = cells.load_json(path)
  readers = cells.metric_readers()
  entries = {m['name']: m for m in manifest['per_layer']}
  assert len(entries) == len(manifest['per_layer'])
  for name in NEW:
    entry = entries[name]
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves'}
    assert (entry['unit'], entry['better'], entry['source'],
            entry['moves']) == ('s', 'lower', 'program_span', 'setup_s')
    assert name in readers
  # The layers the accepted entries already name.
  layers = {m['layer'] for m in manifest['per_layer']
            if m['name'] not in NEW}
  assert {entries[name]['layer'] for name in NEW} <= layers
  assert entries['setup_train_to_first_step_s']['layer'] == 'train step'
  for cell in manifest['workloads']:
    assert set(NEW) <= set(
        cells.Cell(path, cell['name']).metric_names('per_layer'))


def test_the_tiny_manifest_of_its_own_differs_by_the_new_entries_only():
  traced, startup = (cells.load_json(p) for p in (TINY_TRACE, TINY_STARTUP))
  for key in ('command', 'paths', 'run_seconds', 'configs', 'workloads',
              'end_to_end'):
    assert traced[key] == startup[key]
  assert startup['per_layer'][:-5] == traced['per_layer']
  assert [m['name'] for m in startup['per_layer'][-5:]] == list(NEW)
