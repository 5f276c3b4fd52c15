"""The trainer loop in the span ring: every second of the training thread
under a name, the step-completion watcher, the profiler's clock marker, and
idle gaps named from ring records."""

import shutil
import threading
import time

import jax
import pytest

from tensor2robot_tpu import observability as obs
from tensor2robot_tpu.observability import forensics as forensics_lib
from tensor2robot_tpu.observability import spans
from tensor2robot_tpu.observability.autoprofiler import AutoProfiler
from tensor2robot_tpu.trainer import Trainer
from tensor2robot_tpu.trainer import train_eval
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

WATCHER = 't2r-step-watch'


@pytest.fixture(autouse=True)
def fresh_registry():
  previous = obs.set_registry(obs.TelemetryRegistry())
  yield obs.get_registry()
  obs.set_registry(previous)


@pytest.fixture
def mark():
  spans.event('test.mark')
  return max(r.id for r in spans.records())


def _trainer(tmp_path, **kwargs):
  kwargs.setdefault('save_checkpoints_steps', 10**9)
  kwargs.setdefault('async_checkpoints', False)
  kwargs.setdefault('log_every_n_steps', 10**9)
  return Trainer(MockT2RModel(), str(tmp_path / 'run'), **kwargs)


def _watchers():
  return [t for t in threading.enumerate() if t.name == WATCHER]


class Recorder:
  """A hook that does nothing but exist, so ``train.hooks`` is recorded."""

  def __init__(self, stop_at=None):
    self.stop_at = stop_at

  def begin(self, trainer):
    pass

  def after_step(self, trainer, state, step, metrics):
    if self.stop_at is not None and step >= self.stop_at:
      raise StopIteration('stopped from a hook')

  def end(self, trainer, state):
    pass


# -- the loop's spans ---------------------------------------------------------


def test_every_span_of_the_loop_nests_under_its_iteration(tmp_path, mark):
  trainer = _trainer(tmp_path, log_every_n_steps=2, save_checkpoints_steps=3)
  trainer.train(MockInputGenerator(batch_size=8), max_train_steps=4,
                hooks=[Recorder()])
  trainer.close()
  records = spans.records(since_id=mark)
  main = threading.current_thread().name
  iterations = {r.id: r for r in records if r.name == 'train.iteration'}
  assert sorted(r.attrs['step'] for r in iterations.values()) == [1, 2, 3, 4]
  assert all(r.thread == main and r.parent == 0
             for r in iterations.values())
  children = {}
  for r in records:
    if r.parent in iterations:
      children.setdefault(iterations[r.parent].attrs['step'], []).append(r)
  for step in (1, 2, 3, 4):
    names = [r.name for r in children[step]]
    for name in ('data.put_batch', 'train.step', 'train.hooks'):
      assert names.count(name) == 1, (step, names)
    for r in children[step]:
      if r.name in ('data.put_batch', 'train.step', 'train.hooks'):
        assert r.attrs['step'] == step
      if r.name == 'data.next':  # the batch of the NEXT step
        assert r.attrs['step'] == step + 1
  # data.next ends every iteration but the last.
  assert [s for s in children if any(
      r.name == 'data.next' for r in children[s])] == [1, 2, 3]
  # The log window at its cadence (and at the last step), the periodic
  # checkpoint inside its iteration.
  assert [s for s in sorted(children) if any(
      r.name == 'train.log_window' for r in children[s])] == [2, 4]
  assert [s for s in sorted(children) if any(
      r.name == 'ckpt.save' for r in children[s])] == [3]
  # The final save falls after the loop: under no iteration.
  saves = [r for r in records if r.name == 'ckpt.save']
  assert [r.parent in iterations for r in saves] == [True, False]


def test_a_step_writes_well_under_forty_records(tmp_path, mark):
  trainer = _trainer(tmp_path)
  trainer.train(MockInputGenerator(batch_size=8), max_train_steps=10,
                hooks=[Recorder()])
  trainer.close()
  in_loop = [r for r in spans.records(since_id=mark)
             if r.name != 'ckpt.save']
  assert len(in_loop) / 10 < 15


def test_goodput_still_partitions_the_loop(tmp_path):
  trainer = _trainer(tmp_path)
  trainer.train(MockInputGenerator(batch_size=8), max_train_steps=5)
  trainer.close()
  fractions = trainer.last_goodput.fractions()
  assert abs(sum(fractions.values()) - 1.0) < 1e-6


# -- the watcher --------------------------------------------------------------


class FakeLeaf:
  """Stands for a step's output: done when the test says so."""

  def __init__(self, done=False):
    self._done = threading.Event()
    if done:
      self._done.set()

  def finish(self):
    self._done.set()

  def is_ready(self):
    return self._done.is_set()

  def block_until_ready(self):
    assert self._done.wait(timeout=30)
    return self


def _done_events(mark, count, timeout=10.0):
  deadline = time.perf_counter() + timeout
  while True:
    events = [r for r in spans.records(since_id=mark)
              if r.name == 'train.step_done']
    if len(events) >= count or time.perf_counter() > deadline:
      return events
    time.sleep(0.005)


@pytest.fixture
def watcher():
  watcher = train_eval._StepWatcher()
  yield watcher
  watcher.stop()
  assert not _watchers()


def test_watcher_sees_every_step_of_a_slow_loop(watcher, mark):
  for step in range(1, 6):
    leaf = FakeLeaf()
    watcher.submit(step, {'loss': leaf})
    time.sleep(0.01)
    leaf.finish()
    assert len(_done_events(mark, step)) == step
  events = _done_events(mark, 5)
  assert [e.attrs['step'] for e in events] == [1, 2, 3, 4, 5]
  assert [e.attrs['steps_covered'] for e in events] == [1] * 5
  assert all(e.thread == WATCHER for e in events)
  assert all(a.end_ns < b.end_ns for a, b in zip(events[:-1], events[1:]))


def test_watcher_sees_every_step_while_the_host_leads(watcher, mark):
  """A device-bound loop: six steps dispatched ahead, finishing in order."""
  leaves = [FakeLeaf() for _ in range(6)]
  for step, leaf in enumerate(leaves, 1):
    watcher.submit(step, {'loss': leaf})
  for step, leaf in enumerate(leaves, 1):
    # The device is still on this step when the watcher turns to it.
    deadline = time.perf_counter() + 10
    while len(watcher._pending) > 6 - step and time.perf_counter() < deadline:
      time.sleep(0.001)
    leaf.finish()
    assert len(_done_events(mark, step)) == step
  events = _done_events(mark, 6)
  assert [e.attrs['step'] for e in events] == [1, 2, 3, 4, 5, 6]
  assert [e.attrs['steps_covered'] for e in events] == [1] * 6


def test_watcher_coalesces_a_fast_loop(watcher, mark):
  first = FakeLeaf()
  watcher.submit(1, {'loss': first})
  deadline = time.perf_counter() + 10
  while watcher._pending and time.perf_counter() < deadline:
    time.sleep(0.001)  # until the watcher is waiting for step 1
  for step in range(2, 101):
    watcher.submit(step, {'loss': FakeLeaf(done=True)})
  first.finish()
  events = _done_events(mark, 2)
  time.sleep(0.05)
  events = _done_events(mark, 2)
  # One wake-up for the step it waited for, one for everything behind it.
  assert [(e.attrs['step'], e.attrs['steps_covered']) for e in events] == [
      (1, 1), (100, 99)]


def test_watcher_skips_an_output_with_no_leaves(watcher, mark):
  watcher.submit(1, {})
  watcher.submit(2, {'loss': FakeLeaf(done=True)})
  events = _done_events(mark, 1)
  assert [e.attrs['step'] for e in events] == [2]


def test_watcher_holds_metrics_never_the_donated_state(tmp_path, mark,
                                                       monkeypatch):
  handed = []
  submit = train_eval._StepWatcher.submit

  def spy(self, step, metrics):
    handed.append((step, jax.tree_util.tree_leaves(metrics)[0]))
    submit(self, step, metrics)

  monkeypatch.setattr(train_eval._StepWatcher, 'submit', spy)
  trainer = _trainer(tmp_path)
  state = trainer.train(MockInputGenerator(batch_size=8), max_train_steps=6)
  trainer.close()
  assert [step for step, _ in handed] == [1, 2, 3, 4, 5, 6]
  state_leaves = {id(leaf) for leaf in jax.tree_util.tree_leaves(state)}
  for _, leaf in handed:
    # A donated buffer is deleted by the next step; these are all alive.
    assert not leaf.is_deleted()
    assert id(leaf) not in state_leaves
    assert leaf.shape == ()
  events = _done_events(mark, 1)
  assert events and sum(e.attrs['steps_covered'] for e in events) <= 6
  assert events[-1].attrs['step'] <= 6


@pytest.mark.parametrize('how', ['returns', 'raises', 'stopped_from_a_hook'])
def test_watcher_is_gone_after_train(tmp_path, how):
  trainer = _trainer(tmp_path)
  generator = MockInputGenerator(batch_size=8)
  if how == 'returns':
    trainer.train(generator, max_train_steps=3)
  elif how == 'stopped_from_a_hook':
    with pytest.raises(StopIteration):
      trainer.train(generator, max_train_steps=10, hooks=[Recorder(2)])
  else:
    class Boom(Recorder):

      def after_step(self, trainer, state, step, metrics):
        raise RuntimeError('boom')

    with pytest.raises(RuntimeError):
      trainer.train(generator, max_train_steps=10, hooks=[Boom()])
  trainer.close()
  assert not _watchers()


# -- the profiler's clock marker ----------------------------------------------


class FakeProfiler:

  def __init__(self, monkeypatch, backend):
    self.started = []
    self.stopped = 0
    monkeypatch.setattr(jax.profiler, 'start_trace', self._start)
    monkeypatch.setattr(jax.profiler, 'stop_trace', self._stop)
    monkeypatch.setattr(jax, 'default_backend', lambda: backend)

  def _start(self, log_dir, **kwargs):
    self.started.append((time.perf_counter_ns(), kwargs))

  def _stop(self):
    self.stopped += 1


def test_autoprofiler_starts_with_both_host_tracers_off_and_notes_the_offset(
    tmp_path, monkeypatch):
  fake = FakeProfiler(monkeypatch, 'tpu')
  profiler = AutoProfiler(str(tmp_path), window_steps=1, emit_reports=False)
  assert profiler.request_capture('step_time_regression', 1)
  before_ns = time.perf_counter_ns()
  profiler.maybe_profile(1)
  after_ns = time.perf_counter_ns()
  assert profiler.active and not profiler.broken
  (started_ns, kwargs), = fake.started
  options = kwargs['profiler_options']
  assert options.host_tracer_level == 0
  assert options.python_tracer_level == 0
  # The marker ran AFTER start_trace, and its end is on the ring's clock.
  assert before_ns < started_ns < profiler.marker_done_ns < after_ns
  profiler.maybe_profile(2)
  assert fake.stopped == 1 and not profiler.active


def test_autoprofiler_on_the_cpu_backend_keeps_the_thunks_it_would_lose(
    tmp_path, monkeypatch):
  fake = FakeProfiler(monkeypatch, 'cpu')
  profiler = AutoProfiler(str(tmp_path), static_window=(1, 2),
                          emit_reports=False)
  profiler.maybe_profile(1)
  options = fake.started[0][1]['profiler_options']
  assert options.python_tracer_level == 0
  assert options.host_tracer_level > 0
  profiler.abort()
  assert fake.stopped == 1


def test_a_capture_hands_the_rings_records_to_the_report(tmp_path, mark):
  """A real (CPU) capture: no device plane, so no gap can be named, but the
  report carries the interval's records."""
  profiler = AutoProfiler(str(tmp_path), static_window=(1, 2))
  profiler.maybe_profile(1)
  with obs.span('inside.capture', step=1):
    jax.block_until_ready(jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))
  path = profiler.maybe_profile(2)
  assert path is not None
  (_, report), = forensics_lib.read_reports(str(tmp_path))
  names = [r['name'] for r in report['host_spans']]
  assert 'inside.capture' in names and 'test.mark' not in names
  inside = report['host_spans'][names.index('inside.capture')]
  assert inside['attrs'] == {'step': 1}
  assert inside['thread'] == threading.current_thread().name
  assert report['host_device_overlap'] is None  # degrades to absent


# -- idle gaps named from ring records ----------------------------------------


def _record(id_, name, start, end, thread='MainThread', parent=0):
  return spans.SpanRecord(id_, parent, name, thread, start, end, {})


def test_forensics_names_a_hand_made_gap_from_hand_made_records():
  busy = [[0, 1_000_000], [4_000_000, 5_000_000], [5_000_500, 6_000_000],
          [9_000_000, 10_000_000]]
  records = [
      _record(1, 'train.iteration', 500_000, 8_900_000),
      _record(2, 'data.next', 900_000, 3_900_000, parent=1),
      _record(3, 'data.put_batch', 3_950_000, 4_400_000, parent=1),
      _record(4, 'train.step_done', 7_000_000, 7_000_000),  # an instant
  ]
  gaps = forensics_lib.name_idle_gaps(busy, records)
  # 1 ms..4 ms: the innermost span open at 2.5 ms is data.next; 5.0005 ms
  # is a launch gap (under 2 us); 6 ms..9 ms: only the iteration is open.
  assert gaps == {'data.next': pytest.approx(0.003),
                  'train.iteration': pytest.approx(0.003)}
  assert forensics_lib.name_idle_gaps(busy, []) == {
      forensics_lib.NO_HOST_EVENT: pytest.approx(0.006)}


# -- the start-up -------------------------------------------------------------

COMPILE = ('compile.trace', 'compile.lower', 'compile.backend')


@pytest.fixture(scope='module')
def started(tmp_path_factory):
  """One fresh ten-step run; the ring's records of it, in start order."""
  spans.event('test.mark')
  mark = max(r.id for r in spans.records())
  trainer = _trainer(tmp_path_factory.mktemp('startup'))
  trainer.train(MockInputGenerator(batch_size=8), max_train_steps=10)
  trainer.close()
  return sorted(spans.records(since_id=mark), key=lambda r: r.start_ns)


@pytest.fixture(scope='module')
def trained_two_steps(tmp_path_factory):
  """A model directory with a checkpoint at step 2; copy it, do not use it."""
  root = tmp_path_factory.mktemp('trained')
  trainer = _trainer(root)
  trainer.train(MockInputGenerator(batch_size=8), max_train_steps=2)
  trainer.close()
  return root / 'run'


def _one(records, name, **attrs):
  found = [r for r in records if r.name == name and
           all(r.attrs.get(k) == v for k, v in attrs.items())]
  assert len(found) == 1, (name, attrs, len(found))
  return found[0]


@pytest.mark.parametrize('case', [
    'startup_holds_the_first_batch_and_the_state',
    'startup_ends_where_the_first_iteration_starts',
    'the_first_step_has_compile_children_the_tenth_none',
    'the_init_program_compiles_under_init_state',
    'nothing_compiles_under_no_span',
])
def test_the_start_up_of_a_fresh_run_is_in_the_ring(started, case):
  startup = _one(started, 'train.startup')
  first_batch = _one(started, 'train.first_batch')
  init_state = _one(started, 'train.init_state')
  compiles = [r for r in started if r.name in COMPILE]
  if case == 'startup_holds_the_first_batch_and_the_state':
    assert startup.parent == 0 and startup.attrs == {'start_step': 0}
    assert first_batch.parent == init_state.parent == startup.id
    assert init_state.attrs == {'restored': 0}
    assert startup.start_ns <= first_batch.start_ns
    assert first_batch.end_ns <= init_state.start_ns
    assert init_state.end_ns <= startup.end_ns
    assert startup.thread == threading.current_thread().name
    # The first batch is NOT a data.next: the input metrics read that name.
    assert not [r for r in started if r.name == 'data.next'
                and r.start_ns < startup.end_ns]
  elif case == 'startup_ends_where_the_first_iteration_starts':
    first = _one(started, 'train.iteration', step=1)
    # Between the two: the signal handlers and the step watcher's thread.
    assert 0 <= first.start_ns - startup.end_ns < 50e6
    assert first.parent == 0
    assert not [r for r in started if r.name != 'test.mark' and
                r.thread == startup.thread and r.start_ns < startup.start_ns]
  elif case == 'the_first_step_has_compile_children_the_tenth_none':
    first, tenth = (_one(started, 'train.step', step=n) for n in (1, 10))
    children = [r for r in compiles if r.parent == first.id]
    assert [r.name for r in children] == list(COMPILE)
    assert children[0].attrs['fun'] == 'step'
    assert children[2].attrs['fun'] == 'jit(step)'
    for r in children:
      assert first.start_ns <= r.start_ns and r.end_ns <= first.end_ns
    # They say what the first step's seconds were: most of the record.
    covered = sum(r.end_ns - r.start_ns for r in children)
    assert covered > 0.5 * (first.end_ns - first.start_ns)
    assert not [r for r in compiles if r.parent == tenth.id]
    later = [r for r in started if r.name == 'train.step'
             and r.attrs['step'] > 1]
    assert not [r for r in compiles if r.parent in {s.id for s in later}]
  elif case == 'the_init_program_compiles_under_init_state':
    under = [r for r in compiles if r.parent == init_state.id]
    assert {r.name for r in under} == set(COMPILE)
    assert all(init_state.start_ns <= r.start_ns and
               r.end_ns <= init_state.end_ns for r in under)
  elif case == 'nothing_compiles_under_no_span':
    # Every compile of Trainer.train has a program span over it.
    assert not [r for r in compiles if r.parent == 0 and
                startup.start_ns <= r.start_ns]


@pytest.mark.parametrize('case', [
    'a_state_handed_in_leaves_init_state_outside_the_startup',
    'a_restore_is_noted_and_timed_once',
    'a_start_up_that_raises_leaves_the_stack_clean',
    'nothing_left_to_train_still_closes_the_startup',
])
def test_the_start_up_spans_on_the_other_paths(tmp_path, mark, case,
                                               trained_two_steps):
  generator = MockInputGenerator(batch_size=8)
  if case == 'a_state_handed_in_leaves_init_state_outside_the_startup':
    # As the benchmark's drivers do: the state first, then train().
    trainer = _trainer(tmp_path)
    generator = train_eval.provide_input_generator_with_model_information(
        generator, trainer.model, train_eval.ModeKeys.TRAIN)
    features, labels = next(generator.create_dataset_iterator(
        mode=train_eval.ModeKeys.TRAIN))
    state = trainer.init_state(features, labels)
    trainer.train(generator, max_train_steps=2, state=state)
    trainer.close()
    records = spans.records(since_id=mark)
    init_state = _one(records, 'train.init_state')
    startup = _one(records, 'train.startup')
    assert init_state.parent == 0
    assert init_state.end_ns <= startup.start_ns
    assert trainer._init_state_s == pytest.approx(
        (init_state.end_ns - init_state.start_ns) * 1e-9)
  elif case == 'a_restore_is_noted_and_timed_once':
    shutil.copytree(trained_two_steps, tmp_path / 'run')
    trainer = _trainer(tmp_path)
    trainer.train(generator, max_train_steps=3)
    trainer.close()
    records = spans.records(since_id=mark)
    init_state = _one(records, 'train.init_state')
    assert init_state.attrs == {'restored': 1}
    assert _one(records, 'train.startup').attrs == {'start_step': 2}
    restore = _one(records, 'ckpt.restore')
    assert restore.parent == init_state.id
    # What the recovery timeline calls restore_s is this span's time.
    assert trainer._init_state_s == pytest.approx(
        (init_state.end_ns - init_state.start_ns) * 1e-9)
  elif case == 'a_start_up_that_raises_leaves_the_stack_clean':
    class Broken(MockInputGenerator):

      def create_dataset_iterator(self, **kwargs):
        raise RuntimeError('no data')

    trainer = _trainer(tmp_path)
    with pytest.raises(RuntimeError):
      trainer.train(Broken(batch_size=8), max_train_steps=2)
    trainer.close()
    with obs.span('after'):
      pass
    records = spans.records(since_id=mark)
    startup = _one(records, 'train.startup')
    assert _one(records, 'train.first_batch').parent == startup.id
    assert _one(records, 'after').parent == 0
    assert not [r for r in records if r.name == 'train.iteration']
  elif case == 'nothing_left_to_train_still_closes_the_startup':
    shutil.copytree(trained_two_steps, tmp_path / 'run')
    trainer = _trainer(tmp_path)
    trainer.train(generator, max_train_steps=2)
    trainer.close()
    records = spans.records(since_id=mark)
    assert _one(records, 'train.startup').attrs == {'start_step': 2}
    assert not [r for r in records if r.name == 'train.iteration']
    with obs.span('after'):
      pass
    assert _one(spans.records(since_id=mark), 'after').parent == 0
