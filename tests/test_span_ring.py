"""observability/spans.py: the span ring. Parent and thread of nested and
cross-thread spans, events, attributes, capacity and ``dropped``, and the
histograms beside it."""

import sys
import threading

import pytest

from tensor2robot_tpu import observability as obs
from tensor2robot_tpu.observability import spans


@pytest.fixture(autouse=True)
def fresh_registry():
  previous = obs.set_registry(obs.TelemetryRegistry())
  yield obs.get_registry()
  obs.set_registry(previous)


@pytest.fixture
def mark():
  """The ring is the process's: a test reads what came after this id."""
  spans.event('test.mark')
  return max(r.id for r in spans.records())


def _by_name(mark):
  return {r.name: r for r in spans.records(since_id=mark)}


def test_nested_spans_name_their_parent_and_thread(mark):
  with obs.span('outer'):
    with obs.span('middle'):
      with obs.span('inner'):
        pass
    with obs.span('sibling'):
      pass
  got = _by_name(mark)
  assert got['outer'].parent == 0
  assert got['middle'].parent == got['outer'].id
  assert got['inner'].parent == got['middle'].id
  assert got['sibling'].parent == got['outer'].id
  assert {r.thread for r in got.values()} == {
      threading.current_thread().name}
  # A span is appended when it closes, so a parent follows its children.
  order = [r.name for r in spans.records(since_id=mark)]
  assert order == ['inner', 'middle', 'sibling', 'outer']
  for child, parent in (('inner', 'middle'), ('middle', 'outer')):
    assert got[parent].start_ns <= got[child].start_ns
    assert got[child].end_ns <= got[parent].end_ns


def test_a_span_on_another_thread_has_its_own_stack_and_the_same_clock(mark):
  def work():
    with obs.span('worker.outer'):
      with obs.span('worker.inner'):
        pass

  with obs.span('main.open'):
    thread = threading.Thread(target=work, name='test-worker')
    thread.start()
    thread.join(timeout=10)
    assert not thread.is_alive()
  got = _by_name(mark)
  # The main thread's open span is NOT the worker's parent.
  assert got['worker.outer'].parent == 0
  assert got['worker.inner'].parent == got['worker.outer'].id
  assert got['worker.outer'].thread == 'test-worker'
  assert got['main.open'].thread == threading.current_thread().name
  # One clock: the worker ran while the main thread's span was open.
  assert got['main.open'].start_ns <= got['worker.outer'].start_ns
  assert got['worker.outer'].end_ns <= got['main.open'].end_ns


def test_event_is_an_instant_under_the_open_span(mark):
  with obs.span('holder'):
    obs.event('tick', count=3, seconds=0.5)
  obs.event('loose')
  got = _by_name(mark)
  assert got['tick'].start_ns == got['tick'].end_ns
  assert got['tick'].parent == got['holder'].id
  assert got['tick'].attrs == {'count': 3, 'seconds': 0.5}
  assert got['holder'].start_ns <= got['tick'].start_ns <= \
      got['holder'].end_ns
  assert got['loose'].parent == 0 and got['loose'].attrs == {}


def test_attributes_at_open_and_noted_inside(mark):
  with obs.span('pack', batch=7) as sp:
    sp.note(bytes=1024)
  assert _by_name(mark)['pack'].attrs == {'batch': 7, 'bytes': 1024}


def test_decorated_function_records_a_fresh_span_each_call(
    mark, fresh_registry):

  @obs.span('decorated', kind=1)
  def work(x):
    return x + 1

  assert work(1) == 2 and work(2) == 3
  records = [r for r in spans.records(since_id=mark)
             if r.name == 'decorated']
  assert len(records) == 2 and records[0].id != records[1].id
  assert all(r.attrs == {'kind': 1} for r in records)
  assert fresh_registry.scalars()['span/decorated/count'] == 2.0


def test_a_span_that_raises_still_closes_and_leaves_the_stack_clean(mark):
  with pytest.raises(ValueError):
    with obs.span('outer'):
      with obs.span('failing'):
        raise ValueError('boom')
  with obs.span('after'):
    pass
  got = _by_name(mark)
  assert got['failing'].parent == got['outer'].id
  assert got['after'].parent == 0


def test_ring_and_histogram_are_fed_by_the_same_exit(mark, fresh_registry):
  for _ in range(5):
    with obs.span('both') as sp:
      pass
  records = [r for r in spans.records(since_id=mark) if r.name == 'both']
  assert len(records) == 5
  assert fresh_registry.scalars()['span/both/count'] == 5.0
  assert sp.elapsed == pytest.approx(
      (records[-1].end_ns - records[-1].start_ns) * 1e-9)


def test_a_registry_passed_in_gets_the_histogram(mark, fresh_registry):
  other = obs.TelemetryRegistry()
  with obs.span('routed', registry=other):
    pass
  assert other.scalars()['span/routed/count'] == 1.0
  assert 'span/routed/count' not in fresh_registry.scalars()
  assert 'routed' in _by_name(mark)


def test_capacity_and_dropped():
  ring = spans.SpanRing(capacity=4)
  assert ring.dropped() == 0 and ring.records() == []
  for i in range(1, 4):
    ring.append((i, 0, 'n', 't', i, i + 1, {}))
  assert ring.dropped() == 0 and len(ring.records()) == 3
  for i in range(4, 11):
    ring.append((i, 0, 'n', 't', i, i + 1, {}))
  # The newest four are kept, the six before them counted.
  assert [r.id for r in ring.records()] == [7, 8, 9, 10]
  assert ring.dropped() == 6
  assert [r.id for r in ring.records(since_id=8)] == [9, 10]
  record = ring.records()[0]
  assert record._fields == ('id', 'parent', 'name', 'thread', 'start_ns',
                            'end_ns', 'attrs')


def test_the_process_ring_is_bounded_and_has_no_switch():
  assert spans.RING_CAPACITY == 65536
  assert spans.dropped() >= 0
  # Nothing in the module turns the ring off.
  assert not [name for name in dir(spans)
              if 'active' in name or 'enable' in name or 'disable' in name]


def test_since_id_returns_only_newer_records(mark):
  with obs.span('first'):
    pass
  first = _by_name(mark)['first']
  with obs.span('second'):
    pass
  assert [r.name for r in spans.records(since_id=first.id)] == ['second']


def test_many_threads_lose_no_record_and_share_no_id(mark):
  """More writers than cores, a short switch interval: every span of every
  thread is in the ring once, under its own thread, with a unique id."""
  threads, per_thread = 16, 300
  interval = sys.getswitchinterval()
  sys.setswitchinterval(1e-5)
  try:
    def work(index):
      for i in range(per_thread):
        with obs.span('stress', worker=index, i=i):
          pass

    workers = [threading.Thread(target=work, args=(k,), name='stress-%d' % k)
               for k in range(threads)]
    for worker in workers:
      worker.start()
    for worker in workers:
      worker.join(timeout=60)
      assert not worker.is_alive()
  finally:
    sys.setswitchinterval(interval)
  records = [r for r in spans.records(since_id=mark) if r.name == 'stress']
  assert len(records) == threads * per_thread
  assert len({r.id for r in records}) == len(records)
  for r in records:
    assert r.thread == 'stress-%d' % r.attrs['worker']
    assert r.parent == 0
  assert obs.get_registry().scalars()['span/stress/count'] == \
      threads * per_thread


# -- a closed interval, and the compile path that writes them ------------------


def test_an_interval_the_caller_measured_lies_under_the_open_span(
    mark, fresh_registry):
  with obs.span('holder'):
    spans.interval('measured', 1_000, 4_000_000, fun='f', inner=2)
  spans.interval('loose', 5, 5)
  got = _by_name(mark)
  assert got['measured'].parent == got['holder'].id
  assert (got['measured'].start_ns, got['measured'].end_ns) == (
      1_000, 4_000_000)
  assert got['measured'].attrs == {'fun': 'f', 'inner': 2}
  assert got['measured'].thread == threading.current_thread().name
  assert got['loose'].parent == 0
  # The ring only: whoever measured the interval keeps its own aggregate.
  assert 'span/measured/count' not in fresh_registry.scalars()


PHASES = ('compile.trace', 'compile.lower', 'compile.backend')


def _compile_outer():
  """A jitted function, new each call, that calls an inner jitted one."""
  import jax
  import jax.numpy as jnp

  @jax.jit
  def inner(x):
    return jnp.where(x > 0, x, 0.0) * 2

  @jax.jit
  def outer(x):
    return inner(x).sum() + 1

  return outer


@pytest.fixture
def listeners():
  from tensor2robot_tpu.observability import signals

  import jax.numpy as jnp

  was_enabled = signals._enabled
  # The tests' operand is a compile request of its own the first time a
  # process builds it (whichever case a worker runs first): not theirs.
  obs.uninstall_jax_listeners()
  jnp.ones((4,))
  obs.install_jax_listeners()
  yield signals
  if was_enabled:
    obs.install_jax_listeners()
  else:
    obs.uninstall_jax_listeners()


@pytest.mark.parametrize('case', [
    'one_record_a_phase_naming_the_outer_function',
    'disjoint_and_in_order_on_the_thread',
    'a_second_call_writes_none',
    'the_parent_is_the_open_span',
    'another_thread_has_its_own_phases',
    'uninstall_stops_the_records',
    'the_counters_count_what_they_counted',
    'an_eager_op_is_a_request_of_its_own',
])
def test_the_compile_path_writes_its_outermost_phases_into_the_ring(
    case, listeners, fresh_registry):
  import jax.numpy as jnp

  operand = jnp.ones((4,))
  outer = _compile_outer()
  spans.event('test.mark')
  mark = max(r.id for r in spans.records())

  def phases():
    return [r for r in spans.records(since_id=mark) if r.name in PHASES]

  if case == 'uninstall_stops_the_records':
    obs.uninstall_jax_listeners()
    outer(operand)
    assert phases() == []
    assert 'jax/compiles' not in fresh_registry.scalars()
    # Phases that opened and closed while it was off left nothing open:
    # the next outermost phase is a record again.
    obs.install_jax_listeners()
    _compile_outer()(operand)
    assert [r.name for r in phases()] == list(PHASES)
    return
  if case == 'an_eager_op_is_a_request_of_its_own':
    jnp.ones((3, 5, 7)) @ jnp.ones((7, 2))
    backends = [r for r in phases() if r.name == 'compile.backend']
    assert len(backends) >= 1
    assert fresh_registry.scalars()['jax/compiles'] == len(backends)
    return
  if case == 'another_thread_has_its_own_phases':
    with obs.span('main.open'):
      thread = threading.Thread(target=outer, args=(operand,),
                                name='test-compiler')
      thread.start()
      thread.join(timeout=60)
    got = phases()
    assert [r.name for r in got] == list(PHASES)
    assert {r.thread for r in got} == {'test-compiler'}
    assert {r.parent for r in got} == {0}
    return

  with obs.span('holder'):
    outer(operand)
  got = phases()
  assert [r.name for r in got] == list(PHASES)
  trace, lower, backend = got
  if case == 'one_record_a_phase_naming_the_outer_function':
    # jax's own words: the function for the trace, jit(function) after it.
    assert trace.attrs['fun'] == 'outer'
    assert lower.attrs['fun'] == backend.attrs['fun'] == 'jit(outer)'
    # inner, where, multiply, sum, add ... were traced inside outer's trace
    # and folded into it.
    assert trace.attrs['inner'] >= 1
    assert set(trace.attrs) == set(lower.attrs) == {'fun', 'inner'}
    assert backend.attrs == {'fun': 'jit(outer)', 'from_cache': 0,
                             'cache_read_ms': 0.0}
  elif case == 'disjoint_and_in_order_on_the_thread':
    assert {r.thread for r in got} == {threading.current_thread().name}
    edges = [t for r in got for t in (r.start_ns, r.end_ns)]
    assert edges == sorted(edges)
    assert all(r.start_ns < r.end_ns for r in got)
    holder = _by_name(mark)['holder']
    assert holder.start_ns <= trace.start_ns
    assert backend.end_ns <= holder.end_ns
  elif case == 'a_second_call_writes_none':
    outer(operand)
    assert len(phases()) == 3
  elif case == 'the_parent_is_the_open_span':
    holder = _by_name(mark)['holder']
    assert {r.parent for r in got} == {holder.id}
    # The compile path's aggregates stay jax/compile_ms and jax/trace_ms.
    assert not [tag for tag in fresh_registry.scalars()
                if tag.startswith('span/compile.')]
  elif case == 'the_counters_count_what_they_counted':
    scalars = fresh_registry.scalars()
    # One compile request, one histogram entry for it; every trace event,
    # folded or not, in jax/trace_ms as before.
    assert scalars['jax/compiles'] == 1.0
    assert scalars['jax/compile_ms/count'] == 1.0
    assert scalars['jax/compile_ms/max'] == pytest.approx(
        (backend.end_ns - backend.start_ns) / 1e6, rel=0.05, abs=0.5)
    traced = scalars['jax/trace_ms/count']
    assert 1 + trace.attrs['inner'] <= traced <= (
        1 + trace.attrs['inner'] + lower.attrs['inner'])


def test_a_backend_compile_inside_a_trace_is_still_a_record(
    listeners, fresh_registry):
  """An operation run eagerly while a function is traced compiles inside
  that trace: its trace and lowering fold into the outer record, its
  backend compile is a record of its own, inside the outer one."""
  import jax
  import jax.numpy as jnp

  @jax.jit
  def outer(x):
    with jax.ensure_compile_time_eval():
      table = jnp.cumsum(jnp.arange(11.0))  # eager, compiled here
    return x * table[3]

  spans.event('test.mark')
  mark = max(r.id for r in spans.records())
  outer(jnp.ones((4,)))
  got = [r for r in spans.records(since_id=mark) if r.name in PHASES]
  traces = [r for r in got if r.name == 'compile.trace']
  backends = [r for r in got if r.name == 'compile.backend']
  assert [r.attrs['fun'] for r in traces] == ['outer']
  assert len(backends) >= 2 and backends[-1].attrs['fun'] == 'jit(outer)'
  nested = [b for b in backends if traces[0].start_ns <= b.start_ns
            and b.end_ns <= traces[0].end_ns]
  assert nested and nested == backends[:-1]
  assert fresh_registry.scalars()['jax/compiles'] == len(backends)


_CACHED_COMPILE = '''
import json, sys
import jax, jax.numpy as jnp
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
from tensor2robot_tpu import observability as obs
from tensor2robot_tpu.observability import spans
obs.install_jax_listeners()
operand = jnp.ones((16, 16))
mark = max([r.id for r in spans.records()] or [0])
jax.jit(lambda x: (x @ x).sum(), keep_unused=True)(operand)
print(json.dumps([r.attrs for r in spans.records(since_id=mark)
                  if r.name == 'compile.backend']))
'''


def test_a_persistent_cache_hit_is_noted_with_the_time_the_read_took(
    tmp_path):
  import json
  import os
  import subprocess

  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=root,
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
  runs = []
  for _ in range(2):  # two fresh processes, one cache directory
    done = subprocess.run([sys.executable, '-c', _CACHED_COMPILE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    runs.append(json.loads(done.stdout.splitlines()[-1]))
  (cold,), (warm,) = runs
  assert cold['from_cache'] == 0 and cold['cache_read_ms'] == 0.0
  assert warm['from_cache'] == 1 and warm['cache_read_ms'] > 0
  assert cold['fun'] == warm['fun']
