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
