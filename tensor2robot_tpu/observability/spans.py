"""Trace spans: wall-time histograms plus one in-memory ring of records.

``span('data.next')`` times a region and does two things when it closes:
it records milliseconds into the registry histogram ``span/data.next``
(the aggregate every dashboard reads), and it appends one record to a
process-wide, bounded ring: ``id``, ``parent`` (the span open on the same
thread when this one opened), ``name``, ``thread``, ``start_ns``,
``end_ns`` and a small dict of attributes (numbers; the ``compile.*``
records also name their function). ``event(name, ...)``
appends an instant record (start == end) at the same boundaries, and
``interval(name, start_ns, end_ns, ...)`` one the caller measured itself
(the compile path's listeners, `signals.py`, learn of a phase as it ends).

Every record of every thread is on ONE clock, ``time.perf_counter_ns``,
so the input producer, the trainer loop and the step-completion watcher
can be laid beside each other, and beside a profiler capture through
the clock marker `observability/autoprofiler.py` notes when it starts a
trace (the host tracer is never on: PERF.md, Findings PR 24).

The ring is as always-on as the histograms: no switch. A span costs two
clock reads, a thread-local stack push and pop, one histogram bump and
one locked append (measured cost: PERF.md, Findings PR 25). The ring
holds ``RING_CAPACITY`` records; older ones fall out and are counted by
``dropped()``, so a reader can tell a whole window from a torn one.

Use as a context manager or a decorator::

    with span('data.next', step=step) as sp:
        batch = next(iterator)
    sp.elapsed  # seconds, so goodput call sites time the region once

    @span('policy.pack')
    def pack(...): ...

    for record in records(since_id=seen): ...

A span must open and close on one thread and must not stay open across
a generator's ``yield`` (the thread's stack of open spans names the
parent).
"""

from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
import weakref
from typing import List, Optional

from tensor2robot_tpu.observability import registry as registry_lib

__all__ = ['RING_CAPACITY', 'SpanRecord', 'SpanRing', 'dropped', 'event',
           'interval', 'records', 'span']

# Span histograms hold milliseconds: sub-ms histogram bumps up to minutes
# (a slow checkpoint commit, a cold data pipeline).
SPAN_BUCKETS_MS = registry_lib.exponential_buckets(0.01, 2.0, 25)

# The trainer and its input producer write about 10 records a step, so
# 65,536 hold the last 20 minutes of a 0.34 s step and 10 of a 0.17 s
# one: longer than any capture or benchmark window, and a dozen MB at
# most (a record is one tuple and one small dict).
RING_CAPACITY = 65536

SpanRecord = collections.namedtuple(
    'SpanRecord', 'id parent name thread start_ns end_ns attrs')


class SpanRing:
  """The newest ``capacity`` records, in the order they were appended
  (a span is appended when it CLOSES, so a parent follows its children)."""

  def __init__(self, capacity: int = RING_CAPACITY):
    self._capacity = int(capacity)
    self._records: collections.deque = collections.deque(maxlen=self._capacity)
    self._appended = 0
    self._lock = threading.Lock()

  def append(self, record: tuple) -> None:
    with self._lock:
      self._records.append(record)
      self._appended += 1

  def dropped(self) -> int:
    """Records that fell out because the ring was full."""
    return max(0, self._appended - self._capacity)

  def records(self, since_id: int = 0) -> List[SpanRecord]:
    """Records with ``id > since_id``. Ids are given when a span OPENS: a
    span still open at one read turns up at a later read with an id under
    ones already seen, so a poller that must miss nothing filters by
    ``end_ns`` instead."""
    with self._lock:
      snapshot = list(self._records)
    return [SpanRecord._make(r) for r in snapshot if r[0] > since_id]


class _ThreadState(threading.local):
  """Per thread: the ids of its open spans, innermost last, and its name
  as it was when the thread first recorded."""

  def __init__(self):
    self.stack: List[int] = []
    self.thread = threading.current_thread().name


_RING = SpanRing()
_IDS = itertools.count(1)  # next() on it is atomic under the GIL
_LOCAL = _ThreadState()
# registry -> {span name: its histogram}. ``registry.histogram`` checks the
# registration under the registry's lock on every call (1.7 us here, more
# than the rest of a span); a registry never drops an instrument, so what
# it returned once stays right.
_HISTOGRAMS: 'weakref.WeakKeyDictionary' = weakref.WeakKeyDictionary()


def _histogram(registry: registry_lib.TelemetryRegistry, name: str):
  try:
    return _HISTOGRAMS[registry][name]
  except KeyError:
    histogram = registry.histogram('span/' + name, bounds=SPAN_BUCKETS_MS)
    _HISTOGRAMS.setdefault(registry, {})[name] = histogram
    return histogram


def records(since_id: int = 0) -> List[SpanRecord]:
  """The process ring's records (``SpanRing.records``)."""
  return _RING.records(since_id)


def dropped() -> int:
  """How many records the process ring has lost to its capacity."""
  return _RING.dropped()


def event(name: str, **attrs) -> None:
  """Appends an instant record: a count or a moment at a span boundary."""
  now_ns = time.perf_counter_ns()
  local = _LOCAL
  stack = local.stack
  _RING.append((next(_IDS), stack[-1] if stack else 0, name, local.thread,
                now_ns, now_ns, attrs))


def interval(name: str, start_ns: int, end_ns: int, **attrs) -> None:
  """Appends a closed interval the caller measured on this module's clock
  (``time.perf_counter_ns``), under the span open on the calling thread.
  The ring only: the caller keeps whatever aggregate it has of its own
  (the compile path's are ``jax/compile_ms`` and ``jax/trace_ms``)."""
  local = _LOCAL
  stack = local.stack
  _RING.append((next(_IDS), stack[-1] if stack else 0, name, local.thread,
                start_ns, end_ns, attrs))


class span:  # noqa: N801 — reads as a keyword at call sites
  """Times one region into ``span/<name>`` (ms) and into the ring."""

  __slots__ = ('_name', '_registry', '_attrs', '_id', '_parent', '_start_ns',
               'elapsed')

  def __init__(self, name: str,
               registry: Optional[registry_lib.TelemetryRegistry] = None,
               **attrs):
    self._name = name
    self._registry = registry
    self._attrs = attrs
    self.elapsed = 0.0

  def note(self, **attrs) -> None:
    """Attributes known only inside the region (bytes packed, ...)."""
    self._attrs.update(attrs)

  def __enter__(self) -> 'span':
    stack = _LOCAL.stack
    self._parent = stack[-1] if stack else 0
    self._id = next(_IDS)
    stack.append(self._id)
    self._start_ns = time.perf_counter_ns()
    return self

  def __exit__(self, exc_type, exc, tb) -> None:
    end_ns = time.perf_counter_ns()
    local = _LOCAL
    local.stack.pop()
    self.elapsed = (end_ns - self._start_ns) * 1e-9
    _histogram(self._registry or registry_lib.get_registry(),
               self._name).record(self.elapsed * 1e3)
    _RING.append((self._id, self._parent, self._name, local.thread,
                  self._start_ns, end_ns, self._attrs))

  def __call__(self, fn):
    """Decorator form: each call runs under a fresh span instance."""
    name = self._name
    registry = self._registry
    attrs = self._attrs

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
      with span(name, registry=registry, **attrs):
        return fn(*args, **kwargs)

    return wrapper
