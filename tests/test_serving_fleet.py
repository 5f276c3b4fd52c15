"""Serving-fleet tests (ISSUE 14): replica handles, the telemetry-
weighted router (dispatch, shed-at-the-door, ejection + exactly-once
retry), ServingFleet lifecycle (scale up/down, rolling swap, autoscaler,
indexed telemetry streams), the fleet HTTP frontend (503 on fleet-wide
shed), and the doctor/CI-gate fleet section.

Everything runs on CPU with injected ``batch_fn``s, like
tests/test_serving.py — the routing / ejection / scaling contract is
host logic.
"""

import http.client
import importlib.machinery
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tensor2robot_tpu.observability import (
    TelemetryRegistry,
    read_telemetry,
    set_registry,
)
from tensor2robot_tpu.observability import doctor
from tensor2robot_tpu.observability.telemetry_file import discover_hosts
from tensor2robot_tpu.serving import (
    FleetRouter,
    HttpReplicaHandle,
    LocalReplicaHandle,
    PolicyServer,
    ReplicaHandle,
    RequestRejected,
    RouterConfig,
    ServingConfig,
    ServingFleet,
    ServingFleetConfig,
    replica_host_meta,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def registry():
  fresh = TelemetryRegistry()
  previous = set_registry(fresh)
  yield fresh
  set_registry(previous)


def _state(value, size=3):
  return {'x': np.full((size,), float(value), np.float32)}


def _echo_batch_fn(variables, features, seed):
  x = features['x']
  return {'y': x * variables['scale'],
          'version': np.full((x.shape[0],), variables['version'],
                             np.int64)}


def _make_server(registry, scale=2.0, version=1, batch_fn=None,
                 telemetry=None, report_interval_s=0.05,
                 max_queue_depth=64):
  server = PolicyServer(
      batch_fn or _echo_batch_fn, {'scale': scale, 'version': version},
      ServingConfig(max_batch_size=4, max_wait_ms=1.0,
                    max_queue_depth=max_queue_depth,
                    report_interval_s=report_interval_s),
      version=version, telemetry=telemetry, registry=registry)
  server.start()
  return server


def _drive(submit, n, concurrency=8, timeout_s=10.0):
  """n concurrent requests through ``submit``; returns (results, errors)."""
  results = []
  errors = []
  lock = threading.Lock()
  todo = iter(range(n))

  def worker():
    while True:
      with lock:
        try:
          i = next(todo)
        except StopIteration:
          return
      try:
        result = submit(_state(i)).result(timeout=timeout_s)
        with lock:
          results.append((i, result))
      except Exception as e:  # noqa: BLE001 — errors are the assertion
        with lock:
          errors.append((i, e))

  threads = [threading.Thread(target=worker) for _ in range(concurrency)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  return results, errors


# -- replica handles ----------------------------------------------------------


class TestLocalReplicaHandle:

  def test_snapshot_reflects_server_window(self, registry):
    server = _make_server(registry)
    handle = LocalReplicaHandle(1, server)
    try:
      snap = handle.snapshot()
      assert snap['alive'] and snap['p99_ms'] is None  # no window yet
      assert snap['max_queue_depth'] == 64
      server.select_action(_state(1), timeout_s=5.0)
      deadline = time.monotonic() + 5.0
      while handle.snapshot()['p99_ms'] is None and \
          time.monotonic() < deadline:
        time.sleep(0.01)
      snap = handle.snapshot()
      assert snap['p99_ms'] is not None and snap['p99_ms'] > 0
      assert snap['heartbeat_age_s'] < 5.0
    finally:
      handle.close()
    assert not handle.snapshot()['alive']  # closed server reads dead

  def test_wedged_serve_loop_reads_as_stale_heartbeat(self, registry):
    gate = threading.Event()

    def wedged(variables, features, seed):
      gate.wait(10.0)
      return _echo_batch_fn(variables, features, seed)

    server = _make_server(registry, batch_fn=wedged,
                          report_interval_s=0.02)
    handle = LocalReplicaHandle(1, server)
    try:
      handle.submit(_state(1))  # wedges the loop inside the batch
      time.sleep(0.2)
      snap = handle.snapshot()
      assert snap['alive']  # thread alive, but...
      assert snap['heartbeat_age_s'] > 0.1  # ...it stopped reporting
    finally:
      gate.set()
      handle.close()


class TestHttpReplicaHandle:

  @pytest.fixture()
  def http_replica(self, registry):
    from tensor2robot_tpu.serving.frontend import build_http_server

    server = PolicyServer(_echo_batch_fn, {'scale': 2.0, 'version': 5},
                          ServingConfig(max_batch_size=4, max_wait_ms=1.0),
                          version=5, registry=registry,
                          feature_spec={'x': ((3,), np.float32)})
    server.start()
    httpd, port = build_http_server(server, port=0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    yield server, port
    httpd.shutdown()
    server.close()

  def test_submit_and_snapshot_over_http(self, http_replica):
    _, port = http_replica
    handle = HttpReplicaHandle(7, '127.0.0.1', port)
    try:
      result = handle.submit(_state(3)).result(timeout=10.0)
      np.testing.assert_allclose(result.outputs['y'], 6.0)
      assert result.version == 5
      snap = handle.snapshot()
      assert snap['alive'] and snap['params_version'] == 5
      assert snap['max_queue_depth'] == 64
    finally:
      handle.close()

  def test_dead_endpoint_reads_dead_not_raising(self, registry):
    handle = HttpReplicaHandle(7, '127.0.0.1', 1)  # nothing listens
    snap = handle.snapshot()
    assert not snap['alive']
    handle.close()

  def test_router_mixes_local_and_http_replicas(self, registry,
                                                http_replica):
    """The multi-host story: one router, handles of both kinds."""
    _, port = http_replica
    local = LocalReplicaHandle(1, _make_server(registry, version=5))
    remote = HttpReplicaHandle(2, '127.0.0.1', port)
    router = FleetRouter([local, remote],
                         RouterConfig(health_interval_s=0.05),
                         registry=registry).start()
    try:
      results, errors = _drive(router.submit, 40, concurrency=8)
      assert not errors
      assert {r.replica for _, r in results} == {1, 2}
      for i, result in results:
        np.testing.assert_allclose(result.outputs['y'], i * 2.0)
    finally:
      router.stop()
      local.close()
      remote.close()


# -- router dispatch ----------------------------------------------------------


class TestFleetRouter:

  def _router(self, registry, n=3, config=None, batch_fns=None):
    handles = []
    for i in range(1, n + 1):
      batch_fn = (batch_fns or {}).get(i)
      handles.append(LocalReplicaHandle(
          i, _make_server(registry, batch_fn=batch_fn)))
    router = FleetRouter(handles,
                         config or RouterConfig(health_interval_s=0.05),
                         registry=registry)
    return router, handles

  def test_spreads_load_and_ids_are_unique(self, registry):
    router, handles = self._router(registry)
    router.start()
    try:
      results, errors = _drive(router.submit, 120, concurrency=16)
      assert not errors
      assert len(results) == 120
      ids = [r.request_id for _, r in results]
      assert len(set(ids)) == len(ids)  # exactly-once delivery
      served = {r.replica for _, r in results}
      assert served == {1, 2, 3}  # every replica carried load
      for i, result in results:
        np.testing.assert_allclose(result.outputs['y'], i * 2.0)
    finally:
      router.stop()
      for handle in handles:
        handle.close()

  def test_weights_follow_windowed_p99(self, registry):
    def slow(variables, features, seed):
      time.sleep(0.05)
      return _echo_batch_fn(variables, features, seed)

    router, handles = self._router(registry, n=2, batch_fns={2: slow})
    router.start()
    try:
      results, errors = _drive(router.submit, 80, concurrency=8)
      assert not errors
      time.sleep(0.2)  # a health pass over closed report windows
      router.observe()
      with router._lock:
        weights = dict(router._weights)
      # The slow replica's windowed p99 is ~25x the fast one's: its
      # routing weight must sit well below the fast replica's.
      assert weights[1] > weights[2]
      by_replica = {1: 0, 2: 0}
      for _, result in results:
        by_replica[result.replica] += 1
      assert by_replica[1] > by_replica[2]  # load followed the weights
    finally:
      router.stop()
      for handle in handles:
        handle.close()

  def test_fleet_wide_shed_before_any_replica_queue(self, registry):
    gate = threading.Event()

    def gated(variables, features, seed):
      gate.wait(10.0)
      return _echo_batch_fn(variables, features, seed)

    router, handles = self._router(
        registry, n=2,
        config=RouterConfig(health_interval_s=0.05, max_fleet_pending=6),
        batch_fns={1: gated, 2: gated})
    router.start()
    futures = []
    try:
      shed = 0
      for i in range(40):
        try:
          futures.append(router.submit(_state(i)))
        except RequestRejected:
          shed += 1
      assert shed == 40 - 6  # cap enforced at the router...
      # ...and no replica's own admission control ever fired: the shed
      # happened BEFORE any replica queue was touched.
      assert registry.counter('serving/rejected').value == 0
      assert registry.counter('serving_fleet/rejected').value == shed
    finally:
      gate.set()
      for future in futures:
        future.result(timeout=10.0)  # admitted requests all complete
      router.stop()
      for handle in handles:
        handle.close()

  def test_no_replicas_is_a_runtime_error(self, registry):
    router = FleetRouter([], RouterConfig(), registry=registry)
    with pytest.raises(RuntimeError, match='no replicas'):
      router.submit(_state(1))


# -- replica death under load (ISSUE 14 satellite) ----------------------------


class TestReplicaDeathUnderLoad:

  def test_eject_retry_exactly_once_no_duplicate_executions(
      self, registry, tmp_path):
    """Kill one replica mid-stream: the router ejects it within one
    report window, its in-queue requests are retried EXACTLY ONCE on
    healthy peers, every request id is delivered exactly once, no
    request executes on two replicas, and doctor names the replica."""
    executed = {}  # value -> set of batch-call ids that scored it
    executed_lock = threading.Lock()
    call_ids = iter(range(10**9))
    wedge = threading.Event()
    # Set at TEARDOWN only (after every assertion), so the wedged serve
    # thread unblocks and close() does not wait out a long sleep.
    wedge_release = threading.Event()

    def make_batch_fn(replica_id):
      def batch_fn(variables, features, seed):
        if replica_id == 2 and wedge.is_set():
          wedge_release.wait(45.0)  # the "killed" replica: wedged
          raise RuntimeError('zombie batch discarded')  # never scores
        call_id = next(call_ids)
        with executed_lock:
          # Distinct-call counting: padding replicates a row WITHIN one
          # call, so a value scored twice in one call is padding, while
          # the same value in TWO calls is a duplicate execution.
          for value in set(np.asarray(features['x'])[:, 0].tolist()):
            executed.setdefault(value, set()).add(call_id)
        return _echo_batch_fn(variables, features, seed)
      return batch_fn

    def factory(replica_id, telemetry):
      return LocalReplicaHandle(replica_id, _make_server(
          registry, batch_fn=make_batch_fn(replica_id),
          telemetry=telemetry, report_interval_s=0.05))

    config = ServingFleetConfig(
        max_replicas=3, report_interval_s=0.1, health_interval_s=0.05,
        stale_after_s=0.3, drain_timeout_s=2.0)
    fleet = ServingFleet(factory, config, model_dir=str(tmp_path),
                         initial_replicas=3, registry=registry)
    fleet.start()
    results = []
    errors = []
    stop = threading.Event()
    lock = threading.Lock()
    values = iter(range(10**9))

    def client():
      while not stop.is_set():
        value = next(values)
        try:
          results.append((value,
                          fleet.select_action(_state(value),
                                              timeout_s=30.0)))
        except Exception as e:  # noqa: BLE001
          with lock:
            errors.append((value, e))

    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
      t.start()
    try:
      time.sleep(0.3)  # all three replicas serving
      wedge.set()  # replica 2 "dies" mid-stream
      deadline = time.monotonic() + 5.0
      while 2 not in fleet.router.ejected_ids() and \
          time.monotonic() < deadline:
        time.sleep(0.02)
      assert fleet.router.ejected_ids() == [2]
      time.sleep(0.4)  # keep serving on the peers post-ejection
    finally:
      stop.set()
      for t in threads:
        t.join()

    assert not errors  # retried requests succeeded on peers
    ids = [r.request_id for _, r in results]
    assert len(set(ids)) == len(ids)  # delivered exactly once
    retried = [r for _, r in results if r.retried]
    assert retried, 'the ejected replica\'s in-queue requests were ' \
        'never re-routed'
    assert all(r.replica != 2 for r in retried)
    # Zero duplicate executions: no request value was scored by two
    # DISTINCT batch calls (the wedged replica never finished its
    # batch; the retry path was the only execution).
    duplicates = {v: calls for v, calls in executed.items()
                  if len(calls) > 1}
    assert not duplicates, duplicates
    for value, result in results:
      np.testing.assert_allclose(result.outputs['y'], value * 2.0)

    # Doctor, while the fleet is live: CRITICAL naming replica 2.
    time.sleep(0.15)  # one more report window carrying the ejection
    findings = doctor.diagnose(str(tmp_path))
    crit = [f for f in findings if f['severity'] == doctor.CRITICAL
            and (f.get('detail') or {}).get('kind')
            == 'fleet_replica_ejected']
    assert crit and crit[0]['detail']['replicas'] == ['2']
    wedge_release.set()  # unblock the zombie so close() is fast
    fleet.close()

  def test_returned_replica_re_arms_into_rotation(self, registry):
    wedge = threading.Event()
    wedge.set()

    def flaky(variables, features, seed):
      if wedge.is_set():
        time.sleep(0.4)
      return _echo_batch_fn(variables, features, seed)

    fast = LocalReplicaHandle(1, _make_server(registry,
                                              report_interval_s=0.03))
    sick = LocalReplicaHandle(2, _make_server(registry, batch_fn=flaky,
                                              report_interval_s=0.03))
    router = FleetRouter([fast, sick],
                         RouterConfig(health_interval_s=0.03,
                                      stale_after_s=0.15),
                         registry=registry).start()
    try:
      sick.submit(_state(0))  # wedge replica 2's loop past staleness
      deadline = time.monotonic() + 5.0
      while 2 not in router.ejected_ids() and \
          time.monotonic() < deadline:
        time.sleep(0.02)
      assert router.ejected_ids() == [2]
      wedge.clear()  # the replica recovers and reports again
      deadline = time.monotonic() + 5.0
      while router.ejected_ids() and time.monotonic() < deadline:
        time.sleep(0.02)
      assert router.ejected_ids() == []  # re-armed, back in rotation
      assert registry.counter('serving_fleet/returns').value == 1
    finally:
      router.stop()
      fast.close()
      sick.close()


# -- fleet lifecycle: scaling + rolling swap ----------------------------------


class TestServingFleet:

  def _factory(self, registry, created=None, batch_ms=0.0):
    def factory(replica_id, telemetry):
      if created is not None:
        created.append(replica_id)

      def batch_fn(variables, features, seed):
        if batch_ms:
          time.sleep(batch_ms / 1e3)
        return _echo_batch_fn(variables, features, seed)

      return LocalReplicaHandle(replica_id, _make_server(
          registry, batch_fn=batch_fn, telemetry=telemetry,
          max_queue_depth=8))
    return factory

  def test_scale_up_and_down_with_zero_drops(self, registry, tmp_path):
    created = []
    config = ServingFleetConfig(max_replicas=3, report_interval_s=0.1,
                                health_interval_s=0.05,
                                drain_timeout_s=5.0)
    fleet = ServingFleet(self._factory(registry, created), config,
                         model_dir=str(tmp_path), initial_replicas=1,
                         registry=registry)
    with fleet:
      replica_id, ready_s = fleet.scale_up(reason='test')
      assert replica_id == 2 and ready_s >= 0.0
      assert fleet.last_scaleup_seconds == ready_s
      assert fleet.router.replica_ids() == [1, 2]
      results, errors = _drive(fleet.submit, 40, concurrency=8)
      assert not errors and len(results) == 40
      retired = fleet.scale_down(reason='test')
      assert retired in (1, 2)
      assert len(fleet.router.replica_ids()) == 1
      # The retired replica drained: every accepted request answered.
      results, errors = _drive(fleet.submit, 10, concurrency=4)
      assert not errors
      with pytest.raises(RuntimeError, match='min_replicas'):
        fleet.scale_down()
    records = read_telemetry(str(tmp_path / 'telemetry.0.jsonl'))
    scales = [r for r in records if r['kind'] == 'serving_fleet_scale']
    assert [s['direction'] for s in scales] == ['up', 'down']
    assert scales[0]['time_to_ready_s'] >= 0.0
    assert records[-1]['kind'] == 'serving_fleet_stop'

  def test_scale_up_refused_at_max(self, registry):
    config = ServingFleetConfig(max_replicas=1, report_interval_s=0.1)
    fleet = ServingFleet(self._factory(registry), config,
                         initial_replicas=1, registry=registry)
    with fleet:
      with pytest.raises(RuntimeError, match='max_replicas'):
        fleet.scale_up()

  def test_autoscaler_follows_the_demand_curve(self, registry):
    created = []
    config = ServingFleetConfig(
        min_replicas=1, max_replicas=3, autoscale=True,
        scale_up_at=0.4, scale_down_at=0.05, scale_windows=2,
        report_interval_s=0.08, health_interval_s=0.05,
        drain_timeout_s=5.0)
    fleet = ServingFleet(self._factory(registry, created, batch_ms=30.0),
                         config, initial_replicas=1, registry=registry)
    futures = []
    with fleet:
      stop_pump = threading.Event()

      def pump():
        # Sustained demand: keep the fleet's queues pressurized so
        # utilization stays above scale_up_at across windows.
        while not stop_pump.is_set():
          try:
            futures.append(fleet.submit(_state(1)))
          except RequestRejected:
            pass  # saturated IS the demand signal
          time.sleep(0.002)

      pump_thread = threading.Thread(target=pump)
      pump_thread.start()
      deadline = time.monotonic() + 10.0
      while len(fleet.router.replica_ids()) < 3 and \
          time.monotonic() < deadline:
        time.sleep(0.05)
      stop_pump.set()
      pump_thread.join()
      assert len(fleet.router.replica_ids()) == 3  # scaled up on load
      for future in futures:
        future.result(timeout=30.0)  # every admitted request answered
      futures = []
      deadline = time.monotonic() + 10.0
      while len(fleet.router.replica_ids()) > 1 and \
          time.monotonic() < deadline:
        time.sleep(0.05)
      assert len(fleet.router.replica_ids()) == 1  # idled back to min
    assert registry.counter('serving_fleet/scale_ups').value == 2
    assert registry.counter('serving_fleet/scale_downs').value == 2

  def test_rolling_swap_under_load_both_versions_serve(self, registry,
                                                       tmp_path):
    def slowish(variables, features, seed):
      time.sleep(0.002)
      return _echo_batch_fn(variables, features, seed)

    def factory(replica_id, telemetry):
      return LocalReplicaHandle(replica_id, _make_server(
          registry, batch_fn=slowish, telemetry=telemetry))

    config = ServingFleetConfig(max_replicas=3, report_interval_s=0.05,
                                health_interval_s=0.05)
    fleet = ServingFleet(factory, config, model_dir=str(tmp_path),
                         initial_replicas=3, registry=registry)
    results = []
    failures = []
    stop = threading.Event()

    def client(value):
      while not stop.is_set():
        try:
          results.append((value,
                          fleet.select_action(_state(value),
                                              timeout_s=10.0)))
        except Exception as e:  # noqa: BLE001
          failures.append(e)

    with fleet:
      threads = [threading.Thread(target=client, args=(i,))
                 for i in range(8)]
      for t in threads:
        t.start()
      time.sleep(0.15)
      wave = fleet.rolling_swap({'scale': 3.0, 'version': 2}, 2,
                                pause_s=0.02)
      time.sleep(0.15)
      stop.set()
      for t in threads:
        t.join()
      assert wave == [1, 2, 3]  # one replica at a time, in order
      assert not failures  # zero failed requests fleet-wide
      versions = {r.version for _, r in results}
      assert versions == {1, 2}  # both versions actually served
      for value, result in results:
        scale = {1: 2.0, 2: 3.0}[result.version]
        np.testing.assert_allclose(result.outputs['y'], value * scale)
        assert int(result.outputs['version']) == result.version
    records = read_telemetry(str(tmp_path / 'telemetry.0.jsonl'))
    swaps = [r for r in records if r['kind'] == 'serving_fleet_swap']
    assert len(swaps) == 1 and swaps[0]['wave'] == [1, 2, 3]


# -- post-review regression tests ---------------------------------------------


class TestReviewFixes:

  def test_rearmed_replica_is_reconciled_onto_the_swap_version(
      self, registry):
    """A replica ejected while a rolling wave walked the fleet missed
    its swap; on re-arm the fleet must bring it onto the new version
    before it serves stale weights."""
    wedge = threading.Event()

    def gated(variables, features, seed):
      if wedge.is_set():
        wedge_released.wait(10.0)
      return _echo_batch_fn(variables, features, seed)

    wedge_released = threading.Event()

    def factory(replica_id, telemetry):
      batch_fn = gated if replica_id == 2 else None
      return LocalReplicaHandle(replica_id, _make_server(
          registry, batch_fn=batch_fn, telemetry=telemetry,
          report_interval_s=0.03))

    config = ServingFleetConfig(max_replicas=2, report_interval_s=0.1,
                                health_interval_s=0.03,
                                stale_after_s=0.15, drain_timeout_s=2.0)
    fleet = ServingFleet(factory, config, initial_replicas=2,
                         registry=registry)
    with fleet:
      wedge.set()
      fleet.router.handle(2).submit(_state(0))  # wedge replica 2
      deadline = time.monotonic() + 5.0
      while 2 not in fleet.router.ejected_ids() and \
          time.monotonic() < deadline:
        time.sleep(0.02)
      assert fleet.router.ejected_ids() == [2]
      wave = fleet.rolling_swap({'scale': 5.0, 'version': 2}, 2)
      assert wave == [1]  # the ejected replica missed the wave
      wedge.clear()
      wedge_released.set()  # replica 2 recovers
      deadline = time.monotonic() + 5.0
      while fleet.router.ejected_ids() and time.monotonic() < deadline:
        time.sleep(0.02)
      assert fleet.router.ejected_ids() == []
      # The re-armed replica was reconciled onto v2, not left on v1.
      assert fleet.router.handle(2).server.params_version == 2
      result = fleet.router.handle(2).submit(_state(3)).result(
          timeout=5.0)
      assert result.version == 2
      np.testing.assert_allclose(result.outputs['y'], 15.0)

  def test_admitted_request_bypasses_cap_on_replica_level_retry(
      self, registry):
    """Admission is a promise: a request that passed the router's cap
    and then hit a replica-level rejection must retry on a peer even if
    the fleet filled up in between — never be shed after the fact."""
    real = LocalReplicaHandle(2, _make_server(registry))
    router_box = []

    class FillingRejectingHandle(ReplicaHandle):
      replica_id = 1

      def submit(self, features):
        # Simulate "the fleet filled between this request's cap check
        # and its enqueue": occupy the peer's router-side slot, then
        # reject at the replica level.
        with router_box[0]._lock:
          router_box[0]._outstanding[2][999_999] = object()
        raise RequestRejected('queue filled between check and enqueue')

      def snapshot(self):
        return {'alive': True, 'heartbeat_age_s': 0.0,
                'queue_depth': 0.0, 'max_queue_depth': 64,
                'p99_ms': None, 'requests': None,
                'requests_per_sec': None, 'over_slo': False,
                'slo_ms': 33.0, 'params_version': 1}

    router = FleetRouter([FillingRejectingHandle(), real],
                         RouterConfig(health_interval_s=10.0,
                                      max_fleet_pending=1),
                         registry=registry)
    router_box.append(router)
    try:
      result = router.submit(_state(4)).result(timeout=10.0)
      # Retried onto the real replica despite total >= cap at retry
      # time; the router never shed the admitted request.
      assert result.retried and result.replica == 2
      np.testing.assert_allclose(result.outputs['y'], 8.0)
      assert registry.counter('serving_fleet/rejected').value == 0
    finally:
      with router._lock:
        router._outstanding[2].pop(999_999, None)
      real.close()

  def test_failed_spawn_leaks_no_phantom_replica_stream(self, registry,
                                                        tmp_path):
    fail = threading.Event()

    def factory(replica_id, telemetry):
      if fail.is_set():
        raise RuntimeError('artifact store exploded')
      return LocalReplicaHandle(replica_id, _make_server(
          registry, telemetry=telemetry))

    config = ServingFleetConfig(max_replicas=3, report_interval_s=0.5)
    fleet = ServingFleet(factory, config, model_dir=str(tmp_path),
                         initial_replicas=1, registry=registry)
    with fleet:
      fail.set()
      with pytest.raises(RuntimeError, match='exploded'):
        fleet.scale_up()
      # No open logger, no 0-byte phantom stream for the dead id.
      assert 2 not in fleet._replica_telemetry
      assert not (tmp_path / 'telemetry.2.jsonl').exists()
      fail.clear()
      replica_id, _ = fleet.scale_up()  # the fleet recovers; id burned
      assert replica_id == 3
      results, errors = _drive(fleet.submit, 10, concurrency=4)
      assert not errors
    assert sorted(discover_hosts(str(tmp_path))) == [0, 1, 3]


class TestReviewFixesRound2:

  class _AsyncSheddingHandle(ReplicaHandle):
    """An HTTP-shaped replica: rejections arrive IN the future, never
    as a synchronous raise (the thread-pool submit contract)."""

    replica_id = 1

    def __init__(self):
      self.sheds = 0

    def submit(self, features):
      from concurrent.futures import Future
      self.sheds += 1
      future = Future()
      future.set_exception(RequestRejected('remote replied 503'))
      return future

    def snapshot(self):
      return {'alive': True, 'heartbeat_age_s': 0.0, 'queue_depth': 0.0,
              'max_queue_depth': 64, 'p99_ms': None, 'requests': None,
              'requests_per_sec': None, 'over_slo': False,
              'slo_ms': 33.0, 'params_version': 1}

  def test_async_replica_rejection_retries_on_a_peer(self, registry):
    """An HTTP replica's shed resolves the pool future with
    RequestRejected instead of raising synchronously — the router must
    give it the same one-retry-on-a-peer semantics."""
    shedder = self._AsyncSheddingHandle()
    real = LocalReplicaHandle(2, _make_server(registry))
    router = FleetRouter([shedder, real],
                         RouterConfig(health_interval_s=10.0),
                         registry=registry)
    try:
      result = router.submit(_state(3)).result(timeout=10.0)
      assert shedder.sheds == 1  # the shedder was tried...
      assert result.retried and result.replica == 2  # ...and retried
      np.testing.assert_allclose(result.outputs['y'], 6.0)
      assert registry.counter('serving_fleet/retries').value == 1
    finally:
      real.close()

  def test_fresh_replica_enters_at_peer_mean_weight(self, registry):
    handles = [LocalReplicaHandle(i, _make_server(registry))
               for i in (1, 2)]
    router = FleetRouter(handles, RouterConfig(health_interval_s=10.0),
                         registry=registry)
    try:
      for i in range(20):
        router.submit(_state(i)).result(timeout=10.0)
      time.sleep(0.1)
      router.observe()  # normalizes weights to sum 1 (~0.5 each)
      late = LocalReplicaHandle(3, _make_server(registry))
      handles.append(late)
      router.add_replica(late)
      with router._lock:
        weights = dict(router._weights)
      # The newcomer must NOT enter at 1.0 against ~0.5 peers (it would
      # absorb nearly all dispatches until the next health pass).
      assert weights[3] <= max(weights[1], weights[2]) * 1.5
    finally:
      for handle in handles:
        handle.close()

  def test_close_after_failed_start_releases_everything(self, registry,
                                                        tmp_path):
    spawned = []

    def factory(replica_id, telemetry):
      if replica_id == 2:
        raise RuntimeError('replica 2 factory exploded')
      handle = LocalReplicaHandle(replica_id, _make_server(
          registry, telemetry=telemetry))
      spawned.append(handle)
      return handle

    config = ServingFleetConfig(max_replicas=3, report_interval_s=0.5)
    fleet = ServingFleet(factory, config, model_dir=str(tmp_path),
                         initial_replicas=3, registry=registry)
    with pytest.raises(RuntimeError, match='exploded'):
      fleet.start()
    # start()'s failure path closed the fleet: replica 1's server is
    # down, no stream left open, close() again is a no-op.
    assert spawned and not spawned[0].server.alive
    assert fleet._replica_telemetry == {}
    fleet.close()

  def test_close_on_never_started_fleet_is_safe(self, registry,
                                                tmp_path):
    fleet = ServingFleet(
        lambda rid, t: (_ for _ in ()).throw(AssertionError('no spawn')),
        ServingFleetConfig(), model_dir=str(tmp_path), registry=registry)
    fleet.close()  # releases the stream-0 logger; never raises
    records = read_telemetry(str(tmp_path / 'telemetry.0.jsonl'))
    # Never started: no fabricated start/stop lifecycle records.
    assert records == []

  def test_burned_ids_keep_identity_self_consistent(self, registry,
                                                    tmp_path):
    def factory(replica_id, telemetry):
      return LocalReplicaHandle(replica_id, _make_server(
          registry, telemetry=telemetry))

    config = ServingFleetConfig(min_replicas=1, max_replicas=2,
                                report_interval_s=0.5)
    fleet = ServingFleet(factory, config, model_dir=str(tmp_path),
                         initial_replicas=2, registry=registry)
    with fleet:
      fleet.scale_down(replica_id=1)
      replica_id, _ = fleet.scale_up()  # ids never reused: 3 > max=2
      assert replica_id == 3
      fleet.select_action(_state(1), timeout_s=10.0)
      time.sleep(0.1)
    records = read_telemetry(str(tmp_path / 'telemetry.3.jsonl'))
    assert records, 'burned-id replica stream missing'
    for record in records:
      # The stamped identity never contradicts itself.
      assert record['process_index'] < record['process_count']


# -- per-replica telemetry isolation (ISSUE 14 satellite) ---------------------


class TestFleetTelemetryLayout:

  def _run_fleet(self, registry, model_dir):
    def factory(replica_id, telemetry):
      return LocalReplicaHandle(replica_id, _make_server(
          registry, telemetry=telemetry))

    config = ServingFleetConfig(max_replicas=3, report_interval_s=0.05,
                                health_interval_s=0.05)
    fleet = ServingFleet(factory, config, model_dir=model_dir,
                         initial_replicas=2, registry=registry)
    with fleet:
      results, errors = _drive(fleet.submit, 30, concurrency=6)
      assert not errors
      time.sleep(0.15)  # replica + fleet report windows close

  def test_indexed_streams_router_owns_stream_zero(self, registry,
                                                   tmp_path):
    self._run_fleet(registry, str(tmp_path))
    hosts = discover_hosts(str(tmp_path))
    assert sorted(hosts) == [0, 1, 2]
    router_records = read_telemetry(hosts[0]['telemetry'])
    kinds = {r['kind'] for r in router_records}
    assert 'serving_fleet' in kinds and 'serving' not in kinds
    for replica in (1, 2):
      replica_records = read_telemetry(hosts[replica]['telemetry'])
      kinds = {r['kind'] for r in replica_records}
      assert 'serving' in kinds and 'serving_fleet' not in kinds
      # Every record stamped with the replica's stream identity.
      assert all(r['process_index'] == replica for r in replica_records)

  def test_replica_ids_are_one_based(self):
    with pytest.raises(ValueError, match='1-based'):
      replica_host_meta(0, 4)

  def test_doctor_judges_the_router_stream(self, registry, tmp_path):
    self._run_fleet(registry, str(tmp_path))
    findings = doctor.diagnose(str(tmp_path))
    assert not any(f['severity'] == doctor.CRITICAL for f in findings)
    healthy = [f for f in findings
               if (f.get('detail') or {}).get('kind') == 'fleet_healthy']
    assert healthy and healthy[0]['detail']['replica_count'] == 2

  def test_summarize_prints_per_replica_table(self, registry, tmp_path):
    self._run_fleet(registry, str(tmp_path))
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_telemetry'),
         'summarize', str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stdout + result.stderr
    assert 'serving fleet: 2 replicas' in result.stdout
    assert 'replica' in result.stdout and 'weight' in result.stdout
    # --json carries the raw record for automation.
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_telemetry'),
         'summarize', '--json', str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    payload = json.loads(result.stdout)
    assert payload['serving_fleet']['replica_count'] == 2
    assert set(payload['serving_fleet']['replicas']) == {'1', '2'}


# -- fleet HTTP frontend (ISSUE 14 satellite: 503 on router shed) -------------


class TestFleetHttpFrontend:

  def test_round_trip_and_503_on_fleet_wide_shed(self, registry):
    from tensor2robot_tpu.serving.frontend import build_http_server

    gate = threading.Event()

    def gated(variables, features, seed):
      gate.wait(10.0)
      return _echo_batch_fn(variables, features, seed)

    def factory(replica_id, telemetry):
      return LocalReplicaHandle(replica_id, _make_server(
          registry, batch_fn=gated))

    config = ServingFleetConfig(max_replicas=2, report_interval_s=0.5,
                                health_interval_s=0.1,
                                max_fleet_pending=4, drain_timeout_s=15.0)
    fleet = ServingFleet(factory, config, initial_replicas=2,
                         registry=registry)
    fleet.start()
    httpd, port = build_http_server(fleet, port=0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
      # Saturate the fleet-wide cap with the batchers gated shut.
      futures = [fleet.submit(_state(i)) for i in range(4)]
      conn = http.client.HTTPConnection('127.0.0.1', port, timeout=10)
      conn.request('POST', '/v1/select_action',
                   body=json.dumps({'features': {'x': [1.0, 2.0, 3.0]}}),
                   headers={'Content-Type': 'application/json'})
      response = conn.getresponse()
      body = json.loads(response.read())
      conn.close()
      # The regression this satellite names: a ROUTER-level shed must be
      # an explicit 503 with a JSON body ("retry elsewhere"), never a
      # dropped connection.
      assert response.status == 503
      assert 'shed at the router' in body['error']

      gate.set()
      for future in futures:
        future.result(timeout=10.0)
      conn = http.client.HTTPConnection('127.0.0.1', port, timeout=10)
      conn.request('POST', '/v1/select_action',
                   body=json.dumps({'features': {'x': [1.0, 2.0, 3.0]}}),
                   headers={'Content-Type': 'application/json'})
      response = conn.getresponse()
      body = json.loads(response.read())
      assert response.status == 200
      np.testing.assert_allclose(body['outputs']['y'], [2.0, 4.0, 6.0])
      conn.request('GET', '/healthz')
      health = json.loads(conn.getresponse().read())
      conn.close()
      assert health['replica_count'] == 2
      assert health['rejected_total'] >= 1
    finally:
      gate.set()
      httpd.shutdown()
      fleet.close()


# -- doctor fixtures + bench schema (ISSUE 14 satellites) ---------------------


def _load_gate_module():
  path = os.path.join(REPO_ROOT, 'bin', 'check_serving_slo')
  loader = importlib.machinery.SourceFileLoader('check_serving_slo', path)
  spec = importlib.util.spec_from_loader('check_serving_slo', loader)
  module = importlib.util.module_from_spec(spec)
  loader.exec_module(module)
  return module


class TestFleetDoctor:

  def test_breaching_replica_is_named_critical(self, tmp_path):
    _load_gate_module().write_fleet_run(str(tmp_path), breach_replica=2)
    findings = doctor.diagnose(str(tmp_path))
    crit = [f for f in findings if f['severity'] == doctor.CRITICAL
            and (f.get('detail') or {}).get('kind')
            == 'fleet_replica_over_slo']
    assert crit and crit[0]['detail']['replica'] == '2'
    assert crit[0]['detail']['p99_ms'] == 48.2

  def test_ejected_replica_is_named_critical(self, tmp_path):
    _load_gate_module().write_fleet_run(str(tmp_path), ejected_replica=3)
    findings = doctor.diagnose(str(tmp_path))
    crit = [f for f in findings if f['severity'] == doctor.CRITICAL
            and (f.get('detail') or {}).get('kind')
            == 'fleet_replica_ejected']
    assert crit and crit[0]['detail']['replicas'] == ['3']

  def test_clean_fleet_is_healthy_and_stop_downgrades(self, tmp_path):
    _load_gate_module().write_fleet_run(str(tmp_path), stopped=True)
    findings = doctor.diagnose(str(tmp_path))
    assert not any(f['severity'] in (doctor.CRITICAL, doctor.WARNING)
                   for f in findings)
    assert any((f.get('detail') or {}).get('kind') == 'fleet_healthy'
               for f in findings)

  def test_stopped_fleet_with_breach_is_warning_not_critical(
      self, tmp_path):
    _load_gate_module().write_fleet_run(str(tmp_path), breach_replica=1,
                                        stopped=True)
    findings = doctor.diagnose(str(tmp_path))
    assert not any(f['severity'] == doctor.CRITICAL for f in findings)
    warn = [f for f in findings if f['severity'] == doctor.WARNING
            and (f.get('detail') or {}).get('kind')
            == 'fleet_replica_over_slo']
    assert warn and warn[0]['detail']['replica'] == '1'
