"""Telemetry-layer coverage (ISSUE 3 acceptance tests).

Registry thread-safety under concurrent writers, histogram percentile
math against numpy, span histograms, goodput fractions over
a real (CPU) training run landing in BOTH TensorBoard events and
telemetry.jsonl, predictor latency histograms, and the t2r_telemetry
CLI smoke test.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading

import jax
import numpy as np
import pytest

from tensor2robot_tpu import observability as obs
from tensor2robot_tpu.observability import goodput as goodput_lib
from tensor2robot_tpu.trainer import Trainer
from tensor2robot_tpu.trainer.metrics import read_events
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_registry():
  """Every test gets its own default registry; the process one survives."""
  previous = obs.set_registry(obs.TelemetryRegistry())
  yield obs.get_registry()
  obs.set_registry(previous)


@pytest.fixture(scope='module')
def trained_run():
  """One CPU training run whose model_dir later tests read files from."""
  model_dir = tempfile.mkdtemp()
  model = MockT2RModel()
  generator = MockInputGenerator(batch_size=8)
  trainer = Trainer(model, model_dir, save_checkpoints_steps=3,
                    async_checkpoints=False, log_every_n_steps=3)
  trainer.train(generator, max_train_steps=6)
  trainer.close()
  return model_dir


# -- registry -----------------------------------------------------------------


class TestRegistry:

  def test_counter_gauge_basics(self, fresh_registry):
    counter = fresh_registry.counter('c')
    counter.inc()
    counter.inc(2.5)
    assert counter.value == 3.5
    with pytest.raises(ValueError):
      counter.inc(-1)
    gauge = fresh_registry.gauge('g')
    gauge.set(7)
    assert gauge.value == 7.0

  def test_same_name_same_kind_returns_same_instrument(self, fresh_registry):
    assert fresh_registry.counter('x') is fresh_registry.counter('x')

  def test_kind_conflict_raises(self, fresh_registry):
    fresh_registry.counter('x')
    with pytest.raises(ValueError, match='already registered'):
      fresh_registry.gauge('x')

  def test_bounds_and_label_conflicts_raise(self, fresh_registry):
    fresh_registry.histogram('h', bounds=(1.0, 2.0))
    # Unconstrained lookup of an existing histogram is fine...
    assert fresh_registry.histogram('h') is fresh_registry.histogram(
        'h', bounds=(1.0, 2.0))
    # ...but different EXPLICIT bounds would silently corrupt percentiles.
    with pytest.raises(ValueError, match='bounds'):
      fresh_registry.histogram('h', bounds=(10.0, 20.0))
    fresh_registry.counter_family('fam', ('a', 'b'))
    with pytest.raises(ValueError, match='labels'):
      fresh_registry.counter_family('fam', ('a',))

  def test_labeled_series_export_as_path_segments(self, fresh_registry):
    family = fresh_registry.counter_family('req', ('predictor',))
    family.series('CheckpointPredictor').inc(4)
    assert fresh_registry.scalars()['req/CheckpointPredictor'] == 4.0
    with pytest.raises(ValueError, match='label value'):
      family.series('a', 'b')

  def test_thread_safety_under_concurrent_writers(self, fresh_registry):
    counter = fresh_registry.counter('hits')
    histogram = fresh_registry.histogram('lat', bounds=(1.0, 2.0, 4.0))
    family = fresh_registry.counter_family('fam', ('k',))
    per_thread, n_threads = 5000, 8

    def writer(tid):
      series = family.series(str(tid % 2))
      for i in range(per_thread):
        counter.inc()
        histogram.record(float(i % 5))
        series.inc()

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    total = per_thread * n_threads
    assert counter.value == total
    assert histogram.count == total
    assert (family.series('0').value + family.series('1').value) == total

  def test_histogram_percentiles_match_numpy(self, fresh_registry):
    bucket_width = 2.0
    histogram = fresh_registry.histogram(
        'h', bounds=np.arange(bucket_width, 100.0 + bucket_width,
                              bucket_width))
    rng = np.random.RandomState(42)
    values = rng.uniform(0.0, 100.0, size=20000)
    for value in values:
      histogram.record(float(value))
    for p in (5.0, 50.0, 90.0, 95.0, 99.0):
      estimate = histogram.percentile(p)
      exact = float(np.percentile(values, p))
      # Fixed buckets bound the error to one bucket width.
      assert abs(estimate - exact) <= bucket_width, (p, estimate, exact)
    assert histogram.count == values.size
    np.testing.assert_allclose(histogram.mean, values.mean(), rtol=1e-6)

  def test_histogram_single_value_and_empty(self, fresh_registry):
    histogram = fresh_registry.histogram('h', bounds=(1.0, 10.0, 100.0))
    assert histogram.percentile(50.0) == 0.0  # empty
    histogram.record(42.0)
    assert histogram.percentile(50.0) == 42.0  # min==max clamp

  def test_snapshot_delta(self, fresh_registry):
    counter = fresh_registry.counter('c')
    histogram = fresh_registry.histogram('h', bounds=(1.0, 2.0))
    counter.inc(3)
    histogram.record(0.5)
    before = fresh_registry.snapshot()
    counter.inc(2)
    histogram.record(1.5)
    delta = obs.snapshot_delta(before, fresh_registry.snapshot())
    assert delta['counters']['c'] == 2.0
    assert delta['histograms']['h']['count'] == 1
    assert delta['histograms']['h']['counts'] == [0, 1, 0]

  def test_exponential_buckets_validation(self):
    assert obs.exponential_buckets(1.0, 2.0, 3) == (1.0, 2.0, 4.0)
    with pytest.raises(ValueError):
      obs.exponential_buckets(0.0, 2.0, 3)


# -- spans --------------------------------------------------------------------


class TestSpans:

  def test_span_records_elapsed_into_histogram(self, fresh_registry):
    with obs.span('unit.test') as sp:
      pass
    assert sp.elapsed >= 0.0
    scalars = fresh_registry.scalars()
    assert scalars['span/unit.test/count'] == 1.0

  def test_span_decorator(self, fresh_registry):

    @obs.span('unit.decorated')
    def work(x):
      return x + 1

    assert work(1) == 2
    assert work(2) == 3
    assert fresh_registry.scalars()['span/unit.decorated/count'] == 2.0


# -- goodput ------------------------------------------------------------------


class TestGoodputTracker:

  def test_fractions_partition_to_one(self):
    tracker = obs.GoodputTracker()
    tracker.add(goodput_lib.PRODUCTIVE, 6.0)
    tracker.add(goodput_lib.DATA, 2.0)
    tracker.add(goodput_lib.CHECKPOINT, 1.0)
    tracker.add(goodput_lib.RETRY, 1.0)
    fractions = tracker.fractions()
    assert fractions == {'productive': 0.6, 'data': 0.2,
                         'checkpoint': 0.1, 'retry': 0.1}
    assert sum(fractions.values()) == pytest.approx(1.0)
    scalars = tracker.scalars()
    assert scalars['goodput/total_seconds'] == pytest.approx(10.0)
    assert scalars['goodput/data_fraction'] == pytest.approx(0.2)

  def test_empty_tracker_and_bad_category(self):
    tracker = obs.GoodputTracker()
    assert sum(tracker.fractions().values()) == 0.0
    with pytest.raises(ValueError, match='category'):
      tracker.add('naptime', 1.0)
    tracker.add(goodput_lib.DATA, -0.5)  # clock jitter clamps to zero
    assert tracker.total_seconds() == 0.0


# -- telemetry.jsonl + heartbeat ---------------------------------------------


class TestTelemetryFile:

  def test_round_trip(self, tmp_path):
    logger = obs.TelemetryLogger(str(tmp_path))
    logger.log('run_start', step=0, max_train_steps=10)
    logger.log('train', step=5, loss=0.25,
               goodput={'productive': 0.9, 'data': 0.1})
    logger.log('note')  # step defaults to null
    logger.close()
    records = obs.read_telemetry(str(tmp_path))
    assert [r['kind'] for r in records] == ['run_start', 'train', 'note']
    assert records[1]['loss'] == 0.25
    assert records[1]['goodput'] == {'productive': 0.9, 'data': 0.1}
    assert records[2]['step'] is None
    assert all('time' in r for r in records)

  def test_append_only_across_logger_instances(self, tmp_path):
    first = obs.TelemetryLogger(str(tmp_path))
    first.log('run_start', step=0)
    first.close()
    second = obs.TelemetryLogger(str(tmp_path))  # the restarted process
    second.log('run_start', step=7)
    second.close()
    kinds = [(r['kind'], r['step'])
             for r in obs.read_telemetry(str(tmp_path))]
    assert kinds == [('run_start', 0), ('run_start', 7)]

  def test_torn_tail_is_dropped_interior_damage_raises(self, tmp_path):
    path = tmp_path / obs.TELEMETRY_FILENAME
    good = json.dumps({'time': 1.0, 'kind': 'train', 'step': 1})
    path.write_text(good + '\n{"torn": tru')
    records = obs.read_telemetry(str(tmp_path))
    assert len(records) == 1  # killed-mid-append tail is not an error
    path.write_text('{"torn": tru\n' + good + '\n')
    with pytest.raises(ValueError, match='malformed telemetry'):
      obs.read_telemetry(str(tmp_path))

  def test_heartbeat_atomic_replace(self, tmp_path):
    logger = obs.TelemetryLogger(str(tmp_path))
    logger.heartbeat(3)
    logger.heartbeat(9, phase='train')
    logger.close()
    beat = obs.read_heartbeat(str(tmp_path))
    assert beat['step'] == 9
    assert beat['phase'] == 'train'
    assert beat['pid'] == os.getpid()
    assert not os.path.exists(
        os.path.join(str(tmp_path), obs.HEARTBEAT_FILENAME + '.tmp'))


class TestTelemetryRotation:

  def _logger(self, tmp_path, **kwargs):
    kwargs.setdefault('max_bytes', 4096)
    kwargs.setdefault('max_rotated', 2)
    return obs.TelemetryLogger(str(tmp_path), **kwargs)

  def test_live_file_stays_under_cap(self, tmp_path):
    logger = self._logger(tmp_path)
    for step in range(200):
      logger.log('train', step=step, payload='x' * 100)
    logger.close()
    live = os.path.join(str(tmp_path), obs.TELEMETRY_FILENAME)
    assert os.path.getsize(live) <= 4096
    assert os.path.exists(live + '.1')
    assert os.path.exists(live + '.2')
    assert not os.path.exists(live + '.3')  # max_rotated bounds disk

  def test_read_telemetry_stitches_rotated_history_in_order(self, tmp_path):
    logger = self._logger(tmp_path)
    n = 120
    for step in range(n):
      logger.log('train', step=step, payload='x' * 100)
    logger.close()
    live = os.path.join(str(tmp_path), obs.TELEMETRY_FILENAME)
    assert os.path.exists(live + '.1'), 'cap never reached: test is vacuous'
    records = obs.read_telemetry(str(tmp_path))
    steps = [r['step'] for r in records]
    # Oldest-first across generations, monotone, and ending at the live
    # tail; the head may have fallen off with the oldest generation.
    assert steps == sorted(steps)
    assert steps[-1] == n - 1
    assert len(steps) == len(set(steps))

  def test_rotation_happens_at_line_boundaries(self, tmp_path):
    logger = self._logger(tmp_path)
    for step in range(100):
      logger.log('train', step=step, payload='y' * 150)
    logger.close()
    live = os.path.join(str(tmp_path), obs.TELEMETRY_FILENAME)
    for path in (live, live + '.1', live + '.2'):
      with open(path, encoding='utf-8') as f:
        for line in f.read().splitlines():
          json.loads(line)  # every line in every generation is complete

  def test_rotation_disabled_with_none(self, tmp_path):
    logger = obs.TelemetryLogger(str(tmp_path), max_bytes=None)
    for step in range(100):
      logger.log('train', step=step, payload='z' * 200)
    logger.close()
    live = os.path.join(str(tmp_path), obs.TELEMETRY_FILENAME)
    assert not os.path.exists(live + '.1')
    assert len(obs.read_telemetry(str(tmp_path))) == 100

  def test_one_oversized_record_still_lands(self, tmp_path):
    # A single record larger than max_bytes must be written, not spin
    # the rotator: a fresh file always takes at least one record.
    logger = self._logger(tmp_path, max_bytes=256)
    logger.log('train', step=0, payload='w' * 1000)
    logger.log('train', step=1, payload='w' * 1000)
    logger.close()
    records = obs.read_telemetry(str(tmp_path))
    assert [r['step'] for r in records] == [0, 1]


# -- the trainer's goodput breakdown (acceptance criterion) -------------------


class TestTrainingGoodput:

  def test_events_carry_goodput_fractions_summing_to_one(self, trained_run):
    tags = {}
    for _, step_tags in read_events(trained_run):
      tags.update(step_tags)
    fractions = {category: tags['goodput/{}_fraction'.format(category)]
                 for category in goodput_lib.CATEGORIES}
    assert sum(fractions.values()) == pytest.approx(1.0, abs=1e-3)
    assert fractions['productive'] > 0.0
    # Span histograms ride the same export pipeline.
    assert tags['span/train.step/count'] >= 6.0
    assert tags['span/data.next/p50'] >= 0.0
    assert tags['span/ckpt.save/count'] >= 1.0

  def test_telemetry_jsonl_carries_the_same_breakdown(self, trained_run):
    records = obs.read_telemetry(trained_run)
    kinds = [r['kind'] for r in records]
    assert kinds[0] == 'run_start'
    assert 'train' in kinds
    assert kinds[-1] == 'run_end'
    final = records[-1]
    assert final['step'] == 6
    assert set(final['goodput']) == set(goodput_lib.CATEGORIES)
    assert sum(final['goodput'].values()) == pytest.approx(1.0, abs=1e-3)
    assert sum(final['goodput_seconds'].values()) > 0.0

  def test_heartbeat_reflects_final_step(self, trained_run):
    beat = obs.read_heartbeat(trained_run)
    assert beat is not None
    assert beat['step'] == 6
    assert beat['pid'] == os.getpid()

  def test_last_goodput_exposed_on_trainer(self, tmp_path):
    trainer = Trainer(MockT2RModel(), str(tmp_path / 'run'),
                      async_checkpoints=False, write_metrics=False,
                      save_checkpoints_steps=10**9)
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=2)
    trainer.close()
    tracker = trainer.last_goodput
    assert tracker is not None
    assert sum(tracker.fractions().values()) == pytest.approx(1.0)
    # write_metrics=False: no telemetry files, goodput still tracked.
    assert not os.path.exists(
        os.path.join(str(tmp_path / 'run'), obs.TELEMETRY_FILENAME))


# -- reliability counters through the registry --------------------------------


class TestReliabilityCounters:

  def test_quarantine_counts_through_registry(self, fresh_registry):
    from tensor2robot_tpu.reliability import quarantine

    record_quarantine = quarantine.RecordQuarantine(
        max_corrupt_records=10, max_corrupt_records_per_file=10)
    record_quarantine.record_skipped('/data/shard-0', 'bad crc')
    record_quarantine.record_skipped('/data/shard-0', 'bad crc')
    record_quarantine.file_abandoned('/data/shard-0', 'framing lost')
    assert fresh_registry.counter(
        quarantine.RECORDS_SKIPPED_COUNTER).value == 2.0
    assert fresh_registry.counter(
        quarantine.FILES_ABANDONED_COUNTER).value == 1.0
    metrics = quarantine.aggregate_metrics()
    assert metrics['data/corrupt_records_skipped'] == 2.0
    assert metrics['data/corrupt_files_abandoned'] == 1.0
    quarantine.reset_aggregate_metrics()
    assert fresh_registry.counter(
        quarantine.RECORDS_SKIPPED_COUNTER).value == 0.0

  def test_io_retries_count_by_site(self, fresh_registry):
    from tensor2robot_tpu.reliability.retry import RetryPolicy, retry

    attempts = []

    def flaky():
      if len(attempts) < 2:
        attempts.append(1)
        raise IOError('transient blip')
      return 'ok'

    result = retry(flaky,
                   RetryPolicy(max_attempts=3, base_delay_secs=0.0,
                               jitter=0.0),
                   site='unit.site', sleep=lambda _: None)
    assert result == 'ok'
    family = fresh_registry.counter_family('reliability/io_retries',
                                           ('site',))
    assert family.series('unit.site').value == 2.0

  @pytest.mark.fault
  def test_nan_rollback_counts_and_logs_telemetry(self, fresh_registry,
                                                  tmp_path):
    from tensor2robot_tpu.reliability import FaultInjector, set_injector

    model_dir = str(tmp_path / 'run')
    set_injector(FaultInjector().fail('step.nan', times=1, after=4))
    try:
      trainer = Trainer(MockT2RModel(use_batch_norm=False), model_dir,
                        async_checkpoints=False, save_checkpoints_steps=2,
                        log_every_n_steps=100, nan_policy='rollback')
      trainer.train(MockInputGenerator(batch_size=8), max_train_steps=6)
      trainer.close()
    finally:
      set_injector(None)
    assert fresh_registry.counter('reliability/nan_rollbacks').value == 1.0
    rollbacks = [r for r in obs.read_telemetry(model_dir)
                 if r['kind'] == 'rollback']
    assert len(rollbacks) == 1
    assert rollbacks[0]['restored_step'] == rollbacks[0]['step'] - 1


# -- inference instrumentation (acceptance criterion) -------------------------


class TestInferenceLatency:

  def test_checkpoint_predictor_histogram_nonzero_percentiles(
      self, fresh_registry, trained_run):
    from tensor2robot_tpu.predictors import CheckpointPredictor
    from tensor2robot_tpu.predictors import abstract_predictor

    predictor = CheckpointPredictor(MockT2RModel(), trained_run, timeout=5.0)
    assert predictor.restore()
    generator = MockInputGenerator(batch_size=8)
    generator.set_specification_from_model(predictor._model, 'train')
    features, _ = next(generator.create_dataset_iterator(mode='train'))
    n_calls = 4
    for _ in range(n_calls):
      predictor.predict(features.to_dict())
    predictor.close()

    histogram = fresh_registry.histogram_family(
        abstract_predictor.INFERENCE_LATENCY_HISTOGRAM,
        ('predictor',)).series('CheckpointPredictor')
    assert histogram.count == n_calls
    assert histogram.percentile(50.0) > 0.0
    assert histogram.percentile(95.0) >= histogram.percentile(50.0)
    restores = fresh_registry.counter_family(
        abstract_predictor.INFERENCE_RESTORES_COUNTER,
        ('predictor', 'outcome'))
    assert restores.series('CheckpointPredictor', 'success').value == 1.0

  def test_restore_timeout_counts_as_timeout(self, fresh_registry, tmp_path):
    from tensor2robot_tpu.predictors import CheckpointPredictor
    from tensor2robot_tpu.predictors import abstract_predictor
    from tensor2robot_tpu.predictors import checkpoint_predictor

    predictor = CheckpointPredictor(MockT2RModel(), str(tmp_path),
                                    timeout=0.01)
    assert not predictor.restore()
    restores = fresh_registry.counter_family(
        abstract_predictor.INFERENCE_RESTORES_COUNTER,
        ('predictor', 'outcome'))
    assert restores.series('CheckpointPredictor', 'timeout').value == 1.0
    # The wait gauge never leaks a stale value past restore().
    assert fresh_registry.gauge_family(
        checkpoint_predictor.CHECKPOINT_WAIT_GAUGE,
        ('dir',)).series(str(tmp_path)).value == 0.0

  def test_wait_loop_reports_periodically(self, fresh_registry, tmp_path,
                                          monkeypatch):
    from tensor2robot_tpu.predictors import CheckpointPredictor
    from tensor2robot_tpu.predictors import checkpoint_predictor

    monkeypatch.setattr(checkpoint_predictor, '_POLL_INTERVAL_SECS', 0.02)
    monkeypatch.setattr(checkpoint_predictor,
                        '_WAIT_REPORT_INTERVAL_SECS', 0.05)
    observed = []
    wait_gauge = fresh_registry.gauge_family(
        checkpoint_predictor.CHECKPOINT_WAIT_GAUGE,
        ('dir',)).series(str(tmp_path))

    def capture(msg, *args):
      observed.append((msg % args, wait_gauge.value))

    monkeypatch.setattr(checkpoint_predictor, 'log_warning', capture)
    predictor = CheckpointPredictor(MockT2RModel(), str(tmp_path),
                                    timeout=0.3)
    assert not predictor.restore()
    waiting = [(msg, gauge) for msg, gauge in observed
               if 'still waiting' in msg]
    assert waiting, 'silent wait: no periodic progress log emitted'
    assert all(gauge > 0.0 for _, gauge in waiting)
    assert 'elapsed' in waiting[0][0]

  def test_policy_select_action_latency(self, fresh_registry):
    from tensor2robot_tpu.policies import policies as policies_lib

    class _StubPredictor:

      def predict(self, features):
        return {'inference_output': np.zeros((1, 2), np.float32)}

    class _StubModel:

      def pack_features(self, state, context, timestep):
        return {'x': np.zeros((1, 2), np.float32)}

    policy = policies_lib.RegressionPolicy(t2r_model=_StubModel(),
                                           predictor=_StubPredictor())
    for _ in range(3):
      policy.SelectAction({'x': 1}, None, 0)
    histogram = fresh_registry.histogram_family(
        policies_lib.POLICY_LATENCY_HISTOGRAM,
        ('policy',)).series('RegressionPolicy')
    assert histogram.count == 3
    assert histogram.percentile(95.0) >= 0.0


# -- t2r_telemetry CLI --------------------------------------------------------


class TestTelemetryCLI:

  def _run(self, *argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_telemetry')]
        + list(argv),
        capture_output=True, text=True, timeout=120,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'})

  def test_summarize_reports_goodput_and_scalars(self, trained_run):
    result = self._run('summarize', trained_run)
    assert result.returncode == 0, result.stderr
    assert 'heartbeat: step=6' in result.stdout
    assert 'goodput @ step' in result.stdout
    assert 'productive' in result.stdout
    assert 'span/train.step' in result.stdout or 'examples/sec' \
        in result.stdout

  def test_summarize_lists_a_histogram_once(self, trained_run):
    # A histogram takes ONE line of the listing, not one a statistic: the
    # once-a-run start-up spans (five equal statistics each, in thousands
    # of ms) would else crowd out what recurs. Which instruments make the
    # 15 follows what else the worker's process registry held.
    result = self._run('summarize', trained_run)
    assert result.returncode == 0, result.stderr
    listing = result.stdout.split('events @ step')[1].splitlines()[1:]
    tags = [line.split()[0] for line in listing]
    assert len(tags) == len(set(tags)) == 15
    assert not [tag for tag in tags if tag.rpartition('/')[2] in (
        'count', 'mean', 'p50', 'p95', 'p99', 'max')]
    assert not [tag for tag in tags if tag.startswith('span/compile.')]
    histograms = [line for line in listing if 'count=' in line]
    assert histograms and all('p99=' in line for line in histograms)

  def test_summarize_stage_table_reports_bytes(self, trained_run):
    # ISSUE 10 satellite: per-stage BYTES alongside examples in the
    # pipeline stage table — wire-compression wins must be visible in
    # live runs, not only in bench reruns.
    result = self._run('summarize', trained_run)
    assert result.returncode == 0, result.stderr
    assert 'pipeline @ step' in result.stdout
    table = [line for line in result.stdout.splitlines()
             if line.startswith('  transfer')]
    assert table, result.stdout
    assert 'B/ex)' in table[0], table[0]

  def test_tail_pretty_prints_records(self, trained_run):
    result = self._run('tail', trained_run)
    assert result.returncode == 0, result.stderr
    assert '[run_start]' in result.stdout
    assert '[run_end' in result.stdout
    assert 'productive=' in result.stdout
