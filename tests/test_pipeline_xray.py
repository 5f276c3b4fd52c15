"""Pipeline X-ray coverage (ISSUE 7 acceptance tests).

The stage model end to end: source-side StageMeter counters from the
C++ loader stats export, the Python parser pipeline, and the device
feed; PipelineXray's windowed capacity attribution and the three new
anomaly kinds; the injected ``data.stall`` acceptance loop (exactly one
budgeted capture whose forensics report attributes the transfer stage,
clean run -> zero pipeline anomalies); and the doctor's pipeline
section ranking a stall as CRITICAL.
"""

import glob
import json
import os

import numpy as np
import pytest

from tensor2robot_tpu import observability as obs
from tensor2robot_tpu.data import native_loader, tfrecord
from tensor2robot_tpu.data.wire import build_example
from tensor2robot_tpu.observability import doctor as doctor_lib
from tensor2robot_tpu.observability import pipeline_xray as xray_lib
from tensor2robot_tpu.reliability import fault_injection
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec
from tensor2robot_tpu.trainer import Trainer
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel


@pytest.fixture(autouse=True)
def fresh_registry():
  previous = obs.set_registry(obs.TelemetryRegistry())
  yield obs.get_registry()
  obs.set_registry(previous)


@pytest.fixture(autouse=True)
def no_injector():
  fault_injection.set_injector(None)
  yield
  fault_injection.set_injector(None)


# -- the shared attribution rule ---------------------------------------------


class TestAttributeStages:

  def test_names_the_slowest_stage(self):
    out = xray_lib.attribute_stages(
        {'device': 2878.0, 'decode': 925.0, 'transfer': 239.0})
    assert out['bottleneck'] == 'transfer'
    assert out['headroom_vs_device'] == pytest.approx(239.0 / 2878.0)

  def test_skips_unmeasured_stages(self):
    # A stage that could not be measured is unknown, not infinitely
    # fast — and must not win the argmin by defaulting to 0/-1.
    out = xray_lib.attribute_stages(
        {'device': 100.0, 'decode': -1.0, 'transfer': None, 'read': 50.0})
    assert out['bottleneck'] == 'read'
    assert set(out['rates']) == {'device', 'read'}

  def test_device_bound_pipeline(self):
    out = xray_lib.attribute_stages({'device': 100.0, 'decode': 900.0})
    assert out['bottleneck'] == 'device'
    assert out['headroom_vs_device'] == 1.0

  def test_empty_and_tie(self):
    assert xray_lib.attribute_stages({})['bottleneck'] is None
    # Deterministic tie-break: lexicographically first stage.
    out = xray_lib.attribute_stages({'transfer': 10.0, 'decode': 10.0})
    assert out['bottleneck'] == 'decode'


# -- stage meters ------------------------------------------------------------


class TestStageMeter:

  def test_counters_land_under_stage_names(self, fresh_registry):
    meter = xray_lib.StageMeter('decode')
    meter.add(examples=8, nbytes=1024, busy_s=0.5)
    meter.add(examples=8, nbytes=1024, busy_s=0.25)
    scalars = fresh_registry.scalars()
    assert scalars['pipeline/decode/examples'] == 16.0
    assert scalars['pipeline/decode/bytes'] == 2048.0
    assert scalars['pipeline/decode/busy_seconds'] == 0.75


# -- windowed attribution ----------------------------------------------------


def _goodput(productive, data):
  return {'productive': productive, 'data': data, 'checkpoint': 0.0,
          'retry': 0.0}


class TestPipelineXray:

  def _xray(self, **kwargs):
    kwargs.setdefault('min_baseline_windows', 2)
    return xray_lib.PipelineXray(xray_lib.XrayConfig(**kwargs))

  def _window(self, registry, examples, decode_busy, transfer_busy,
              transfer_bytes=0.0, decode_idle=0.0):
    xray_lib.StageMeter('decode', registry).add(
        examples=examples, nbytes=examples * 1000, busy_s=decode_busy)
    xray_lib.StageMeter('transfer', registry).add(
        examples=examples, nbytes=transfer_bytes, busy_s=transfer_busy)
    if decode_idle:
      registry.counter(xray_lib.DECODE_IDLE_COUNTER).inc(decode_idle)

  def test_capacity_attribution_names_slowest_stage(self, fresh_registry):
    xray = self._xray()
    # decode: 100 ex / 0.8 s = 125 ex/s; transfer: 100 / 0.1 = 1000;
    # device: 100 / productive 0.05 = 2000 -> decode gates.
    self._window(fresh_registry, 100, decode_busy=0.8, transfer_busy=0.1)
    record, anomalies = xray.observe(
        10, examples=100, window_seconds=1.0,
        goodput_seconds=_goodput(0.05, 0.9))
    assert anomalies == []
    assert record['schema'] == 't2r.pipeline.v1'
    assert record['bottleneck'] == 'decode'
    stages = record['stages']
    assert stages['decode']['examples_per_sec_capacity'] == \
        pytest.approx(125.0)
    assert stages['transfer']['examples_per_sec_capacity'] == \
        pytest.approx(1000.0)
    assert record['headroom_vs_device'] == pytest.approx(125.0 / 2000.0)
    # The derived windowed gauges rode into the registry for TensorBoard.
    scalars = fresh_registry.scalars()
    assert scalars['pipeline/examples_per_sec/decode'] == \
        pytest.approx(125.0)
    assert scalars['pipeline/headroom_vs_device'] == \
        pytest.approx(125.0 / 2000.0)

  def test_decode_capacity_normalizes_by_worker_pool(self, fresh_registry):
    xray = self._xray()
    fresh_registry.gauge(xray_lib.DECODE_WORKERS_GAUGE).set(4.0)
    # 100 ex over 2.0 pool-busy seconds across 4 workers: each example
    # costs 20 ms, but four workers run in parallel -> 200 ex/s.
    self._window(fresh_registry, 100, decode_busy=2.0, transfer_busy=0.01)
    record, _ = xray.observe(1, examples=100, window_seconds=1.0,
                             goodput_seconds=_goodput(0.5, 0.5))
    assert record['stages']['decode']['examples_per_sec_capacity'] == \
        pytest.approx(200.0)

  def test_stall_fires_and_names_the_gating_stage(self, fresh_registry):
    xray = self._xray(min_baseline_windows=2, stall_ratio=2.0,
                      stall_data_fraction=0.5)
    goodput = {'productive': 0.0, 'data': 0.0, 'checkpoint': 0.0,
               'retry': 0.0}

    def advance(productive, data):
      goodput['productive'] += productive
      goodput['data'] += data
      return dict(goodput)

    for step in (1, 2, 3):
      self._window(fresh_registry, 100, decode_busy=0.1,
                   transfer_busy=0.05)
      _, anomalies = xray.observe(step, examples=100, window_seconds=1.0,
                                  goodput_seconds=advance(0.9, 0.1))
      assert anomalies == []
    # Collapse: 4 examples in a 1 s window, 95% lost to data, with the
    # transfer stage eating the window -> stall attributed to transfer.
    self._window(fresh_registry, 4, decode_busy=0.001, transfer_busy=0.9)
    record, anomalies = xray.observe(4, examples=4, window_seconds=1.0,
                                     goodput_seconds=advance(0.05, 0.95))
    assert [a.kind for a in anomalies] == ['pipeline_stall']
    assert anomalies[0].detail['stage'] == 'transfer'
    assert record['bottleneck'] == 'transfer'
    assert fresh_registry.scalars()[
        'watchdog/anomalies/pipeline_stall'] == 1.0

  def test_stalled_window_stays_out_of_baseline(self, fresh_registry):
    xray = self._xray(min_baseline_windows=2)
    seconds = {'productive': 0.0, 'data': 0.0}

    def advance(productive, data):
      seconds['productive'] += productive
      seconds['data'] += data
      return {'productive': seconds['productive'], 'data': seconds['data'],
              'checkpoint': 0.0, 'retry': 0.0}

    for step in (1, 2, 3):
      self._window(fresh_registry, 100, 0.1, 0.05)
      xray.observe(step, 100, 1.0, advance(0.9, 0.1))
    # A SUSTAINED stall keeps firing — the stalled windows must not drag
    # the flow baseline down until the stall looks normal.
    for step in (4, 5, 6):
      self._window(fresh_registry, 4, 0.001, 0.9)
      _, anomalies = xray.observe(step, 4, 1.0, advance(0.05, 0.95))
      assert [a.kind for a in anomalies] == ['pipeline_stall'], step

  def test_worker_starvation(self, fresh_registry):
    xray = self._xray(starvation_idle_fraction=0.75,
                      starvation_data_fraction=0.5)
    # Workers 90% idle while the trainer loses 80% of the window to
    # data: the read stage cannot feed the pool.
    self._window(fresh_registry, 10, decode_busy=0.1, transfer_busy=0.01,
                 decode_idle=0.9)
    _, anomalies = xray.observe(1, examples=10, window_seconds=1.0,
                                goodput_seconds=_goodput(0.2, 0.8))
    assert [a.kind for a in anomalies] == ['worker_starvation']
    assert anomalies[0].detail['worker_idle_fraction'] == \
        pytest.approx(0.9)

  def test_busy_workers_never_read_as_starved(self, fresh_registry):
    xray = self._xray()
    self._window(fresh_registry, 10, decode_busy=0.9, transfer_busy=0.01,
                 decode_idle=0.1)
    _, anomalies = xray.observe(1, examples=10, window_seconds=1.0,
                                goodput_seconds=_goodput(0.2, 0.8))
    assert anomalies == []

  def test_transfer_regression(self, fresh_registry):
    xray = self._xray(min_baseline_windows=2,
                      transfer_regression_ratio=2.0,
                      transfer_min_busy_fraction=0.05)
    for step in (1, 2, 3):
      # 100 MB over 0.5 busy seconds = 200 MB/s.
      self._window(fresh_registry, 100, decode_busy=0.01,
                   transfer_busy=0.5, transfer_bytes=100e6)
      _, anomalies = xray.observe(step, 100, 1.0,
                                  goodput_seconds=None)
      assert anomalies == []
    # 10 MB over 0.5 s = 20 MB/s: 10x below the 200 MB/s baseline.
    self._window(fresh_registry, 100, decode_busy=0.01, transfer_busy=0.5,
                 transfer_bytes=10e6)
    _, anomalies = xray.observe(4, 100, 1.0, goodput_seconds=None)
    assert [a.kind for a in anomalies] == ['transfer_regression']
    assert anomalies[0].detail['mb_per_sec'] == pytest.approx(20.0)

  def test_negligible_transfer_never_fires_regression(self, fresh_registry):
    """A hop that is <5% of the window is jitter, not a bottleneck:
    its MB/s estimate must not arm or trip the regression baseline."""
    xray = self._xray(min_baseline_windows=2)
    for step in (1, 2, 3):
      self._window(fresh_registry, 100, decode_busy=0.01,
                   transfer_busy=0.001, transfer_bytes=100e6)
      xray.observe(step, 100, 1.0, goodput_seconds=None)
    self._window(fresh_registry, 100, decode_busy=0.01,
                 transfer_busy=0.001, transfer_bytes=1e3)
    _, anomalies = xray.observe(4, 100, 1.0, goodput_seconds=None)
    assert anomalies == []


# -- native loader stats export ----------------------------------------------


def _numeric_specs():
  features = SpecStruct(
      vec=TensorSpec((3,), np.float32, name='vec'),
      idx=TensorSpec((2,), np.int64, name='idx'))
  labels = SpecStruct(target=TensorSpec((1,), np.float32, name='target'))
  return features, labels


def _write_numeric_records(path, n, seed=0):
  rng = np.random.RandomState(seed)
  records = [build_example({
      'vec': rng.rand(3).astype(np.float32),
      'idx': np.asarray([i, i * 2], np.int64),
      'target': np.asarray([i * 0.5], np.float32),
  }) for i in range(n)]
  tfrecord.write_records(path, records)
  return records


class TestNativeLoaderStats:

  def test_stats_flow_through_lazy_launch_boundary(self, tmp_path,
                                                   fresh_registry):
    path = str(tmp_path / 'data.tfrecord')
    records = _write_numeric_records(path, 12)
    features, labels = _numeric_specs()
    plan = native_loader.plan_for_specs(features, labels)
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=4, num_epochs=1, num_threads=2)
    # Before the first next(): reading stats must NOT launch the worker
    # threads (the deterministic error-delivery contract) — all zeros.
    before = stream.stats()
    assert before['records_read'] == 0
    assert before['rows_parsed'] == 0
    batches = list(stream)
    assert len(batches) == 3
    stats = stream.stats()
    stream.close()
    assert stats['records_read'] == 12
    assert stats['rows_parsed'] == 12
    assert stats['n_workers'] == 2
    assert stats['bytes_read'] > 0
    assert stats['parse_bytes'] == sum(len(r) + 0 for r in records)
    assert stats['worker_busy_us'] >= stats['max_worker_busy_us'] >= 0
    # ...and the registry saw the same flow as pipeline/* counters.
    scalars = fresh_registry.scalars()
    assert scalars['pipeline/read/examples'] == 12.0
    assert scalars['pipeline/decode/examples'] == 12.0
    assert scalars['pipeline/read/bytes'] == stats['bytes_read']
    assert scalars[xray_lib.DECODE_WORKERS_GAUGE] == 2.0
    assert scalars['span/data.pack/count'] == 3.0


class TestPythonPipelineStages:

  def test_python_parser_path_meters_read_and_decode(self, tmp_path,
                                                     fresh_registry):
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.modes import ModeKeys

    path = str(tmp_path / 'data.tfrecord')
    _write_numeric_records(path, 12)
    features, labels = _numeric_specs()
    generator = DefaultRecordInputGenerator(
        file_patterns=path, batch_size=4, use_native=False)
    generator.set_specification(features, labels)
    batches = list(generator.create_dataset_iterator(
        mode=ModeKeys.EVAL, num_epochs=1))
    assert len(batches) == 3
    scalars = fresh_registry.scalars()
    assert scalars['pipeline/read/examples'] == 12.0
    assert scalars['pipeline/decode/examples'] == 12.0
    assert scalars['pipeline/read/bytes'] > 0
    assert scalars['pipeline/decode/busy_seconds'] > 0
    # The prefetch producer owns the batch-stage example count.
    assert scalars['pipeline/batch/examples'] == 12.0


# -- double-buffered device feed ---------------------------------------------


class TestDoubleBufferedFeed:

  def _feed(self):
    import jax

    from tensor2robot_tpu.data.device_feed import HostDeviceFeed
    from tensor2robot_tpu.parallel import create_mesh

    mesh = create_mesh({'data': 1}, devices=jax.devices()[:1])
    return HostDeviceFeed(mesh)

  def _batches(self, n):
    for i in range(n):
      yield {'features': {'x': np.full((4, 3), i, np.float32)},
             'labels': None}

  def test_delivers_in_order_and_ends_cleanly(self, fresh_registry):
    from tensor2robot_tpu.data.device_feed import DoubleBufferedFeed

    buffered = DoubleBufferedFeed(self._batches(5), self._feed(), depth=2)
    seen = [float(np.asarray(batch['features']['x'])[0, 0])
            for batch in buffered]
    assert seen == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert buffered.close()
    # Every buffered batch crossed the metered transfer hop.
    scalars = fresh_registry.scalars()
    assert scalars['pipeline/transfer/examples'] == 20.0
    assert scalars['pipeline/transfer/ms/count'] == 5.0

  def test_producer_error_surfaces_at_get(self, fresh_registry):
    from tensor2robot_tpu.data.device_feed import DoubleBufferedFeed

    def _bad():
      yield {'features': {'x': np.zeros((2, 2), np.float32)},
             'labels': None}
      raise RuntimeError('decode exploded')

    buffered = DoubleBufferedFeed(_bad(), self._feed(), depth=2)
    buffered.get()
    with pytest.raises(RuntimeError, match='decode exploded'):
      buffered.get()
    assert buffered.close()

  def test_close_unblocks_a_full_buffer(self, fresh_registry):
    from tensor2robot_tpu.data.device_feed import (
        BUFFER_OCCUPANCY_GAUGE,
        DoubleBufferedFeed,
    )

    buffered = DoubleBufferedFeed(self._batches(50), self._feed(), depth=2)
    buffered.get()  # producer now keeps the depth-2 buffer topped up
    assert buffered.close(timeout=30)
    assert fresh_registry.scalars()[BUFFER_OCCUPANCY_GAUGE] == 0.0

  def test_deep_feed_drains_in_order_under_stall_no_torn_batches(
      self, fresh_registry, monkeypatch):
    """ISSUE 10 satellite: a ``data.stall`` on the hop with depth N must
    drain IN ORDER and never deliver a torn/mixed-version batch — every
    leaf of every delivered batch carries one version, in sequence."""
    from tensor2robot_tpu.data.device_feed import PipelinedFeed

    monkeypatch.setattr(fault_injection, 'DATA_STALL_SECONDS', 0.05)
    fault_injection.set_injector(
        fault_injection.FaultInjector().fail('data.stall', times=3,
                                             after=4))

    def versioned(n):
      for i in range(n):
        yield {'features': {'a': np.full((4, 3), i, np.float32),
                            'b': np.full((4, 7), i, np.float32)},
               'labels': {'y': np.full((4, 1), i, np.float32)}}

    buffered = PipelinedFeed(versioned(12), self._feed(), depth=4)
    seen = []
    for batch in buffered:
      versions = {float(np.asarray(leaf).ravel()[0])
                  for leaf in (batch['features']['a'],
                               batch['features']['b'],
                               batch['labels']['y'])}
      assert len(versions) == 1, 'torn batch: {}'.format(versions)
      uniform = {float(v)
                 for v in np.asarray(batch['features']['a']).ravel()}
      assert len(uniform) == 1, 'torn rows: {}'.format(uniform)
      seen.append(versions.pop())
    assert seen == [float(i) for i in range(12)]
    assert buffered.close()
    # Every batch crossed the metered hop exactly once, stall included.
    scalars = fresh_registry.scalars()
    assert scalars['pipeline/transfer/ms/count'] == 12.0
    assert scalars['pipeline/transfer/examples'] == 48.0


# -- the acceptance loop -----------------------------------------------------


def _make_trainer(model_dir, **kwargs):
  kwargs.setdefault('save_checkpoints_steps', 10**9)
  kwargs.setdefault('async_checkpoints', False)
  return Trainer(MockT2RModel(), model_dir, **kwargs)


@pytest.mark.fault
class TestXrayLoop:

  def test_clean_run_emits_records_and_zero_pipeline_anomalies(
      self, tmp_path, fresh_registry):
    model_dir = str(tmp_path)
    trainer = _make_trainer(
        model_dir, log_every_n_steps=2,
        # Jitter-proof thresholds (see test_forensics.py): the windows
        # here are 2 millisecond-scale mock steps, so one OS scheduling
        # transient can fake a production-threshold collapse. The
        # injected-stall test below fires at ~77x under tighter
        # settings, so the clean/dirty asymmetry keeps its teeth.
        watchdog_config=obs.WatchdogConfig(regression_ratio=10.0,
                                           goodput_drop=0.9),
        xray_config=xray_lib.XrayConfig(stall_ratio=10.0,
                                        stall_data_fraction=0.9,
                                        starvation_data_fraction=0.9,
                                        transfer_regression_ratio=10.0))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=10)
    trainer.close()
    records = obs.read_telemetry(model_dir)
    pipelines = [r for r in records if r['kind'] == 'pipeline']
    assert pipelines, 'no t2r.pipeline.v1 records emitted'
    latest = pipelines[-1]
    assert latest['schema'] == 't2r.pipeline.v1'
    assert latest['bottleneck'] in xray_lib.STAGES
    # The record's own stage capacities re-attribute to the same gate
    # (observability/pipeline_xray.py::attribute_stages).
    rates = {stage: info.get('examples_per_sec_capacity')
             for stage, info in latest['stages'].items()}
    assert xray_lib.attribute_stages(rates)['bottleneck'] == \
        latest['bottleneck']
    # Per-stage pipeline metrics reached the registry export.
    scalars = fresh_registry.scalars()
    assert scalars['pipeline/transfer/examples'] > 0
    assert scalars['pipeline/batch/examples'] > 0
    assert scalars['pipeline/transfer/ms/count'] > 0
    # Clean run: ZERO pipeline anomalies, zero captures.
    assert not [r for r in records if r['kind'] == 'anomaly'
                and r.get('anomaly') in (xray_lib.PIPELINE_STALL,
                                         xray_lib.WORKER_STARVATION,
                                         xray_lib.TRANSFER_REGRESSION)]
    assert trainer.auto_profiler.captures_taken == 0

  def test_injected_stall_is_captured_and_attributed(
      self, tmp_path, fresh_registry, monkeypatch):
    monkeypatch.setattr(fault_injection, 'DATA_STALL_SECONDS', 0.25)
    fault_injection.set_injector(
        fault_injection.FaultInjector().fail('data.stall', times=6,
                                             after=8))
    model_dir = str(tmp_path)
    trainer = _make_trainer(
        model_dir, log_every_n_steps=2, profile_budget=1,
        profile_window_steps=2, profile_min_interval_secs=0.0,
        # The stall also inflates step time; disable the watchdog so the
        # capture is attributable to the PIPELINE detection alone.
        enable_watchdog=False,
        xray_config=xray_lib.XrayConfig(min_baseline_windows=2))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=20)
    trainer.close()

    records = obs.read_telemetry(model_dir)
    anomalies = [r for r in records if r['kind'] == 'anomaly']
    stalls = [r for r in anomalies if r['anomaly'] == 'pipeline_stall']
    assert stalls, anomalies
    # The stall lives on the host->device hop: attributed to transfer.
    assert stalls[0]['detail']['stage'] == 'transfer'
    # Exactly ONE budgeted capture answered it...
    assert trainer.auto_profiler.captures_taken == 1
    report_paths = glob.glob(os.path.join(model_dir, 'forensics',
                                          '*.json'))
    assert len(report_paths) == 1
    with open(report_paths[0]) as f:
      report = json.load(f)
    # ...and its report carries the stage table naming the gate.
    assert report['reason'] == 'pipeline_stall'
    assert report['trigger']['stage'] == 'transfer'
    assert report['pipeline'] is not None
    assert report['pipeline']['schema'] == 't2r.pipeline.v1'
    assert report['pipeline']['bottleneck'] == 'transfer'
    assert 'transfer' in report['pipeline']['stages']

  def test_injected_stall_with_deep_feed_one_capture(
      self, tmp_path, fresh_registry, monkeypatch):
    """ISSUE 10 satellite: the SAME acceptance shape through the N-deep
    pipelined trainer feed (feed_depth=4) — the stall now fires in the
    PRODUCER thread, the buffer drains in order, and the X-ray still
    answers with exactly one budgeted pipeline capture attributing the
    transfer stage."""
    monkeypatch.setattr(fault_injection, 'DATA_STALL_SECONDS', 0.25)
    fault_injection.set_injector(
        fault_injection.FaultInjector().fail('data.stall', times=8,
                                             after=8))
    model_dir = str(tmp_path)
    trainer = _make_trainer(
        model_dir, log_every_n_steps=2, profile_budget=1,
        profile_window_steps=2, profile_min_interval_secs=0.0,
        enable_watchdog=False, feed_depth=4,
        xray_config=xray_lib.XrayConfig(min_baseline_windows=2))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=24)
    trainer.close()

    records = obs.read_telemetry(model_dir)
    anomalies = [r for r in records if r['kind'] == 'anomaly']
    pipeline_kinds = (xray_lib.PIPELINE_STALL,
                      xray_lib.TRANSFER_REGRESSION)
    fired = [r for r in anomalies if r['anomaly'] in pipeline_kinds]
    assert fired, anomalies
    assert trainer.auto_profiler.captures_taken == 1
    report_paths = glob.glob(os.path.join(model_dir, 'forensics',
                                          '*.json'))
    assert len(report_paths) == 1
    with open(report_paths[0]) as f:
      report = json.load(f)
    assert report['reason'] in pipeline_kinds
    # The training itself completed every step despite the stalls —
    # the deep buffer delivered every batch exactly once, in order.
    trains = [r for r in records if r['kind'] == 'train']
    assert trains and trains[-1]['step'] == 24


# -- doctor ------------------------------------------------------------------


class TestDoctorPipeline:

  def _write_run(self, model_dir, stalled, end=True):
    logger = obs.TelemetryLogger(model_dir)
    logger.log('run_start', step=0)
    goodput = {'productive': 0.7, 'data': 0.25, 'checkpoint': 0.05,
               'retry': 0.0}
    for step in (2, 4, 6):
      logger.log('train', step=step, loss=0.5, examples_per_sec=239.0,
                 goodput=goodput, gauges={})
      logger.log('pipeline', step=step, schema='t2r.pipeline.v1',
                 examples_per_sec=239.0, bottleneck='transfer',
                 headroom_vs_device=0.22,
                 stages={'transfer': {'busy_fraction': 0.4}})
      logger.heartbeat(step)
    if stalled:
      logger.log('anomaly', step=8, anomaly='pipeline_stall',
                 message='stalled', detail={'stage': 'transfer'})
      logger.heartbeat(8)
    if end:
      logger.log('run_end', step=8, goodput=goodput)
    logger.close()

  def test_live_stall_is_critical(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, stalled=True, end=False)
    findings = doctor_lib.diagnose(model_dir)
    stall = [f for f in findings if 'pipeline stalled' in f['message']]
    assert stall and stall[0]['severity'] == doctor_lib.CRITICAL
    assert stall[0]['detail']['stage'] == 'transfer'

  def test_recovered_stall_is_warning_for_live_run(self, tmp_path):
    """One historical hiccup must not hold the automation gate at exit
    2 forever: a LATER healthy pipeline window downgrades the stall."""
    model_dir = str(tmp_path)
    logger = obs.TelemetryLogger(model_dir)
    logger.log('run_start', step=0)
    logger.log('anomaly', step=4, anomaly='pipeline_stall',
               message='stalled', detail={'stage': 'transfer'})
    logger.log('pipeline', step=4, schema='t2r.pipeline.v1',
               bottleneck='transfer', anomalies=['pipeline_stall'])
    logger.log('pipeline', step=6, schema='t2r.pipeline.v1',
               bottleneck='device', headroom_vs_device=1.0, anomalies=[])
    logger.heartbeat(6)  # run still live
    logger.close()
    findings = doctor_lib.diagnose(model_dir)
    stall = [f for f in findings if 'pipeline stalled' in f['message']]
    assert stall and stall[0]['severity'] == doctor_lib.WARNING
    assert 'recovered since' in stall[0]['message']

  def test_finished_run_stall_is_warning(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, stalled=True, end=True)
    findings = doctor_lib.diagnose(model_dir)
    stall = [f for f in findings if 'pipeline stalled' in f['message']]
    assert stall and stall[0]['severity'] == doctor_lib.WARNING

  def test_gated_pipeline_is_a_warning_with_headroom(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, stalled=False)
    findings = doctor_lib.diagnose(model_dir)
    gated = [f for f in findings if 'gated by transfer' in f['message']]
    assert gated and gated[0]['severity'] == doctor_lib.WARNING
    assert '22%' in gated[0]['message']
