"""Performance-forensics coverage (ISSUE 4 acceptance tests).

The closed loop, driven end to end on the CPU mesh: an injected slowdown
(FaultInjector 'step.slow') trips the watchdog, which triggers exactly
one budgeted profiler capture, which lands as a structured
``forensics/<step>.json`` whose top-op and goodput-attribution fields
are asserted — while a clean run triggers zero captures and reports
``recompiles/train_step == 1``. Plus unit coverage for every watchdog
detection, the AutoProfiler budget/rate-limit arithmetic, report
degradation on missing captures, the jax.monitoring signal sources, and
the doctor's ranked diagnosis.
"""

import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import observability as obs
from tensor2robot_tpu.observability import doctor as doctor_lib
from tensor2robot_tpu.observability import forensics as forensics_lib
from tensor2robot_tpu.observability import signals as signals_lib
from tensor2robot_tpu.observability import watchdog as watchdog_lib
from tensor2robot_tpu.observability.autoprofiler import AutoProfiler
from tensor2robot_tpu.reliability import fault_injection
from tensor2robot_tpu.trainer import Trainer
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


# -- watchdog ----------------------------------------------------------------


class TestWatchdog:

  def _config(self, **kwargs):
    kwargs.setdefault('min_baseline_windows', 2)
    return watchdog_lib.WatchdogConfig(**kwargs)

  def test_step_time_regression_fires_after_baseline(self, fresh_registry):
    dog = obs.Watchdog(self._config(regression_ratio=1.8))
    assert dog.observe(1, 0.10) == []  # no baseline yet
    assert dog.observe(2, 0.11) == []
    anomalies = dog.observe(3, 0.40)
    assert [a.kind for a in anomalies] == ['step_time_regression']
    assert anomalies[0].detail['ratio'] > 1.8
    # Counted into the registry for the TensorBoard/telemetry export.
    assert fresh_registry.scalars()[
        'watchdog/anomalies/step_time_regression'] == 1.0

  def test_anomalous_window_stays_out_of_baseline(self, fresh_registry):
    dog = obs.Watchdog(self._config(regression_ratio=1.8))
    dog.observe(1, 0.10)
    dog.observe(2, 0.10)
    # A SUSTAINED regression keeps firing: the slow windows must not
    # drag the rolling baseline up until the regression looks normal.
    for step in range(3, 8):
      assert dog.observe(step, 0.40), 'regression self-normalized'

  def test_jitter_below_ratio_never_fires(self, fresh_registry):
    dog = obs.Watchdog(self._config(regression_ratio=1.8))
    for step, step_time in enumerate([0.10, 0.11, 0.097, 0.12, 0.105]):
      assert dog.observe(step, step_time) == []

  def test_goodput_drop(self, fresh_registry):
    dog = obs.Watchdog(self._config(goodput_drop=0.25))
    seconds = {'productive': 0.0, 'data': 0.0, 'checkpoint': 0.0,
               'retry': 0.0}

    def window(productive, data):
      seconds['productive'] += productive
      seconds['data'] += data
      return dict(seconds)

    assert dog.observe(1, None, window(9.0, 1.0)) == []  # primes last
    assert dog.observe(2, None, window(9.0, 1.0)) == []
    assert dog.observe(3, None, window(9.0, 1.0)) == []
    anomalies = dog.observe(4, None, window(3.0, 7.0))
    assert [a.kind for a in anomalies] == ['goodput_drop']
    assert 'data' in anomalies[0].message

  def test_recompile_growth_fires_once_per_growth(self, fresh_registry):
    dog = obs.Watchdog(self._config(recompile_warmup_windows=1))
    gauge = fresh_registry.gauge(watchdog_lib.RECOMPILE_GAUGE)
    gauge.set(1.0)
    assert dog.observe(1, 0.1) == []  # warmup locks the baseline at 1
    assert dog.observe(2, 0.1) == []
    gauge.set(2.0)
    anomalies = dog.observe(3, 0.1)
    assert [a.kind for a in anomalies] == ['recompile']
    assert dog.observe(4, 0.1) == []  # same cache size: reported once

  def test_feed_shape_instability_fires(self, fresh_registry):
    dog = obs.Watchdog(self._config())
    fresh_registry.gauge(watchdog_lib.RECOMPILE_GAUGE).set(1.0)
    dog.observe(1, 0.1)
    fresh_registry.gauge(watchdog_lib.FEED_SHAPES_GAUGE).set(2.0)
    anomalies = dog.observe(2, 0.1)
    assert [a.kind for a in anomalies] == ['recompile']
    assert 'shape signatures' in anomalies[0].message
    # Latched: the gauge never goes back down, so the same stale
    # condition must not re-fire (and burn the capture budget) forever.
    assert dog.observe(3, 0.1) == []
    fresh_registry.gauge(watchdog_lib.FEED_SHAPES_GAUGE).set(3.0)
    assert [a.kind for a in dog.observe(4, 0.1)] == ['recompile']

  def test_feed_shape_instability_fires_without_cache_probe(
      self, fresh_registry):
    """The shape invariant is independent of the (private, version-
    dependent) jit cache-size probe: it must fire with the recompile
    gauge still at 0."""
    dog = obs.Watchdog(self._config())
    dog.observe(1, 0.1)
    fresh_registry.gauge(watchdog_lib.FEED_SHAPES_GAUGE).set(2.0)
    anomalies = dog.observe(2, 0.1)
    assert [a.kind for a in anomalies] == ['recompile']
    assert 'shape signatures' in anomalies[0].message

  def test_hbm_monotonic_growth(self, fresh_registry):
    dog = obs.Watchdog(self._config(hbm_growth_windows=3,
                                    hbm_growth_bytes=100.0))
    gauge = fresh_registry.gauge_family(
        watchdog_lib.DEVICE_BYTES_GAUGE, ('device',)).series('0')
    fired = []
    for value in (1000, 1100, 1200, 1300, 1400):
      gauge.set(value)
      fired.extend(dog.observe(1, None))
    assert [a.kind for a in fired] == ['hbm_growth']
    assert fired[0].detail['device'] == '0'

  def test_hbm_sawtooth_never_fires(self, fresh_registry):
    """Normal allocator behavior — grow, free, grow — is not a leak."""
    dog = obs.Watchdog(self._config(hbm_growth_windows=3,
                                    hbm_growth_bytes=100.0))
    gauge = fresh_registry.gauge_family(
        watchdog_lib.DEVICE_BYTES_GAUGE, ('device',)).series('0')
    for value in (1000, 1200, 900, 1300, 1000, 1400):
      gauge.set(value)
      assert dog.observe(1, None) == []

  def test_heartbeat_staleness(self):
    now = time.time()  # wall-clock: heartbeat timestamps are wall time
    fresh = {'time': now - 10, 'step': 5, 'pid': 1, 'hostname': 'h'}
    stale = {'time': now - 1000, 'step': 5, 'pid': 1, 'hostname': 'h'}
    assert watchdog_lib.check_heartbeat(fresh, now, stale_secs=300) == []
    anomalies = watchdog_lib.check_heartbeat(stale, now, stale_secs=300)
    assert [a.kind for a in anomalies] == ['heartbeat_stale']
    assert watchdog_lib.check_heartbeat(None, now)[0].kind == \
        'heartbeat_stale'


# -- signal sources ----------------------------------------------------------


class TestSignals:

  def test_compile_events_land_in_registry(self, fresh_registry):
    assert signals_lib.install_jax_listeners()
    try:
      jax.jit(lambda x: x * 2 + 1)(jnp.ones((4,))).block_until_ready()
    finally:
      signals_lib.uninstall_jax_listeners()
    scalars = fresh_registry.scalars()
    assert scalars[signals_lib.COMPILE_COUNTER] >= 1.0
    assert scalars[signals_lib.COMPILE_MS_HISTOGRAM + '/count'] >= 1.0

  def test_uninstalled_listeners_stay_silent(self, fresh_registry):
    signals_lib.install_jax_listeners()
    signals_lib.uninstall_jax_listeners()
    jax.jit(lambda x: x - 3)(jnp.ones((3,))).block_until_ready()
    assert signals_lib.COMPILE_COUNTER not in fresh_registry.scalars()

  def test_sample_memory_reports_host_rss(self, fresh_registry):
    sampled = signals_lib.sample_memory(fresh_registry)
    assert sampled[signals_lib.HOST_RSS_GAUGE] > 0
    assert fresh_registry.scalars()[signals_lib.HOST_RSS_GAUGE] > 0
    # CPU devices expose no memory_stats: no fake device gauges.
    assert not any(tag.startswith('memory/device_')
                   for tag in fresh_registry.scalars())


# -- device feed channel scoping ---------------------------------------------


class TestFeedShapeChannels:

  def test_eval_batch_shape_does_not_trip_train_invariant(
      self, fresh_registry):
    """One feed serves train/eval/summary; each jitted program is
    shape-stable on its own, so a differently-sized eval batch must not
    push the must-stay-1 train gauge past 1."""
    from tensor2robot_tpu.data.device_feed import (
        FEED_SHAPES_GAUGE,
        SparseCoefFeed,
    )
    from tensor2robot_tpu.parallel import create_mesh

    feed = SparseCoefFeed({}, mesh=create_mesh({'data': 1},
                                               devices=jax.devices()[:1]))
    train_batch = {'features': {'x': np.zeros((8, 3), np.float32)}}
    eval_batch = {'features': {'x': np.zeros((2, 3), np.float32)}}
    feed.put_batch(train_batch)
    feed.put_batch(eval_batch, channel='eval')
    feed.put_batch(train_batch)
    assert fresh_registry.scalars()[FEED_SHAPES_GAUGE] == 1.0
    # A second TRAIN shape is the real violation.
    feed.put_batch({'features': {'x': np.zeros((9, 3), np.float32)}})
    assert fresh_registry.scalars()[FEED_SHAPES_GAUGE] == 2.0


# -- autoprofiler budget / rate limit ----------------------------------------


class TestAutoProfiler:

  def test_budget_allows_exactly_max_captures(self, tmp_path,
                                              fresh_registry):
    profiler = AutoProfiler(str(tmp_path), window_steps=1, max_captures=1,
                            min_interval_secs=0.0)
    assert profiler.request_capture('step_time_regression', 1)
    assert not profiler.request_capture('goodput_drop', 1)  # one pending
    profiler.maybe_profile(2)  # starts
    assert profiler.active
    assert not profiler.request_capture('goodput_drop', 2)  # one active
    report = profiler.maybe_profile(3)  # stops + reports
    assert report is not None and os.path.exists(report)
    assert profiler.captures_taken == 1
    assert not profiler.request_capture('goodput_drop', 4)  # budget spent
    assert fresh_registry.scalars()[
        'profiler/captures/step_time_regression'] == 1.0

  def test_rate_limit_blocks_back_to_back_windows(self, tmp_path,
                                                  fresh_registry):
    profiler = AutoProfiler(str(tmp_path), window_steps=1, max_captures=5,
                            min_interval_secs=3600.0, emit_reports=False)
    assert profiler.request_capture('step_time_regression', 1)
    profiler.maybe_profile(1)
    profiler.maybe_profile(2)
    assert profiler.captures_taken == 1
    # The incident is still flapping — but the last capture just ended.
    assert not profiler.request_capture('step_time_regression', 3)

  def test_static_window_does_not_consume_budget(self, tmp_path,
                                                 fresh_registry):
    # min_interval_secs high on purpose: a closing STATIC window must
    # not arm the triggered-capture rate limit either — a pre-planned
    # capture cannot delay the first incident response.
    profiler = AutoProfiler(str(tmp_path), static_window=(1, 2),
                            window_steps=1, max_captures=1,
                            min_interval_secs=3600.0)
    assert profiler.maybe_profile(0) is None
    profiler.maybe_profile(1)
    assert profiler.active
    report = profiler.maybe_profile(2)
    assert report is not None
    assert profiler.captures_taken == 0  # static: separate budget
    assert profiler.request_capture('goodput_drop', 3)  # still available
    profiler.maybe_profile(3)
    profiler.abort()  # close the triggered window without a report

  def test_abort_leaves_no_dangling_trace(self, tmp_path, fresh_registry):
    profiler = AutoProfiler(str(tmp_path), window_steps=10,
                            max_captures=1, min_interval_secs=0.0)
    profiler.request_capture('step_time_regression', 1)
    profiler.maybe_profile(1)
    profiler.abort()
    assert not profiler.active and not profiler.broken
    # A fresh window can start afterwards — the trace was really closed.
    profiler2 = AutoProfiler(str(tmp_path), static_window=(2, 3),
                             window_steps=1, emit_reports=False)
    profiler2.maybe_profile(2)
    assert profiler2.active and not profiler2.broken
    profiler2.maybe_profile(3)


# -- report building / degradation -------------------------------------------


class TestForensicsReport:

  def test_missing_capture_degrades_to_warning(self, fresh_registry):
    report = forensics_lib.build_report(step=7, reason='goodput_drop',
                                        xplane_path=None,
                                        goodput_fractions={'productive': 1.0})
    assert report['schema'] == forensics_lib.REPORT_SCHEMA
    assert report['top_ops'] == []
    assert any('no xplane' in w for w in report['warnings'])

  def test_attribution_names_the_empty_prefetch_queue(self):
    fractions = {'productive': 0.55, 'data': 0.34, 'checkpoint': 0.08,
                 'retry': 0.03}
    scalars = {'span/data.next/p95': 120.0,
               'data/prefetch_queue_depth/train': 0.0,
               'span/ckpt.save/p95': 900.0, 'span/ckpt.save/count': 4.0}
    ranked = forensics_lib.attribute_goodput(fractions, scalars)
    assert [entry['category'] for entry in ranked] == ['data', 'checkpoint']
    assert 'prefetch queue empty' in ranked[0]['detail']
    assert 'ckpt.save p95' in ranked[1]['detail']

  def test_write_and_read_reports(self, tmp_path):
    report = forensics_lib.build_report(step=3)
    path = forensics_lib.write_report(str(tmp_path), 3, report)
    assert path.endswith(os.path.join('forensics', '3.json'))
    # A torn report next to it is skipped, not fatal.
    with open(os.path.join(str(tmp_path), 'forensics', '9.json'),
              'w') as f:
      f.write('{"truncated": ')
    reports = forensics_lib.read_reports(str(tmp_path))
    assert [step for step, _ in reports] == [3]


# -- the acceptance loop -----------------------------------------------------


def _make_trainer(model_dir, **kwargs):
  kwargs.setdefault('save_checkpoints_steps', 10**9)
  kwargs.setdefault('async_checkpoints', False)
  return Trainer(MockT2RModel(), model_dir, **kwargs)


@pytest.mark.fault
class TestForensicsLoop:

  def test_injected_slowdown_trips_exactly_one_budgeted_capture(
      self, tmp_path, fresh_registry, monkeypatch):
    monkeypatch.setattr(fault_injection, 'SLOW_STEP_SECONDS', 0.25)
    fault_injection.set_injector(
        fault_injection.FaultInjector().fail('step.slow', times=6,
                                             after=8))
    model_dir = str(tmp_path)
    trainer = _make_trainer(
        model_dir, log_every_n_steps=2, profile_budget=1,
        profile_window_steps=2, profile_min_interval_secs=0.0,
        watchdog_config=obs.WatchdogConfig(min_baseline_windows=2))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=20)
    trainer.close()

    # The watchdog saw the regression...
    records = obs.read_telemetry(model_dir)
    anomalies = [r for r in records if r['kind'] == 'anomaly']
    assert any(r['anomaly'] == 'step_time_regression' for r in anomalies)
    assert fresh_registry.scalars()[
        'watchdog/anomalies/step_time_regression'] >= 1.0
    # ...which triggered EXACTLY ONE budgeted capture...
    assert trainer.auto_profiler.captures_taken == 1
    report_paths = glob.glob(os.path.join(model_dir, 'forensics',
                                          '*.json'))
    assert len(report_paths) == 1
    forensics_records = [r for r in records if r['kind'] == 'forensics']
    assert len(forensics_records) == 1
    assert forensics_records[0]['report'] == report_paths[0]
    # ...whose report attributes the window: top op + goodput fields.
    with open(report_paths[0]) as f:
      report = json.load(f)
    assert report['schema'] == forensics_lib.REPORT_SCHEMA
    assert report['reason'] == 'step_time_regression'
    assert report['trigger']['ratio'] > 1.0
    assert report['top_ops'], 'capture yielded no attributed ops'
    top = report['top_ops'][0]
    assert top['name'] and top['ms_per_step'] > 0.0
    assert set(report['goodput']) == {'productive', 'data', 'checkpoint',
                                      'retry'}
    assert abs(sum(report['goodput'].values()) - 1.0) < 1e-6
    assert isinstance(report['attribution'], list)
    assert report['window']['n_steps'] >= 1
    # The injected stall is host-side: the step itself did NOT recompile.
    assert fresh_registry.scalars()['recompiles/train_step'] == 1.0

  def test_clean_run_triggers_nothing_and_counts_one_compile(
      self, tmp_path, fresh_registry):
    model_dir = str(tmp_path)
    # Jitter-proof thresholds: the windows here are 2 millisecond-scale
    # mock steps, so one OS scheduling transient exceeds the production
    # 1.8x ratio and flips this test (observed ~1-in-3 under ambient
    # load on a 2-core container). 10x/0.9 still fail loudly on any
    # genuine anomaly — the injected-slowdown test above fires at ~50x
    # under the PRODUCTION defaults, so the clean/dirty asymmetry keeps
    # its teeth.
    trainer = _make_trainer(
        model_dir, log_every_n_steps=2,
        watchdog_config=obs.WatchdogConfig(regression_ratio=10.0,
                                           goodput_drop=0.9))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=10)
    trainer.close()
    assert trainer.auto_profiler.captures_taken == 0
    assert not os.path.isdir(os.path.join(model_dir, 'forensics'))
    records = obs.read_telemetry(model_dir)
    assert not [r for r in records if r['kind'] in ('anomaly',
                                                    'forensics')]
    # The acceptance number: one compile of the train step, ever.
    assert fresh_registry.scalars()['recompiles/train_step'] == 1.0
    trains = [r for r in records if r['kind'] == 'train']
    assert trains[-1]['gauges']['recompiles/train_step'] == 1.0
    # Memory watermarks rode along with every train record.
    assert trains[-1]['gauges']['memory/host_rss_bytes'] > 0

  def test_static_profile_window_still_produces_a_report(
      self, tmp_path, fresh_registry):
    model_dir = str(tmp_path)
    trainer = _make_trainer(model_dir, log_every_n_steps=100,
                            profile_steps=(2, 4))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=6)
    trainer.close()
    report_paths = glob.glob(os.path.join(model_dir, 'forensics',
                                          '*.json'))
    assert len(report_paths) == 1
    with open(report_paths[0]) as f:
      report = json.load(f)
    assert report['reason'] == 'static'
    assert trainer.auto_profiler.captures_taken == 0  # static != budget


# -- doctor ------------------------------------------------------------------


class TestDoctor:

  def _write_run(self, model_dir, productive=0.6, data=0.35,
                 recompiles=1.0, queue_depth=0.0, end=True):
    logger = obs.TelemetryLogger(model_dir)
    logger.log('run_start', step=0)
    goodput = {'productive': productive, 'data': data,
               'checkpoint': 1.0 - productive - data, 'retry': 0.0}
    for step in (2, 4, 6):
      logger.log('train', step=step, loss=0.5, examples_per_sec=100.0,
                 goodput=goodput,
                 counters={'reliability/nan_rollbacks': 0.0},
                 gauges={'data/prefetch_queue_depth/train': queue_depth,
                         'recompiles/train_step': recompiles})
      logger.heartbeat(step)
    if end:
      logger.log('run_end', step=6, goodput=goodput)
    logger.close()

  def test_ranked_goodput_attribution_across_samples(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, productive=0.6, data=0.35, queue_depth=0.0)
    findings = doctor_lib.diagnose(model_dir)
    messages = [f['message'] for f in findings]
    data_findings = [m for m in messages if 'lost to data' in m]
    assert data_findings, messages
    assert 'prefetch queue empty in 100% of samples' in data_findings[0]
    # Ranked: warnings (goodput) before the info findings.
    severities = [f['severity'] for f in findings]
    assert severities == sorted(
        severities, key=lambda s: {'critical': 0, 'warning': 1,
                                   'info': 2, 'ok': 3}[s])

  def test_recompile_diagnosis(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, productive=0.95, data=0.02,
                    recompiles=3.0)
    findings = doctor_lib.diagnose(model_dir)
    assert any('compiled 3 times' in f['message'] for f in findings)

  def test_stale_heartbeat_is_critical_for_live_run(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, end=False)  # still "running"
    future = time.time() + 10_000  # wall-clock: heartbeat timestamps
    findings = doctor_lib.diagnose(model_dir, now=future)
    assert findings[0]['severity'] == doctor_lib.CRITICAL
    assert 'heartbeat' in findings[0]['message']

  def test_finished_run_heartbeat_is_not_critical(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, productive=0.98, data=0.01, end=True)
    future = time.time() + 10_000  # wall-clock: heartbeat timestamps
    findings = doctor_lib.diagnose(model_dir, now=future)
    assert not any(f['severity'] == doctor_lib.CRITICAL for f in findings)

  def test_forensics_report_surfaces_in_diagnosis(self, tmp_path):
    model_dir = str(tmp_path)
    self._write_run(model_dir, productive=0.98, data=0.01)
    report = forensics_lib.build_report(step=4, reason='goodput_drop')
    report['top_ops'] = [{'name': '%convert_reduce_fusion',
                          'ms_per_step': 33.7, 'fraction': 0.19,
                          'source': 'device'}]
    forensics_lib.write_report(model_dir, 4, report)
    findings = doctor_lib.diagnose(model_dir)
    assert any('%convert_reduce_fusion' in f['message'] for f in findings)


# -- CLI ---------------------------------------------------------------------


class TestDoctorCLI:

  def _run(self, *argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_telemetry')]
        + list(argv),
        capture_output=True, text=True, timeout=120,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'})

  def test_doctor_smoke(self, tmp_path):
    model_dir = str(tmp_path)
    logger = obs.TelemetryLogger(model_dir)
    logger.log('run_start', step=0)
    logger.log('train', step=2, goodput={'productive': 1.0, 'data': 0.0,
                                         'checkpoint': 0.0, 'retry': 0.0},
               gauges={})
    logger.heartbeat(2)
    logger.log('run_end', step=2)
    logger.close()
    result = self._run('doctor', model_dir)
    assert result.returncode == 0, result.stderr
    assert 'doctor:' in result.stdout
    assert 'run finished' in result.stdout

  def test_doctor_exits_2_on_critical(self, tmp_path):
    model_dir = str(tmp_path)
    logger = obs.TelemetryLogger(model_dir)
    logger.log('run_start', step=0)
    logger.log('train', step=2, goodput={'productive': 1.0, 'data': 0.0,
                                         'checkpoint': 0.0, 'retry': 0.0})
    logger.heartbeat(2)  # run never ends; heartbeat goes stale
    logger.close()
    result = self._run('doctor', model_dir, '--heartbeat_stale_secs',
                       '-1')
    assert result.returncode == 2, result.stdout + result.stderr
    assert 'CRIT' in result.stdout

  def test_tail_missing_telemetry_exits_clean(self, tmp_path):
    result = self._run('tail', str(tmp_path))
    assert result.returncode == 0, result.stdout + result.stderr
    assert 'Traceback' not in result.stderr
    assert 'no telemetry at' in result.stdout
    assert len(result.stdout.strip().splitlines()) == 1

  def test_tail_empty_telemetry_exits_clean(self, tmp_path):
    (tmp_path / 'telemetry.jsonl').write_bytes(b'')
    result = self._run('tail', str(tmp_path))
    assert result.returncode == 0, result.stdout + result.stderr
    assert 'Traceback' not in result.stderr
    assert 'is empty' in result.stdout
    assert len(result.stdout.strip().splitlines()) == 1

  def test_summarize_missing_telemetry_exits_clean(self, tmp_path):
    result = self._run('summarize', str(tmp_path))
    assert result.returncode == 0, result.stdout + result.stderr
    assert 'Traceback' not in result.stderr
    assert 'no telemetry at' in result.stdout

  def test_summarize_empty_telemetry_exits_clean(self, tmp_path):
    (tmp_path / 'telemetry.jsonl').write_bytes(b'')
    result = self._run('summarize', str(tmp_path))
    assert result.returncode == 0, result.stdout + result.stderr
    assert 'Traceback' not in result.stderr
    assert 'is empty' in result.stdout
