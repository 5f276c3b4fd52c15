"""Roofline observatory coverage (ISSUE 19 acceptance tests).

The accounting first: the HLO-parse cost model must match hand-computed
FLOPs/bytes EXACTLY on a synthetic module, and match the backend's own
``cost_analysis()`` exactly on a toy jitted program (matmul + tanh +
elementwise) — then within 5% on the real Grasping44 critic step, the
parity that lets the trainer's live gauges and the forensics roofline
record share ONE cost helper. Then the plumbing: build_record's
sum-reconciliation invariant, the watchdog's ``mfu_regression``
detection (and its silence on CPU where the MFU gauge never publishes),
the capture -> ``t2r.roofline.v1`` loop under an injected slow step, and
the ``bin/check_roofline_doctor`` fixtures replayed through doctor.
"""

import glob
import importlib.machinery
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import observability as obs
from tensor2robot_tpu.observability import doctor as doctor_lib
from tensor2robot_tpu.observability import roofline
from tensor2robot_tpu.observability import watchdog as watchdog_lib
from tensor2robot_tpu.parallel import hlo_analysis
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


# -- cost model --------------------------------------------------------------


# Hand-auditable synthetic module: every number below is computed in the
# comments, so a parser regression fails against arithmetic, not a
# recorded blob.
_SYNTHETIC_HLO = """\
HloModule toy

%fused_computation (param_0: f32[8,4]) -> f32[8,4] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  %tanh.1 = f32[8,4]{1,0} tanh(f32[8,4]{1,0} %param_0)
  ROOT %add.1 = f32[8,4]{1,0} add(f32[8,4]{1,0} %tanh.1, f32[8,4]{1,0} %param_0)
}

ENTRY %main (a: f32[8,16], b: f32[16,4]) -> f32[8,4] {
  %a = f32[8,16]{1,0} parameter(0)
  %b = f32[16,4]{1,0} parameter(1)
  %dot.2 = f32[8,4]{1,0} dot(f32[8,16]{1,0} %a, f32[16,4]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %fusion.3 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %dot.2), kind=kLoop, calls=%fused_computation
}
"""


class TestCostModel:

  def test_synthetic_module_matches_hand_computation_exactly(self):
    table = hlo_analysis.op_cost_table(_SYNTHETIC_HLO)
    # dot: 2 * out_elems(32) * contracted_extent(16) = 1024 flops;
    # bytes = a(8*16*4=512) + b(16*4*4=256) + out(8*4*4=128) = 896.
    assert table['%dot'] == {'flops': 1024.0, 'bytes': 896.0,
                             'transcendentals': 0.0, 'count': 1}
    # fusion: recursive into %fused_computation — add = 32 flops, tanh =
    # 32 TRANSCENDENTALS (XLA counts them separately, never in flops);
    # bytes at the fusion boundary only: operand 128 + output 128
    # (the fused interior and its parameter are free).
    assert table['%fusion'] == {'flops': 32.0, 'bytes': 256.0,
                                'transcendentals': 32.0, 'count': 1}
    totals = hlo_analysis.hlo_program_cost(_SYNTHETIC_HLO)
    assert totals['flops'] == 1056.0
    assert totals['bytes'] == 1152.0
    assert totals['transcendentals'] == 32.0

  def test_toy_jitted_program_matches_cost_analysis_exactly(self):
    """The parse IS the backend's count on a real compiled program."""
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 4), jnp.float32)
    compiled = jax.jit(lambda a, b: jnp.tanh(a @ b) + 1.0).lower(
        a, b).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
      analysis = analysis[0]
    parsed = hlo_analysis.hlo_program_cost(compiled.as_text())
    assert parsed['flops'] == float(analysis['flops'])
    assert parsed['bytes'] == float(analysis['bytes accessed'])
    assert parsed['transcendentals'] == float(
        analysis.get('transcendentals', 0.0))

  def test_program_cost_prefers_cost_analysis_and_falls_back(self):
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 4), jnp.float32)
    compiled = jax.jit(lambda a, b: jnp.tanh(a @ b) + 1.0).lower(
        a, b).compile()
    cost = hlo_analysis.program_cost(compiled)
    assert cost['source'] == 'cost_analysis'
    assert cost['flops'] > 0 and cost['bytes'] > 0
    fallback = hlo_analysis.program_cost(_SYNTHETIC_HLO)
    assert fallback['source'] == 'hlo_parse'
    assert fallback['flops'] == 1056.0

  def test_grasping44_critic_step_parity_within_5pct(self):
    """Satellite 2's bar: parse vs cost_analysis on the REAL critic loss
    grad — the program bench.py's flops_per_step now resolves through."""
    from tensor2robot_tpu.data.input_generators import (
        DefaultRandomInputGenerator,
    )
    from tensor2robot_tpu.modes import ModeKeys
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
    )

    model = Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
        device_type='cpu')
    generator = DefaultRandomInputGenerator(batch_size=2)
    generator.set_specification_from_model(model, ModeKeys.TRAIN)
    features, labels = next(
        generator.create_dataset_iterator(mode=ModeKeys.TRAIN, seed=0))
    features, labels = model.preprocessor.preprocess(
        features, labels, ModeKeys.TRAIN, rng=jax.random.PRNGKey(1))
    variables = model.init_variables(jax.random.PRNGKey(0), features,
                                     labels)
    params = variables.pop('params')

    def _loss(p):
      loss, _ = model.loss_fn(p, variables, features, labels,
                              ModeKeys.TRAIN, jax.random.PRNGKey(2))
      return loss

    compiled = jax.jit(jax.grad(_loss)).lower(params).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
      analysis = analysis[0]
    backend_flops = float(analysis['flops'])
    parsed = hlo_analysis.hlo_program_cost(compiled.as_text())
    assert backend_flops > 1e8, 'critic grad unexpectedly tiny'
    assert abs(parsed['flops'] - backend_flops) / backend_flops < 0.05


# -- roofline math -----------------------------------------------------------


class TestRooflineMath:

  def test_device_peaks_table(self):
    flops, bw = roofline.device_peaks('TPU v5e')
    assert flops == 197e12 and bw == 819e9
    assert roofline.device_peaks('TPU v4') == (275e12, 1228e9)
    assert roofline.device_peaks('cpu') is None
    assert roofline.device_peaks('') is None

  def test_classify_bound_bands(self):
    ridge = 100.0
    assert roofline.classify_bound(200.0, ridge) == 'compute'
    assert roofline.classify_bound(50.0, ridge) == 'memory'
    assert roofline.classify_bound(100.0, ridge) == 'ragged'
    assert roofline.classify_bound(126.0, ridge) == 'compute'
    assert roofline.classify_bound(74.0, ridge) == 'memory'
    assert roofline.classify_bound(None, ridge) is None

  def test_normalize_family_joins_both_namings(self):
    # xplane event names vs HLO instruction names fold to one key.
    assert roofline.normalize_family('%fusion.12') == '%fusion'
    assert roofline.normalize_family('fusion.12') == '%fusion'
    assert roofline.normalize_family(
        '%dot.3 = f32[8,4] dot(...)') == '%dot'

  def test_build_record_sum_reconciles_and_ranks(self):
    # Measured families include one name with NO cost-table entry
    # (host-executor naming) and the table includes one family with NO
    # measured event — the unattributed row must absorb both sides so
    # the table still sums to the program totals.
    families = [('%fusion.1', 4.0), ('%unknown_thunk', 1.0)]
    cost_table = {
        '%fusion.1': {'flops': 1e9, 'bytes': 8e8, 'transcendentals': 0.0,
                      'count': 1},
        '%convolution.2': {'flops': 5e12, 'bytes': 2e9,
                           'transcendentals': 0.0, 'count': 1},
    }
    record = roofline.build_record(families, cost_table, 'TPU v5e',
                                   step=7, step_time_s=0.01)
    assert record['schema'] == roofline.ROOFLINE_SCHEMA
    assert record['mode'] == 'roofline'
    rows = {row['family']: row for row in record['families']}
    assert roofline.UNATTRIBUTED in rows
    assert sum(row['flops'] for row in record['families']) == \
        pytest.approx(record['flops_per_step'])
    assert sum(row['bytes'] for row in record['families']) == \
        pytest.approx(record['bytes_per_step'])
    # fusion.1: intensity 1.25 flops/byte — far under the v5e ridge
    # (~240.5) — memory-bound, and the only measured memory-bound row,
    # so it is the gating family.
    assert rows['%fusion']['bound'] == 'memory'
    assert record['gating_memory_bound_family'] == '%fusion'
    # headroom = measured 4 ms - roofline-bound ms (bytes-bound:
    # 8e8 / 819e9 = 0.977 ms).
    assert rows['%fusion']['headroom_ms'] == pytest.approx(
        4.0 - 8e8 / 819e9 * 1e3, abs=1e-3)
    # MFU: total flops / step_time / peak.
    assert record['mfu'] == pytest.approx(
        (1e9 + 5e12) / 0.01 / 197e12, abs=1e-6)
    # The unmeasured convolution carries its cost, ms=None.
    assert rows[roofline.UNATTRIBUTED]['ms'] is None

  def test_build_record_cpu_degrades_to_intensity_only(self):
    record = roofline.build_record(
        [('%fusion.1', 2.0)],
        {'%fusion.1': {'flops': 1e6, 'bytes': 1e6,
                       'transcendentals': 0.0, 'count': 1}},
        'cpu', step=1, step_time_s=0.5)
    assert record['mode'] == 'intensity-only'
    assert record['mfu'] is None
    assert record['peak_flops'] is None
    row = record['families'][0]
    assert row['intensity'] == 1.0
    assert row['bound'] is None and row['pct_peak'] is None

  def test_static_gating_family(self):
    table = {
        '%fusion.9': {'flops': 1e9, 'bytes': 8e8},      # memory-bound
        '%fusion.2': {'flops': 1e7, 'bytes': 1e7},      # memory, smaller
        '%convolution.1': {'flops': 5e12, 'bytes': 2e9},  # compute
    }
    assert roofline.static_gating_family(table, 'TPU v5e') == '%fusion'
    assert roofline.static_gating_family(table, 'cpu') is None
    assert roofline.static_gating_family(
        {'%convolution.1': {'flops': 5e12, 'bytes': 2e9}},
        'TPU v5e') is None

  def test_publish_perf_gauges(self, fresh_registry):
    published = roofline.publish_perf_gauges(
        fresh_registry, flops_per_step=1.97e12, bytes_per_step=8.19e9,
        step_time_s=0.1, device_kind='TPU v5e')
    assert published == (pytest.approx(0.1), pytest.approx(0.1))
    scalars = fresh_registry.scalars()
    assert scalars[roofline.MFU_GAUGE] == pytest.approx(0.1)
    assert scalars[roofline.HBM_BW_GAUGE] == pytest.approx(0.1)

  def test_publish_perf_gauges_cpu_never_touches_gauges(
      self, fresh_registry):
    assert roofline.publish_perf_gauges(
        fresh_registry, 1e12, 1e9, 0.1, 'cpu') is None
    assert roofline.MFU_GAUGE not in fresh_registry.scalars()

  def test_telemetry_payload_compacts(self):
    record = roofline.build_record(
        [('%fusion.1', 4.0)],
        {'%fusion.1': {'flops': 1e9, 'bytes': 8e8}},
        'TPU v5e', step=7, step_time_s=0.01)
    payload = roofline.telemetry_payload(record, top_k=5)
    assert payload['schema'] == roofline.ROOFLINE_SCHEMA
    assert payload['gating_memory_bound_family'] == '%fusion'
    assert set(payload['families'][0]) == {
        'family', 'ms', 'intensity', 'bound', 'headroom_ms'}


# -- watchdog mfu_regression -------------------------------------------------


class TestWatchdogMFU:

  def _config(self, **kwargs):
    kwargs.setdefault('min_baseline_windows', 2)
    return watchdog_lib.WatchdogConfig(**kwargs)

  def test_mfu_regression_fires_below_ratio(self, fresh_registry):
    dog = obs.Watchdog(self._config(mfu_regression_ratio=0.75))
    gauge = fresh_registry.gauge(roofline.MFU_GAUGE)
    gauge.set(0.40)
    assert dog.observe(1, 0.1) == []
    assert dog.observe(2, 0.1) == []
    gauge.set(0.38)
    assert dog.observe(3, 0.1) == []  # jitter, not a regression
    gauge.set(0.10)
    anomalies = dog.observe(4, 0.1)
    assert [a.kind for a in anomalies] == [watchdog_lib.MFU_REGRESSION]
    assert anomalies[0].detail['mfu'] == pytest.approx(0.10)
    assert anomalies[0].detail['baseline_mfu'] > 0.3
    assert fresh_registry.scalars()[
        'watchdog/anomalies/mfu_regression'] == 1.0

  def test_regressed_windows_stay_out_of_baseline(self, fresh_registry):
    dog = obs.Watchdog(self._config())
    gauge = fresh_registry.gauge(roofline.MFU_GAUGE)
    gauge.set(0.40)
    dog.observe(1, 0.1)
    dog.observe(2, 0.1)
    gauge.set(0.10)
    for step in range(3, 8):
      assert dog.observe(step, 0.1), 'mfu regression self-normalized'

  def test_absent_gauge_is_not_applicable(self, fresh_registry):
    # CPU shape: publish_perf_gauges never set the gauge; the watchdog
    # must treat that as not-applicable, not as 0% MFU.
    dog = obs.Watchdog(self._config())
    for step in range(1, 6):
      assert dog.observe(step, 0.1) == []


# -- capture -> t2r.roofline.v1 loop -----------------------------------------


def _make_trainer(model_dir, **kwargs):
  kwargs.setdefault('save_checkpoints_steps', 10**9)
  kwargs.setdefault('async_checkpoints', False)
  return Trainer(MockT2RModel(), model_dir, **kwargs)


@pytest.mark.fault
class TestCaptureRoofline:

  def test_slow_step_capture_builds_reconciled_record(
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

    report_paths = glob.glob(os.path.join(model_dir, 'forensics',
                                          '*.json'))
    assert len(report_paths) == 1
    with open(report_paths[0]) as f:
      report = json.load(f)
    record = report['roofline']
    assert record is not None, report.get('warnings')
    assert record['schema'] == roofline.ROOFLINE_SCHEMA
    # CPU: honest degradation, classified + ranked without raising.
    assert record['mode'] == 'intensity-only'
    assert record['families'], 'no attribution rows'
    assert record['flops_per_step'] > 0
    # The sum-reconciliation acceptance bar (±5%; exact by construction
    # — the unattributed row carries whatever the join missed).
    total = sum(row['flops'] for row in record['families'])
    assert total == pytest.approx(record['flops_per_step'], rel=0.05)
    assert sum(row['bytes'] for row in record['families']) == \
        pytest.approx(record['bytes_per_step'], rel=0.05)
    # The compact telemetry record rode along with the forensics one.
    records = obs.read_telemetry(model_dir)
    roofline_records = [r for r in records if r['kind'] == 'roofline']
    assert len(roofline_records) == 1
    assert roofline_records[0]['schema'] == roofline.ROOFLINE_SCHEMA
    assert roofline_records[0]['flops_per_step'] == pytest.approx(
        record['flops_per_step'])

  def test_clean_run_zero_mfu_regressions(self, tmp_path,
                                          fresh_registry):
    model_dir = str(tmp_path)
    trainer = _make_trainer(
        model_dir, log_every_n_steps=2,
        watchdog_config=obs.WatchdogConfig(min_baseline_windows=2))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=10)
    trainer.close()
    records = obs.read_telemetry(model_dir)
    assert not any(
        r.get('anomaly') == watchdog_lib.MFU_REGRESSION
        for r in records if r['kind'] == 'anomaly')
    scalars = fresh_registry.scalars()
    assert scalars.get('watchdog/anomalies/mfu_regression', 0.0) == 0.0


# -- doctor + CI gate --------------------------------------------------------


def _load_gate():
  path = os.path.join(REPO_ROOT, 'bin', 'check_roofline_doctor')
  loader = importlib.machinery.SourceFileLoader('check_roofline_doctor',
                                                path)
  spec = importlib.util.spec_from_loader('check_roofline_doctor', loader)
  module = importlib.util.module_from_spec(spec)
  loader.exec_module(module)
  return module


class TestDoctorRoofline:

  def test_low_mfu_live_fixture_is_critical_naming_family(self, tmp_path):
    gate = _load_gate()
    model_dir = str(tmp_path)
    gate.write_run(model_dir, mfu=0.11, ended=False)
    findings = doctor_lib.diagnose(model_dir)
    verdicts = [f for f in findings
                if (f.get('detail') or {}).get('kind') == 'roofline']
    assert verdicts and verdicts[0]['severity'] == doctor_lib.CRITICAL
    detail = verdicts[0]['detail']
    assert detail['gating_memory_bound_family'] == gate.GATING_FAMILY
    assert detail['headroom_ms'] == pytest.approx(14.9)
    assert gate.GATING_FAMILY in verdicts[0]['message']

  def test_ended_low_mfu_downgrades_to_warning(self, tmp_path):
    gate = _load_gate()
    model_dir = str(tmp_path)
    gate.write_run(model_dir, mfu=0.11, ended=True)
    findings = doctor_lib.diagnose(model_dir)
    verdicts = [f for f in findings
                if (f.get('detail') or {}).get('kind') == 'roofline']
    assert verdicts and verdicts[0]['severity'] == doctor_lib.WARNING

  def test_healthy_and_intensity_only_fixtures_are_info(self, tmp_path):
    gate = _load_gate()
    clean_dir = str(tmp_path / 'clean')
    cpu_dir = str(tmp_path / 'cpu')
    gate.write_run(clean_dir, mfu=0.37, ended=True)
    gate.write_run(cpu_dir, mfu=0.0, ended=True, mode='intensity-only')
    for model_dir, expected_mode in ((clean_dir, 'roofline'),
                                     (cpu_dir, 'intensity-only')):
      findings = doctor_lib.diagnose(model_dir)
      verdicts = [f for f in findings
                  if (f.get('detail') or {}).get('kind') == 'roofline']
      assert verdicts, model_dir
      assert verdicts[0]['severity'] == doctor_lib.INFO
      if expected_mode == 'intensity-only':
        assert verdicts[0]['detail'].get('mode') == 'intensity-only'
