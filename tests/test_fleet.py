"""Fleet observatory coverage (ISSUE 9 acceptance tests).

The federation layer end to end: per-host telemetry emission (indexed
filenames + identity stamps), a REAL two-process federation round-trip
on the subprocess fixture (``observability/fleet_sim.py`` — the harness
that replaces the jax.distributed dryrun this container cannot run),
torn/partial per-host merges, FleetWatchdog straggler/dead-host
detection, the live FleetObserver, the injected-straggler acceptance
loop (exactly one budgeted capture whose forensics report names the
gating host), the ``host.preempt`` -> ``t2r.recovery.v1`` recovery
timeline, the doctor's fleet verdicts, and the CLI surfaces
(``fleet``, ``--json``, multi-host ``tail`` interleaving).
"""

import importlib.machinery
import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from tensor2robot_tpu.observability import fleet as fleet_lib
from tensor2robot_tpu.observability import fleet_sim
from tensor2robot_tpu.observability import registry as registry_lib
from tensor2robot_tpu.observability import telemetry_file
from tensor2robot_tpu.observability import watchdog as watchdog_lib
from tensor2robot_tpu.observability.telemetry_file import TelemetryLogger

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T2R_TELEMETRY = os.path.join(REPO_ROOT, 'bin', 't2r_telemetry')


@pytest.fixture(autouse=True)
def fresh_registry():
  previous = registry_lib.set_registry(registry_lib.TelemetryRegistry())
  yield registry_lib.get_registry()
  registry_lib.set_registry(previous)


def _load_fleet_gate():
  """Imports bin/check_fleet_doctor (extensionless) for its fixtures."""
  path = os.path.join(REPO_ROOT, 'bin', 'check_fleet_doctor')
  loader = importlib.machinery.SourceFileLoader('check_fleet_doctor', path)
  spec = importlib.util.spec_from_loader('check_fleet_doctor', loader)
  module = importlib.util.module_from_spec(spec)
  loader.exec_module(module)
  return module


# -- per-host emission -------------------------------------------------------


class TestPerHostEmission:

  def test_multi_process_meta_routes_to_indexed_files(self, tmp_path):
    meta = fleet_sim.host_meta(1, 2, device_kind='TPU v4')
    logger = TelemetryLogger(str(tmp_path), host_meta=meta)
    record = logger.log('train', step=5, loss=0.1)
    logger.heartbeat(5)
    logger.close()
    assert os.path.exists(str(tmp_path / 'telemetry.1.jsonl'))
    assert os.path.exists(str(tmp_path / 'heartbeat.1.json'))
    assert not os.path.exists(str(tmp_path / 'telemetry.jsonl'))
    # Every record and heartbeat carries the full identity stamp.
    assert record['process_index'] == 1
    assert record['process_count'] == 2
    assert record['device_kind'] == 'TPU v4'
    assert record['hostname'] == 'simhost1'
    beat = telemetry_file.read_heartbeat(str(tmp_path), process_index=1)
    assert beat['process_index'] == 1
    assert beat['device_kind'] == 'TPU v4'

  def test_single_process_keeps_bare_filenames(self, tmp_path):
    # process_count == 1: today's layout, byte for byte — nothing
    # downstream of a single-host run may change.
    meta = fleet_sim.host_meta(0, 1)
    logger = TelemetryLogger(str(tmp_path), host_meta=meta)
    logger.log('train', step=1)
    logger.heartbeat(1)
    logger.close()
    assert os.path.exists(str(tmp_path / 'telemetry.jsonl'))
    assert os.path.exists(str(tmp_path / 'heartbeat.json'))
    assert not os.path.exists(str(tmp_path / 'telemetry.0.jsonl'))

  def test_rotation_is_per_host(self, tmp_path):
    meta = fleet_sim.host_meta(1, 2)
    logger = TelemetryLogger(str(tmp_path), max_bytes=300,
                             host_meta=meta)
    for step in range(30):
      logger.log('train', step=step, loss=0.5)
    logger.close()
    assert os.path.exists(str(tmp_path / 'telemetry.1.jsonl.1'))
    # read_telemetry stitches THIS host's generations, oldest first.
    records = telemetry_file.read_telemetry(
        str(tmp_path / 'telemetry.1.jsonl'))
    steps = [r['step'] for r in records]
    assert steps == sorted(steps)
    assert all(r['process_index'] == 1 for r in records)

  def test_discover_hosts_maps_bare_and_indexed(self, tmp_path):
    TelemetryLogger(str(tmp_path)).log('run_start')
    fleet_sim.write_host_run(str(tmp_path), 1, 2, [0.01])
    hosts = telemetry_file.discover_hosts(str(tmp_path))
    assert sorted(hosts) == [0, 1]
    assert hosts[0]['telemetry'].endswith('telemetry.jsonl')
    assert hosts[1]['telemetry'].endswith('telemetry.1.jsonl')
    assert hosts[1]['heartbeat'].endswith('heartbeat.1.json')

  def test_discover_hosts_empty_dir(self, tmp_path):
    assert telemetry_file.discover_hosts(str(tmp_path)) == {}


# -- two-process federation round-trip ---------------------------------------


class TestTwoProcessFederation:
  """The federation contract is files, not collectives, so it is proven
  without jax.distributed (tests/test_multihost.py covers that):
  two REAL concurrent processes, each writing its own
  per-host stream under one shared model_dir through the same
  TelemetryLogger path a real trainer process uses."""

  def test_round_trip(self, tmp_path):
    model_dir = str(tmp_path)
    procs = [
        subprocess.Popen(
            [sys.executable, '-m',
             'tensor2robot_tpu.observability.fleet_sim',
             '--model_dir', model_dir,
             '--process_index', str(pid), '--process_count', '2',
             '--step_times', times,
             '--sleep_per_window_secs', '0.05'],
            cwd=REPO_ROOT)
        for pid, times in ((0, '0.010,0.010,0.010'),
                           ('1', '0.020,0.020,0.020'))]
    for proc in procs:
      assert proc.wait(timeout=120) == 0
    # Both hosts emitted their own files...
    assert os.path.exists(os.path.join(model_dir, 'telemetry.0.jsonl'))
    assert os.path.exists(os.path.join(model_dir, 'telemetry.1.jsonl'))
    # ...the fleet view merges and aligns them...
    fleet = fleet_lib.read_fleet(model_dir)
    assert sorted(fleet['hosts']) == [0, 1]
    assert fleet['warnings'] == []
    aligned = fleet_lib.align_train_series(fleet)
    assert aligned['steps'] == [100, 200, 300]
    # ...and fleet goodput is the min across hosts at each aligned step.
    assert aligned['fleet_goodput'][300] == pytest.approx(0.9)
    summary = fleet_lib.fleet_summary(model_dir)
    assert summary['host_count'] == 2
    assert summary['gating_host'] == 1  # 20 ms vs 10 ms step time
    assert summary['step_time_skew'] == pytest.approx(20.0 / 15.0)
    merged = fleet_lib.merged_records(fleet)
    times = [r['time'] for r in merged]
    assert times == sorted(times)  # interleaved by record timestamp
    assert {r['process_index'] for r in merged} == {0, 1}


class TestTornPartialMerge:

  def test_torn_tail_and_corrupt_interior_degrade_to_warnings(
      self, tmp_path):
    model_dir = str(tmp_path)
    fleet_sim.write_host_run(model_dir, 0, 2, [0.01, 0.01])
    fleet_sim.write_host_run(model_dir, 1, 2, [0.01, 0.01])
    path = os.path.join(model_dir, 'telemetry.1.jsonl')
    with open(path, encoding='utf-8') as f:
      lines = f.read().splitlines()
    # Corrupt an interior line AND tear the tail mid-record.
    lines[1] = lines[1][:10] + '#corrupt#'
    lines.append('{"kind": "train", "torn')
    with open(path, 'w', encoding='utf-8') as f:
      f.write('\n'.join(lines))
    fleet = fleet_lib.read_fleet(model_dir)
    # Host 0 is untouched; host 1 lost exactly the corrupt line (the
    # torn tail is silently dropped, same as read_telemetry).
    assert len(fleet['hosts'][0]) == len(lines) - 1
    assert len(fleet['hosts'][1]) == len(lines) - 2
    assert any('host 1' in w and 'malformed' in w
               for w in fleet['warnings'])
    # The single-stream reader still raises on interior corruption —
    # the fleet merge is the only tolerant path.
    with pytest.raises(ValueError):
      telemetry_file.read_telemetry(path)

  def test_heartbeat_only_host_is_partial_not_fatal(self, tmp_path):
    model_dir = str(tmp_path)
    fleet_sim.write_host_run(model_dir, 0, 2, [0.01])
    logger = TelemetryLogger(model_dir,
                             host_meta=fleet_sim.host_meta(1, 2))
    logger.heartbeat(0)
    logger.close()
    os.remove(os.path.join(model_dir, 'telemetry.1.jsonl'))
    fleet = fleet_lib.read_fleet(model_dir)
    assert fleet['hosts'][1] == []
    assert fleet['heartbeats'][1] is not None
    assert any('host 1' in w for w in fleet['warnings'])


# -- fleet watchdog ----------------------------------------------------------


class TestFleetWatchdog:

  def _dog(self, **kwargs):
    kwargs.setdefault('min_baseline_windows', 2)
    return fleet_lib.FleetWatchdog(fleet_lib.FleetConfig(**kwargs))

  def test_straggler_fires_after_baseline_and_names_host(
      self, fresh_registry):
    dog = self._dog()
    assert dog.observe(1, {0: 0.010, 1: 0.011}) == []
    assert dog.observe(2, {0: 0.010, 1: 0.010}) == []
    anomalies = dog.observe(3, {0: 0.010, 1: 0.050})
    assert [a.kind for a in anomalies] == ['straggler']
    assert anomalies[0].detail['host'] == 1
    assert anomalies[0].detail['ratio'] > 2.0
    assert fresh_registry.scalars()[
        'watchdog/anomalies/straggler'] == 1.0

  def test_sustained_straggler_keeps_firing(self):
    # Anomalous windows never fold into the baseline, so a sustained
    # straggler cannot normalize itself away.
    dog = self._dog()
    dog.observe(1, {0: 0.010, 1: 0.010})
    dog.observe(2, {0: 0.010, 1: 0.010})
    for step in range(3, 8):
      assert dog.observe(step, {0: 0.010, 1: 0.050}), \
          'straggler self-normalized'

  def test_fleet_jitter_below_ratio_never_fires(self):
    dog = self._dog()
    for step in range(8):
      assert dog.observe(step, {0: 0.010, 1: 0.013, 2: 0.011}) == []

  def test_born_straggler_is_caught_without_healthy_history(self):
    # A host slow from its very FIRST window (bad chip at boot) must
    # still be named: the peer-median reference needs no healthy
    # baseline, only the warm-up damping windows.
    dog = self._dog(min_baseline_windows=2)
    assert dog.observe(1, {0: 0.010, 1: 0.040}) == []  # warm-up
    assert dog.observe(2, {0: 0.010, 1: 0.040}) == []
    anomalies = dog.observe(3, {0: 0.010, 1: 0.040})
    assert [a.kind for a in anomalies] == ['straggler']
    assert anomalies[0].detail['host'] == 1
    assert anomalies[0].detail['peer_median_s'] == pytest.approx(0.010)

  def test_fleet_wide_slowdown_is_not_a_straggler(self):
    # Every host slowing TOGETHER is a step_time_regression (the
    # per-host watchdog's verdict), not skew: no host lags its peers,
    # so no straggler may fire even against a fast stale baseline.
    dog = self._dog()
    for step in range(1, 5):
      assert dog.observe(step, {0: 0.010, 1: 0.011}) == []
    for step in range(5, 9):
      assert dog.observe(step, {0: 0.050, 1: 0.055}) == [], \
          'fleet-wide slowdown misattributed as a straggler'

  def test_single_host_never_fires(self):
    dog = self._dog()
    for step in range(8):
      assert dog.observe(step, {0: 0.010 * (step + 1)}) == []

  def test_host_dead_fires_once_and_rearms_on_recovery(
      self, fresh_registry):
    dog = self._dog(heartbeat_stale_secs=60.0)
    now = 1e9
    fresh = {'time': now - 1.0, 'step': 100}
    stale = {'time': now - 3600.0, 'step': 40, 'hostname': 'h1',
             'pid': 7}
    anomalies = dog.check_heartbeats({0: fresh, 1: stale}, now)
    assert [a.kind for a in anomalies] == ['host_dead']
    assert anomalies[0].detail['host'] == 1
    assert anomalies[0].detail['hostname'] == 'h1'
    # Latched: a dead host is reported once...
    assert dog.check_heartbeats({0: fresh, 1: stale}, now) == []
    # ...until it comes back fresh, which re-arms the detection.
    assert dog.check_heartbeats({0: fresh, 1: {'time': now}}, now) == []
    assert [a.kind for a in dog.check_heartbeats(
        {0: fresh, 1: stale}, now)] == ['host_dead']

  def test_all_hosts_stale_is_not_host_dead(self):
    # Everyone stale = the run is wedged/stopped (the existing
    # heartbeat_stale diagnosis), not a fleet-partition verdict.
    dog = self._dog(heartbeat_stale_secs=60.0)
    now = 1e9
    stale = {'time': now - 3600.0}
    assert dog.check_heartbeats({0: dict(stale), 1: dict(stale)},
                                now) == []

  def test_missing_heartbeat_file_is_not_dead(self):
    dog = self._dog(heartbeat_stale_secs=60.0)
    now = 1e9
    assert dog.check_heartbeats({0: {'time': now}, 1: None}, now) == []


class TestFleetObserver:

  def test_observer_reads_peer_heartbeats_and_emits_record(
      self, tmp_path):
    model_dir = str(tmp_path)
    fleet_sim.write_host_run(model_dir, 1, 2, [0.040], end='live')
    observer = fleet_lib.FleetObserver(
        model_dir, fleet_sim.host_meta(0, 2),
        config=fleet_lib.FleetConfig(min_baseline_windows=2))
    record, anomalies = observer.observe(
        100, step_time_s=0.010, examples_per_sec=3200.0,
        productive_fraction=0.95)
    assert anomalies == []
    assert record['schema'] == fleet_lib.FLEET_RECORD_SCHEMA
    assert record['host_count'] == 2
    assert record['gating_host'] == 1
    assert record['fleet_min_goodput'] == pytest.approx(0.9)
    assert record['hosts']['1']['step_time_s'] == pytest.approx(0.040)

  def test_observer_single_host_emits_nothing(self, tmp_path):
    observer = fleet_lib.FleetObserver(str(tmp_path),
                                       fleet_sim.host_meta(0, 1))
    record, anomalies = observer.observe(10, step_time_s=0.01)
    assert record is None and anomalies == []

  def test_observer_detects_own_straggle_against_peers(self, tmp_path):
    model_dir = str(tmp_path)
    fleet_sim.write_host_run(model_dir, 1, 3, [0.010], end='live')
    fleet_sim.write_host_run(model_dir, 2, 3, [0.010], end='live')
    observer = fleet_lib.FleetObserver(
        model_dir, fleet_sim.host_meta(0, 3),
        config=fleet_lib.FleetConfig(min_baseline_windows=2))
    for step in (10, 20, 30):
      _, anomalies = observer.observe(step, step_time_s=0.010,
                                      productive_fraction=0.9)
      assert anomalies == []
    record, anomalies = observer.observe(40, step_time_s=0.200,
                                         productive_fraction=0.5)
    assert [a.kind for a in anomalies] == ['straggler']
    assert anomalies[0].detail['host'] == 0  # the observer itself
    assert 'straggler' in record['anomalies']


# -- recovery timeline -------------------------------------------------------


class TestRecoveryTimeline:

  def test_marker_round_trip_is_consumed_once(self, tmp_path):
    model_dir = str(tmp_path)
    fleet_lib.write_recovery_marker(model_dir, 123, -1, 1.25)
    marker = fleet_lib.consume_recovery_marker(model_dir)
    assert marker['step'] == 123
    assert marker['save_seconds'] == pytest.approx(1.25)
    # Consumed: one preemption -> exactly one recovery record.
    assert fleet_lib.consume_recovery_marker(model_dir) is None

  def test_per_host_markers_do_not_collide(self, tmp_path):
    model_dir = str(tmp_path)
    fleet_lib.write_recovery_marker(model_dir, 10, -1, 0.1,
                                    process_index=0)
    fleet_lib.write_recovery_marker(model_dir, 20, -1, 0.2,
                                    process_index=1)
    assert fleet_lib.consume_recovery_marker(
        model_dir, process_index=1)['step'] == 20
    assert fleet_lib.consume_recovery_marker(
        model_dir, process_index=0)['step'] == 10

  def test_record_phases_partition_the_timeline(self):
    now = 1e9
    marker = {'time': now - 10.0, 'step': 50, 'signum': 15,
              'save_seconds': 2.0}
    record = fleet_lib.build_recovery_record(
        marker, restore_seconds=3.0, first_step_seconds=1.0,
        resume_step=51, now=now)
    assert record['schema'] == fleet_lib.RECOVERY_SCHEMA
    phases = record['phases']
    assert phases['emergency_save_s'] == pytest.approx(2.0)
    assert phases['restore_s'] == pytest.approx(3.0)
    assert phases['first_step_s'] == pytest.approx(1.0)
    assert phases['downtime_s'] == pytest.approx(6.0)
    assert record['preemption_recovery_seconds'] == pytest.approx(12.0)
    assert sum(phases.values()) == pytest.approx(
        record['preemption_recovery_seconds'])

  def test_record_invariant_survives_cross_host_clock_skew(self):
    # Resume on a host whose wall clock runs BEHIND the preempting
    # host's: the marker-to-now span reads shorter than the locally
    # measured monotonic durations. The measured durations are the
    # floor — phases must still partition the total exactly.
    now = 1e9
    marker = {'time': now - 1.0, 'step': 50, 'signum': 15,
              'save_seconds': 2.0}
    record = fleet_lib.build_recovery_record(
        marker, restore_seconds=3.0, first_step_seconds=1.0,
        resume_step=51, now=now)
    phases = record['phases']
    assert phases['downtime_s'] == 0.0
    assert record['preemption_recovery_seconds'] == pytest.approx(6.0)
    assert sum(phases.values()) == pytest.approx(
        record['preemption_recovery_seconds'])


# -- doctor fleet verdicts ---------------------------------------------------


class TestDoctorFleet:

  def _diagnose(self, model_dir):
    from tensor2robot_tpu.observability import doctor
    return doctor.diagnose(model_dir)

  def test_straggler_fixture_is_critical_naming_host(self, tmp_path):
    gate = _load_fleet_gate()
    gate.write_fleet_run(str(tmp_path), 'straggler')
    findings = self._diagnose(str(tmp_path))
    hits = [f for f in findings if f['severity'] == 'critical'
            and f['detail'].get('kind') == 'straggler']
    assert hits and hits[0]['detail']['host'] == 1

  def test_dead_host_fixture_is_critical_naming_host(self, tmp_path):
    gate = _load_fleet_gate()
    gate.write_fleet_run(str(tmp_path), 'dead_host')
    findings = self._diagnose(str(tmp_path))
    hits = [f for f in findings if f['severity'] == 'critical'
            and f['detail'].get('kind') == 'host_dead']
    assert hits and hits[0]['detail']['host'] == 1

  def test_clean_fleet_has_no_critical_and_shows_fleet_section(
      self, tmp_path):
    gate = _load_fleet_gate()
    gate.write_fleet_run(str(tmp_path), 'clean')
    findings = self._diagnose(str(tmp_path))
    assert not [f for f in findings if f['severity'] == 'critical']
    assert any(f['detail'].get('host_count') == 2 for f in findings)

  def test_indexed_streams_shadow_a_leftover_bare_run(self, tmp_path):
    # Mixed model_dir: an OLD finished single-process run (bare files,
    # stale heartbeat) followed by a LIVE fleet restart (indexed
    # files). The indexed-wins precedence must hold everywhere: judging
    # run_ended/staleness from the leftover bare files would both page
    # on a healthy fleet (stale bare heartbeat) and silence a real
    # incident (bare run_end suppressing the live dead host).
    model_dir = str(tmp_path)
    now = time.time()
    old = TelemetryLogger(model_dir)
    old.log('run_start', step=0)
    old.log('run_end', step=10)
    old.heartbeat(10, time=now - 7200.0)
    old.close()
    gate = _load_fleet_gate()
    gate.write_fleet_run(model_dir, 'dead_host')
    # read_heartbeat's default prefers the indexed (fresh) heartbeat...
    beat = telemetry_file.read_heartbeat(model_dir)
    assert now - beat['time'] < 300.0
    # ...and doctor judges the LIVE fleet: host 1 dead is CRITICAL,
    # with no spurious whole-run heartbeat_stale page.
    findings = self._diagnose(model_dir)
    hits = [f for f in findings if f['detail'].get('kind') == 'host_dead']
    assert hits and hits[0]['severity'] == 'critical'
    assert not any('wedged' in f['message'] for f in findings
                   if f['severity'] == 'critical')

  def test_dead_host_after_run_end_downgrades_to_warning(self, tmp_path):
    model_dir = str(tmp_path)
    now = time.time()
    fleet_sim.write_host_run(model_dir, 0, 2, [0.010] * 3,
                             end='run_end')
    fleet_sim.write_host_run(model_dir, 1, 2, [0.010, 0.010],
                             end='live', heartbeat_time=now - 3600.0)
    findings = self._diagnose(model_dir)
    hits = [f for f in findings if f['detail'].get('kind') == 'host_dead']
    assert hits and hits[0]['severity'] == 'warning'

  def test_fleet_summary_is_registry_pure(self, tmp_path, fresh_registry):
    # A digest must not fire live counters: doctor/summarize runs over
    # a dead-host dir may repeat arbitrarily without inflating
    # watchdog/anomalies.
    gate = _load_fleet_gate()
    gate.write_fleet_run(str(tmp_path), 'dead_host')
    for _ in range(3):
      summary = fleet_lib.fleet_summary(str(tmp_path))
      assert summary['dead_hosts'] == [1]
    assert 'watchdog/anomalies/host_dead' not in fresh_registry.scalars()

  def test_recovered_straggler_downgrades_to_warning(self, tmp_path):
    gate = _load_fleet_gate()
    model_dir = str(tmp_path)
    gate.write_fleet_run(model_dir, 'straggler')
    # A LATER healthy fleet window means the skew passed: history, not
    # a live page — doctor must release the automation gate.
    logger = TelemetryLogger(model_dir,
                             host_meta=fleet_sim.host_meta(0, 2))
    logger.log('fleet', step=500, schema='t2r.fleet.v1', host_count=2,
               step_time_skew=1.0, gating_host=0, fleet_min_goodput=0.9,
               anomalies=[])
    logger.close()
    findings = self._diagnose(model_dir)
    hits = [f for f in findings
            if f['detail'].get('kind') == 'straggler']
    assert hits and hits[0]['severity'] == 'warning'
    assert hits[0]['detail']['recovered'] is True

  def test_gate_subprocess_passes(self):
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin',
                                      'check_fleet_doctor')],
        capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stdout + result.stderr


# -- CLI ---------------------------------------------------------------------


class TestFleetCLI:

  def _run(self, *argv):
    return subprocess.run([sys.executable, T2R_TELEMETRY] + list(argv),
                          capture_output=True, text=True, timeout=120)

  def test_fleet_command_renders_table_and_json(self, tmp_path):
    model_dir = str(tmp_path)
    fleet_sim.write_host_run(model_dir, 0, 2, [0.010] * 3)
    fleet_sim.write_host_run(model_dir, 1, 2, [0.020] * 3)
    result = self._run('fleet', model_dir)
    assert result.returncode == 0, result.stderr
    assert '2 hosts' in result.stdout
    assert 'gating' in result.stdout
    payload = json.loads(self._run('fleet', model_dir,
                                   '--json').stdout)
    assert payload['host_count'] == 2
    assert payload['gating_host'] == 1

  def test_summarize_reads_the_live_indexed_stream_in_a_mixed_dir(
      self, tmp_path):
    # Leftover bare single-process run + live fleet: summarize must
    # report the FLEET's goodput (indexed-wins, same primary stream as
    # doctor), not the dead bare stream's.
    model_dir = str(tmp_path)
    old = TelemetryLogger(model_dir)
    old.log('train', step=10, loss=9.9, examples_per_sec=1.0,
            goodput={'productive': 0.1, 'data': 0.9, 'checkpoint': 0.0,
                     'retry': 0.0})
    old.log('run_end', step=10, goodput={'productive': 0.1, 'data': 0.9,
                                         'checkpoint': 0.0, 'retry': 0.0})
    old.close()
    fleet_sim.write_host_run(model_dir, 0, 2, [0.010] * 2)
    fleet_sim.write_host_run(model_dir, 1, 2, [0.010] * 2)
    payload = json.loads(self._run('summarize', model_dir,
                                   '--json').stdout)
    assert payload['goodput']['fractions']['productive'] == \
        pytest.approx(0.9)  # the fleet's, not the bare leftover's 0.1

  def test_summarize_and_doctor_json_parse(self, tmp_path):
    model_dir = str(tmp_path)
    fleet_sim.write_host_run(model_dir, 0, 2, [0.010] * 2)
    fleet_sim.write_host_run(model_dir, 1, 2, [0.010] * 2)
    payload = json.loads(self._run('summarize', model_dir,
                                   '--json').stdout)
    assert payload['fleet']['host_count'] == 2
    assert payload['goodput']['fractions']['productive'] == \
        pytest.approx(0.9)
    result = self._run('doctor', '--json', model_dir)
    payload = json.loads(result.stdout)
    assert result.returncode == 0
    assert payload['critical'] is False
    assert isinstance(payload['findings'], list)

  def test_tail_interleaves_hosts_by_timestamp(self, tmp_path):
    model_dir = str(tmp_path)
    # Alternate writes so the interleaving is real, not coincidental.
    loggers = {
        host: TelemetryLogger(model_dir,
                              host_meta=fleet_sim.host_meta(host, 2))
        for host in (0, 1)}
    for step in (10, 20, 30):
      for host, logger in loggers.items():
        logger.log('train', step=step, loss=0.5, examples_per_sec=1.0,
                   goodput={'productive': 1.0})
        time.sleep(0.01)
    for logger in loggers.values():
      logger.close()
    result = self._run('tail', model_dir, '--lines', '10')
    assert result.returncode == 0, result.stderr
    lines = [l for l in result.stdout.splitlines() if l.startswith('[h')]
    prefixes = [line.split(']')[0] + ']' for line in lines]
    assert '[h0]' in prefixes and '[h1]' in prefixes
    # Timestamp order => strict host alternation for alternating writes.
    assert prefixes == ['[h0]', '[h1]'] * 3

  def test_tail_follow_interleaves_live_appends(self, tmp_path):
    model_dir = str(tmp_path)
    for host in (0, 1):
      fleet_sim.write_host_run(model_dir, host, 2, [0.01])
    proc = subprocess.Popen(
        [sys.executable, T2R_TELEMETRY, 'tail', model_dir, '--follow',
         '--poll_secs', '0.2'],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
      time.sleep(0.8)  # backlog printed, follower armed
      loggers = {
          host: TelemetryLogger(model_dir,
                                host_meta=fleet_sim.host_meta(host, 2))
          for host in (0, 1)}
      for host, logger in loggers.items():
        logger.log('train', step=999, loss=0.1, examples_per_sec=1.0,
                   goodput={'productive': 1.0})
        logger.flush()
      for logger in loggers.values():
        logger.close()
      time.sleep(1.0)
    finally:
      proc.terminate()
      stdout, _ = proc.communicate(timeout=30)
    live = [l for l in stdout.splitlines() if 'step=999' in l]
    assert any(l.startswith('[h0]') for l in live), stdout
    assert any(l.startswith('[h1]') for l in live), stdout


# -- the acceptance loop (jax) -----------------------------------------------


@pytest.mark.fault
class TestFleetLoop:

  def _make_trainer(self, model_dir, **kwargs):
    from tensor2robot_tpu.trainer import Trainer
    from tensor2robot_tpu.utils.mocks import MockT2RModel
    from tensor2robot_tpu import observability as obs

    kwargs.setdefault('save_checkpoints_steps', 10**9)
    kwargs.setdefault('async_checkpoints', False)
    kwargs.setdefault('enable_fleet', True)
    kwargs.setdefault(
        'watchdog_config',
        obs.WatchdogConfig(regression_ratio=10.0, goodput_drop=0.9))
    return Trainer(MockT2RModel(), model_dir, **kwargs)

  def test_injected_straggler_trips_one_capture_naming_host(
      self, tmp_path, fresh_registry, monkeypatch):
    from tensor2robot_tpu import observability as obs
    from tensor2robot_tpu.reliability import fault_injection
    from tensor2robot_tpu.utils.mocks import MockInputGenerator

    monkeypatch.setattr(fault_injection, 'SLOW_STEP_SECONDS', 0.25)
    fault_injection.set_injector(
        fault_injection.FaultInjector().fail('step.slow', times=8,
                                             after=8))
    model_dir = str(tmp_path)
    # Two simulated peers with fresh heartbeats and fast steps: THIS
    # process is the straggler the fleet watchdog must name.
    for peer in (1, 2):
      fleet_sim.write_host_run(model_dir, peer, 3, [0.004], end='live')
    trainer = self._make_trainer(
        model_dir, log_every_n_steps=2, profile_budget=1,
        profile_window_steps=2, profile_min_interval_secs=0.0,
        fleet_config=fleet_lib.FleetConfig(min_baseline_windows=2))
    try:
      trainer.train(MockInputGenerator(batch_size=8),
                    max_train_steps=20)
    finally:
      trainer.close()
      fault_injection.set_injector(None)

    records = telemetry_file.read_telemetry(
        os.path.join(model_dir, 'telemetry.jsonl'))
    anomalies = [r for r in records if r['kind'] == 'anomaly']
    stragglers = [r for r in anomalies if r['anomaly'] == 'straggler']
    assert stragglers, [r['anomaly'] for r in anomalies]
    assert stragglers[0]['detail']['host'] == 0
    # Exactly ONE budgeted capture, claimed by the FLEET kind (fleet
    # observes before the generic watchdog, so the straggler — which
    # carries the host attribution — wins the capture request).
    assert trainer.auto_profiler.captures_taken == 1
    import glob
    report_paths = glob.glob(os.path.join(model_dir, 'forensics',
                                          '*.json'))
    assert len(report_paths) == 1
    with open(report_paths[0]) as f:
      report = json.load(f)
    assert report['reason'] == 'straggler'
    # The report names the gating host...
    assert report['trigger']['host'] == 0
    assert report['host']['process_index'] == 0
    assert report['host']['hostname']
    # ...and carries the compute-vs-collective-wait split — WHICH host
    # gated WHICH collective. (Even this 1-CPU-device step carries
    # degenerate all-reduce thunks, so the gating collective is named
    # right here, not only on a real mesh.)
    split = report['collective_wait']
    assert split is not None
    assert split['compute_ms_per_step'] > 0.0
    assert 0.0 <= split['collective_wait_fraction'] <= 1.0
    if split['collectives']:
      assert split['gating_collective']
      assert all(c['kind'] in ('all-reduce', 'all-gather', 'all-to-all',
                               'collective-permute', 'reduce-scatter',
                               'collective-broadcast')
                 for c in split['collectives'])
    # Fleet records rode along at the log cadence.
    fleet_records = [r for r in records if r['kind'] == 'fleet']
    assert fleet_records
    assert fleet_records[-1]['host_count'] == 3

  def test_clean_fleet_run_fires_zero_fleet_anomalies(
      self, tmp_path, fresh_registry):
    from tensor2robot_tpu.utils.mocks import MockInputGenerator

    model_dir = str(tmp_path)
    # Peers matching this host's mock step time, jitter-proof ratio.
    for peer in (1, 2):
      fleet_sim.write_host_run(model_dir, peer, 3, [0.002], end='live')
    trainer = self._make_trainer(
        model_dir, log_every_n_steps=2,
        fleet_config=fleet_lib.FleetConfig(straggler_ratio=10.0,
                                           min_baseline_windows=2))
    trainer.train(MockInputGenerator(batch_size=8), max_train_steps=10)
    trainer.close()
    records = telemetry_file.read_telemetry(
        os.path.join(model_dir, 'telemetry.jsonl'))
    fleet_anomalies = [r for r in records if r['kind'] == 'anomaly'
                       and r['anomaly'] in ('straggler', 'host_dead')]
    assert fleet_anomalies == []
    assert trainer.auto_profiler.captures_taken == 0
    fleet_records = [r for r in records if r['kind'] == 'fleet']
    assert fleet_records and fleet_records[-1]['anomalies'] == []

  def test_host_preempt_site_yields_recovery_record(
      self, tmp_path, fresh_registry):
    from tensor2robot_tpu.reliability import fault_injection
    from tensor2robot_tpu.reliability.errors import TrainingPreempted
    from tensor2robot_tpu.utils.mocks import MockInputGenerator

    model_dir = str(tmp_path)
    fault_injection.set_injector(
        fault_injection.FaultInjector().fail('host.preempt', times=1,
                                             after=5))
    trainer = self._make_trainer(model_dir, log_every_n_steps=2,
                                 enable_fleet=False)
    try:
      with pytest.raises(TrainingPreempted):
        trainer.train(MockInputGenerator(batch_size=8),
                      max_train_steps=20)
    finally:
      trainer.close()
      fault_injection.set_injector(None)
    # The marker started the recovery clock...
    assert os.path.exists(fleet_lib.recovery_marker_path(model_dir))
    records = telemetry_file.read_telemetry(model_dir)
    assert records[-1]['kind'] == 'preempted'
    assert records[-1]['signum'] == \
        fault_injection.INJECTED_PREEMPT_SIGNUM

    # ...and the resuming trainer closes the timeline.
    trainer2 = self._make_trainer(model_dir, log_every_n_steps=2,
                                  enable_fleet=False)
    trainer2.train(MockInputGenerator(batch_size=8), max_train_steps=20)
    trainer2.close()
    assert not os.path.exists(fleet_lib.recovery_marker_path(model_dir))
    records = telemetry_file.read_telemetry(model_dir)
    recoveries = [r for r in records if r['kind'] == 'recovery']
    assert len(recoveries) == 1
    recovery = recoveries[0]
    assert recovery['schema'] == fleet_lib.RECOVERY_SCHEMA
    assert recovery['resume_step'] > recovery['preempted_step']
    phases = recovery['phases']
    assert set(phases) == {'emergency_save_s', 'downtime_s',
                           'restore_s', 'first_step_s'}
    assert recovery['preemption_recovery_seconds'] > 0.0
    assert recovery['preemption_recovery_seconds'] == pytest.approx(
        sum(phases.values()), rel=1e-6)
    assert fresh_registry.scalars()[fleet_lib.RECOVERY_GAUGE] > 0.0
    # Doctor surfaces the timeline.
    from tensor2robot_tpu.observability import doctor
    findings = doctor.diagnose(model_dir)
    assert any(f['detail'].get('kind') == 'recovery' for f in findings)
