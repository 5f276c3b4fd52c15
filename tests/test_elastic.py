"""Elastic multi-host training coverage (ISSUE 15 acceptance tests).

The coordinator-led elastic stack end to end: lease-based membership
(renewal, orderly leave vs. lapse, coordinator re-election), the world
-> mesh planner (DCN x ICI factoring, dense shard reassignment,
checkpoint resharding rules), the ``t2r.elastic.v1`` event vocabulary,
the fleet-sim membership-churn writers feeding the doctor's
shrink-aware verdicts (orderly-departure downgrade, stuck-rebuild
paging), the ``ELASTIC_BENCH_KEYS`` axes collector, and — as slow
tests — the REAL subprocess federation: a single-host driver
round-trip, the cross-process CompiledArtifact correctness pin (the
donation bug that motivated the no-donation artifact path), and the
full 3-host shrink-on-SIGKILL / grow-on-rejoin acceptance run.
"""

import importlib.machinery
import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from tensor2robot_tpu.elastic import axes as axes_lib
from tensor2robot_tpu.elastic import membership
from tensor2robot_tpu.elastic import topology
from tensor2robot_tpu.observability import fleet_sim
from tensor2robot_tpu.observability import registry as registry_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T2R_TELEMETRY = os.path.join(REPO_ROOT, 'bin', 't2r_telemetry')


@pytest.fixture(autouse=True)
def fresh_registry():
  previous = registry_lib.set_registry(registry_lib.TelemetryRegistry())
  yield registry_lib.get_registry()
  registry_lib.set_registry(previous)


def _load_elastic_gate():
  """Imports bin/check_elastic_doctor (extensionless) for its fixtures."""
  path = os.path.join(REPO_ROOT, 'bin', 'check_elastic_doctor')
  loader = importlib.machinery.SourceFileLoader('check_elastic_doctor',
                                                path)
  spec = importlib.util.spec_from_loader('check_elastic_doctor', loader)
  module = importlib.util.module_from_spec(spec)
  loader.exec_module(module)
  return module


def _subprocess_env():
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  env.pop('XLA_FLAGS', None)
  return env


# -- membership: leases ------------------------------------------------------


class TestLeases:

  def test_write_read_roundtrip(self, tmp_path):
    membership.write_lease(str(tmp_path), 2, incarnation=3)
    leases = membership.read_leases(str(tmp_path))
    assert set(leases) == {2}
    assert leases[2]['incarnation'] == 3
    assert leases[2]['status'] == 'active'

  def test_release_flips_to_leaving_but_stays_on_disk(self, tmp_path):
    membership.write_lease(str(tmp_path), 0)
    membership.release_lease(str(tmp_path), 0)
    leases = membership.read_leases(str(tmp_path))
    assert leases[0]['status'] == 'leaving'

  def test_invalid_status_rejected(self, tmp_path):
    with pytest.raises(ValueError):
      membership.write_lease(str(tmp_path), 0, status='zombie')

  def test_observe_classifies_active_leaving_lapsed(self, tmp_path):
    now = time.time()  # wall-clock: fixture stamps cross-process files
    membership.write_lease(str(tmp_path), 0, now=now)
    membership.write_lease(str(tmp_path), 1, now=now - 100.0)
    membership.write_lease(str(tmp_path), 2, now=now)
    membership.release_lease(str(tmp_path), 2)
    view = membership.observe(str(tmp_path), lease_ttl_secs=5.0, now=now)
    assert view.active == (0,)
    assert view.lapsed == (1,)
    assert view.leaving == (2,)

  def test_coordinator_is_lowest_active_and_reelects(self, tmp_path):
    now = time.time()  # wall-clock: fixture stamps cross-process files
    membership.write_lease(str(tmp_path), 0, now=now - 100.0)
    membership.write_lease(str(tmp_path), 1, now=now)
    membership.write_lease(str(tmp_path), 2, now=now)
    view = membership.observe(str(tmp_path), 5.0, now=now)
    # Host 0's lease lapsed: host 1 is now the coordinator.
    assert membership.elect_coordinator(view) == 1

  def test_torn_lease_read_as_absent(self, tmp_path):
    path = membership.lease_path(str(tmp_path), 0)
    os.makedirs(str(tmp_path), exist_ok=True)
    with open(path, 'w') as f:
      f.write('{"half": ')  # torn mid-replace
    assert membership.read_leases(str(tmp_path)) == {}

  def test_lease_keeper_renews_and_stops(self, tmp_path):
    keeper = membership.LeaseKeeper(str(tmp_path), 0, renew_secs=0.05)
    keeper.start()
    try:
      time.sleep(0.3)
      first = membership.read_leases(str(tmp_path))[0]['time']
      time.sleep(0.3)
      second = membership.read_leases(str(tmp_path))[0]['time']
      assert second > first, 'keeper stopped renewing'
    finally:
      keeper.stop(orderly=True)
    assert membership.read_leases(str(tmp_path))[0]['status'] == 'leaving'

  def test_lease_keeper_non_orderly_stop_leaves_lease_active(self,
                                                             tmp_path):
    keeper = membership.LeaseKeeper(str(tmp_path), 1, renew_secs=0.05)
    keeper.start()
    keeper.stop(orderly=False)
    # The preemption simulation: the lease still CLAIMS active and will
    # lapse naturally once its stamp ages out.
    assert membership.read_leases(str(tmp_path))[1]['status'] == 'active'

  def test_incarnation_increments_across_rejoins(self, tmp_path):
    first = membership.LeaseKeeper(str(tmp_path), 0, renew_secs=10.0)
    first.start()
    first.stop(orderly=False)
    second = membership.LeaseKeeper(str(tmp_path), 0, renew_secs=10.0)
    assert second.incarnation == first.incarnation + 1


# -- membership: world plan --------------------------------------------------


class TestWorldPlan:

  def test_publish_read_roundtrip_and_ranks(self, tmp_path):
    plan = membership.publish_plan(str(tmp_path), 2, [4, 0, 2],
                                   boundary_step=10, coordinator=0)
    read = membership.read_plan(str(tmp_path))
    assert read == plan
    assert read['hosts'] == [0, 2, 4]
    assert read['world_size'] == 3
    # Dense ranks over the sorted member list.
    assert membership.plan_rank(read, 0) == 0
    assert membership.plan_rank(read, 2) == 1
    assert membership.plan_rank(read, 4) == 2
    assert membership.plan_rank(read, 7) is None

  def test_missing_plan_is_none(self, tmp_path):
    assert membership.read_plan(str(tmp_path)) is None


# -- topology ----------------------------------------------------------------


class TestTopology:

  def test_fsdp_stays_ici_local_dcn_carries_data_only(self):
    plan = topology.plan_mesh(3, 4, per_host_batch=8)
    assert plan.ici_axis_sizes == {'data': 2, 'fsdp': 2}
    assert plan.dcn_axis_sizes == {'data': 3}
    assert plan.global_batch == 24
    assert plan.global_device_count == 12

  def test_odd_local_devices_disable_fsdp(self):
    plan = topology.plan_mesh(2, 3, per_host_batch=4)
    assert plan.ici_axis_sizes == {'data': 3, 'fsdp': 1}
    assert not plan.use_fsdp

  def test_shard_reassignment_closes_over_departed_rank(self):
    before = topology.plan_mesh(3, 2, 8, hosts=[0, 1, 2])
    after = topology.plan_mesh(2, 2, 8, hosts=[0, 2], epoch=2)
    assert topology.shard_assignment(before, 2) == (2, 3)
    # Host 2 inherits the departed host 1's dense rank: between them
    # the survivors re-cover every input shard.
    assert topology.shard_assignment(after, 2) == (1, 2)
    assert topology.shard_assignment(after, 0) == (0, 2)

  def test_reshard_plan_names_what_changes(self):
    before = topology.plan_mesh(3, 2, 8, hosts=[0, 1, 2])
    after = topology.plan_mesh(2, 2, 8, hosts=[0, 2], epoch=2)
    reshard = topology.reshard_plan(before, after)
    assert reshard['world_before'] == 3 and reshard['world_after'] == 2
    assert reshard['global_batch_before'] == 24
    assert reshard['global_batch_after'] == 16
    assert reshard['rank_moves'] == {'2': {'before': 2, 'after': 1}}

  def test_invalid_plans_rejected(self):
    with pytest.raises(ValueError):
      topology.plan_mesh(0, 2, 8)
    with pytest.raises(ValueError):
      topology.plan_mesh(2, 0, 8)
    with pytest.raises(ValueError):
      topology.plan_mesh(2, 2, 8, hosts=[0, 1, 2])


# -- fleet_sim membership churn ----------------------------------------------


class TestMemberChurn:

  def test_orderly_leave_writes_events_and_leaving_lease(self, tmp_path):
    fleet_sim.write_member_run(str(tmp_path), 1, 3, [0.01, 0.01],
                               membership_end='leave')
    leases = membership.read_leases(str(tmp_path))
    assert leases[1]['status'] == 'leaving'
    from tensor2robot_tpu.observability import fleet as fleet_lib
    records = fleet_lib.merged_records(fleet_lib.read_fleet(str(tmp_path)))
    events = [r['event'] for r in records if r.get('kind') == 'elastic']
    assert events == [membership.EVENT_JOIN, membership.EVENT_LEAVE]

  def test_lapse_backdates_an_active_lease(self, tmp_path):
    fleet_sim.write_member_run(str(tmp_path), 2, 3, [0.01],
                               membership_end='lapse')
    view = membership.observe(str(tmp_path), lease_ttl_secs=60.0)
    assert view.lapsed == (2,)

  def test_live_member_keeps_fresh_active_lease(self, tmp_path):
    fleet_sim.write_member_run(str(tmp_path), 0, 3, [0.01],
                               membership_end='live')
    view = membership.observe(str(tmp_path), lease_ttl_secs=60.0)
    assert view.active == (0,)

  def test_subprocess_member_churn(self, tmp_path):
    """Membership churn with REAL processes: join/leave/lapse mid-run."""
    procs = []
    for host, end in ((0, 'live'), (1, 'leave'), (2, 'lapse')):
      procs.append(subprocess.Popen(
          [sys.executable, '-m',
           'tensor2robot_tpu.observability.fleet_sim',
           '--model_dir', str(tmp_path), '--process_index', str(host),
           '--process_count', '3', '--member',
           '--membership_end', end,
           '--step_times', '0.01,0.01',
           '--sleep_per_window_secs', '0.05'],
          cwd=REPO_ROOT, env=_subprocess_env()))
    for proc in procs:
      assert proc.wait(timeout=60) == 0
    view = membership.observe(str(tmp_path), lease_ttl_secs=60.0)
    assert view.active == (0,)
    assert view.leaving == (1,)
    assert view.lapsed == (2,)

  def test_shrink_ladder_fixture_vocabulary(self, tmp_path):
    fleet_sim.write_shrink_events(str(tmp_path), 0, epoch=2,
                                  world_before=3, world_after=2,
                                  departed=[1], orderly=False,
                                  complete=True, recovery=True)
    from tensor2robot_tpu.observability import fleet as fleet_lib
    records = fleet_lib.merged_records(fleet_lib.read_fleet(str(tmp_path)))
    elastic = [r for r in records if r.get('kind') == 'elastic']
    assert [r['event'] for r in elastic] == [
        membership.EVENT_SHRINK_BEGIN,
        membership.EVENT_SHRINK_PHASE, membership.EVENT_SHRINK_PHASE,
        membership.EVENT_SHRINK_PHASE, membership.EVENT_REBUILD,
        membership.EVENT_SHRINK]
    phases = [r['phase'] for r in elastic
              if r['event'] == membership.EVENT_SHRINK_PHASE]
    assert tuple(phases) == membership.SHRINK_PHASES
    recovery = [r for r in records if r.get('kind') == 'recovery']
    assert len(recovery) == 1
    assert recovery[0]['world_before'] == 3
    assert recovery[0]['world_after'] == 2
    assert recovery[0]['signum'] == membership.ELASTIC_LAPSE_SIGNUM


# -- doctor verdicts ---------------------------------------------------------


class TestDoctorElastic:

  def _diagnose(self, model_dir):
    from tensor2robot_tpu.observability import doctor
    return doctor.diagnose(str(model_dir))

  def test_stuck_rebuild_pages_naming_phase_and_host(self, tmp_path):
    gate = _load_elastic_gate()
    gate.write_elastic_run(str(tmp_path), 'stuck')
    findings = self._diagnose(tmp_path)
    stalled = [f for f in findings
               if f['detail'].get('kind') == 'elastic_rebuild_stalled']
    assert len(stalled) == 1
    assert stalled[0]['severity'] == 'critical'
    assert stalled[0]['detail']['phase'] == 'mesh_rebuild'
    assert stalled[0]['detail']['host'] == 0
    assert stalled[0]['detail']['departed'] == [2]

  def test_clean_shrink_summarizes_without_paging(self, tmp_path):
    gate = _load_elastic_gate()
    gate.write_elastic_run(str(tmp_path), 'clean')
    findings = self._diagnose(tmp_path)
    assert not [f for f in findings if f['severity'] == 'critical'], [
        (f['severity'], f['message']) for f in findings]
    summary = [f for f in findings
               if f['detail'].get('kind') == 'elastic_summary']
    assert summary and summary[0]['detail']['shrinks'] == 1

  def test_orphaned_begin_superseded_by_successor_does_not_page(
      self, tmp_path):
    # The declaring coordinator (host 0) dies mid-ladder: its
    # shrink_begin at epoch 2 is orphaned (only emergency_save done,
    # never completed). A successor coordinator (host 1) then completes
    # the resize at epoch 3 — the fleet manifestly reconfigured past
    # the orphaned begin, so doctor must summarize, not page a
    # permanent elastic_rebuild_stalled CRITICAL.
    fleet_sim.write_shrink_events(str(tmp_path), 0, epoch=2,
                                  world_before=3, world_after=2,
                                  departed=[2], orderly=False,
                                  phases=('emergency_save',),
                                  complete=False)
    fleet_sim.write_shrink_events(str(tmp_path), 1, epoch=3,
                                  world_before=2, world_after=1,
                                  departed=[0], orderly=False,
                                  complete=True, recovery=True,
                                  process_count=3)
    findings = self._diagnose(tmp_path)
    stalled = [f for f in findings
               if f['detail'].get('kind') == 'elastic_rebuild_stalled']
    assert not stalled, [(f['severity'], f['message']) for f in stalled]
    summary = [f for f in findings
               if f['detail'].get('kind') == 'elastic_summary']
    assert summary and summary[0]['detail']['shrinks'] == 1

  def test_orderly_departure_downgrades_while_dead_host_pages(
      self, tmp_path):
    gate = _load_elastic_gate()
    gate.write_elastic_run(str(tmp_path), 'departed_and_dead')
    findings = self._diagnose(tmp_path)
    dead = [f for f in findings if f['detail'].get('kind') == 'host_dead']
    departed = [f for f in findings
                if f['detail'].get('kind') == 'host_departed_orderly']
    assert len(dead) == 1 and dead[0]['detail']['host'] == 2
    assert dead[0]['severity'] == 'critical'
    assert len(departed) == 1 and departed[0]['detail']['host'] == 1
    assert departed[0]['severity'] == 'info'

  def test_gate_passes_end_to_end(self):
    result = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, 'bin', 'check_elastic_doctor')],
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT)
    assert result.returncode == 0, result.stdout + result.stderr

  def test_cli_tail_formats_elastic_records(self, tmp_path):
    fleet_sim.write_shrink_events(str(tmp_path), 0, epoch=2,
                                  world_before=3, world_after=2,
                                  departed=[1], orderly=True,
                                  complete=True)
    result = subprocess.run(
        [sys.executable, T2R_TELEMETRY, 'tail', str(tmp_path),
         '--lines', '50'],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
    assert 'event=shrink_begin' in result.stdout
    assert 'world 3->2' in result.stdout
    assert 'departed=[1] (orderly)' in result.stdout
    assert 'phase=emergency_save' in result.stdout

  def test_cli_summarize_has_elastic_section(self, tmp_path):
    fleet_sim.write_member_run(str(tmp_path), 0, 2, [0.01, 0.01],
                               membership_end='leave')
    fleet_sim.write_shrink_events(str(tmp_path), 0, epoch=2,
                                  world_before=2, world_after=1,
                                  departed=[1], orderly=True,
                                  complete=True)
    result = subprocess.run(
        [sys.executable, T2R_TELEMETRY, 'summarize', str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
    assert 'elastic: world size' in result.stdout
    result = subprocess.run(
        [sys.executable, T2R_TELEMETRY, 'summarize', '--json',
         str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    payload = json.loads(result.stdout)
    assert payload['elastic']['shrinks'] == 1


# -- axes collector ----------------------------------------------------------


class TestAxesCollector:

  def test_collects_schema_from_fixture_run(self, tmp_path):
    gate = _load_elastic_gate()
    gate.write_elastic_run(str(tmp_path), 'clean')
    axes = axes_lib.collect_axes(str(tmp_path))
    assert set(axes) == set(axes_lib.ELASTIC_BENCH_KEYS)
    assert axes['elastic_shrinks'] == 1
    assert axes['elastic_hosts'] >= 2

  def test_cold_start_rebuilds_excluded_from_surviving_compiles(
      self, tmp_path):
    from tensor2robot_tpu.observability.telemetry_file import (
        TelemetryLogger,
    )
    logger = TelemetryLogger(str(tmp_path),
                             host_meta=fleet_sim.host_meta(1, 2))
    # Incarnation 1: cold bind (epoch 1), then a WARM rebuild (epoch 2).
    logger.log('elastic', step=0, **membership.elastic_record(
        membership.EVENT_JOIN, host=1))
    logger.log('elastic', step=1, **membership.elastic_record(
        membership.EVENT_REBUILD, host=1, epoch=1, compiles_delta=4.0))
    logger.log('elastic', step=2, **membership.elastic_record(
        membership.EVENT_REBUILD, host=1, epoch=2, compiles_delta=1.0))
    # Incarnation 2 (rejoin): its first rebuild is a process cold start
    # and must NOT count against the zero-compile claim.
    logger.log('elastic', step=2, **membership.elastic_record(
        membership.EVENT_JOIN, host=1))
    logger.log('elastic', step=3, **membership.elastic_record(
        membership.EVENT_REBUILD, host=1, epoch=3, compiles_delta=2.0))
    logger.log('elastic', step=4, **membership.elastic_record(
        membership.EVENT_REBUILD, host=1, epoch=4, compiles_delta=0.0))
    logger.close()
    axes = axes_lib.collect_axes(str(tmp_path))
    # Only the warm epoch-2 rebuild's 1.0 counts: epoch 1 is the first
    # bind, epoch 3 is the rejoin cold start, epoch 4 is warm at 0.
    assert axes['elastic_surviving_compiles'] == 1.0
    assert axes['elastic_rebind_outcomes'] == ['None', 'None', 'None']


# -- the real subprocess federation (slow) -----------------------------------


def _driver_cmd(base_dir, host, world, total_steps=10**6,
                max_run_seconds=120.0, extra=()):
  return [sys.executable, '-m', 'tensor2robot_tpu.elastic.driver',
          '--base_dir', str(base_dir), '--host', str(host),
          '--world', str(world), '--local_device_count', '2',
          '--boundary_steps', '2', '--per_host_batch', '8',
          '--lease_ttl_secs', '4.0', '--renew_secs', '0.5',
          '--total_steps', str(total_steps),
          '--max_run_seconds', str(max_run_seconds),
          '--stop_file', os.path.join(str(base_dir), 'STOP'),
          ] + list(extra)


@pytest.mark.slow
class TestSingleHostDriver:

  def test_single_host_roundtrip_with_doctor_green(self, tmp_path):
    """World 1: join -> plan -> rebuild -> segments -> orderly leave."""
    proc = subprocess.run(
        _driver_cmd(tmp_path, 0, 1, total_steps=4),
        cwd=REPO_ROOT, env=_subprocess_env(), capture_output=True,
        text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert 'done at step 4' in proc.stdout
    from tensor2robot_tpu.observability import fleet as fleet_lib
    records = fleet_lib.merged_records(fleet_lib.read_fleet(str(tmp_path)))
    events = [r['event'] for r in records if r.get('kind') == 'elastic']
    assert events[0] == membership.EVENT_JOIN
    assert membership.EVENT_GROW in events
    assert membership.EVENT_REBUILD in events
    assert events[-1] == membership.EVENT_LEAVE
    # Doctor judges the finished run clean.
    result = subprocess.run(
        [sys.executable, T2R_TELEMETRY, 'doctor', str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stdout + result.stderr


@pytest.mark.slow
class TestCrossProcessArtifact:

  def test_deserialized_step_matches_self_compiled(self, tmp_path):
    """The donation-bug pin: a persisted train step deserialized in a
    DIFFERENT process must advance a restored state by exactly one step.

    With donation baked into the serialized executable this came back
    step+2 with a skewed rng fold (or outright garbage counters) on
    this jaxlib's CPU backend — the reason the artifact path compiles
    without donation (trainer/train_eval.py)."""
    script = r'''
import os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
sys.path.insert(0, {repo!r})
import jax
from tensor2robot_tpu.trainer import Trainer
from tensor2robot_tpu.utils.mocks import MockT2RModel, MockInputGenerator

base, phase = sys.argv[1], sys.argv[2]
host_dir = os.path.join(base, 'host_' + phase)
trainer = Trainer(MockT2RModel(device_type='cpu'), host_dir,
                  use_fsdp=True, async_checkpoints=False,
                  save_checkpoints_steps=10**9, log_every_n_steps=10**9,
                  use_compiled_artifacts=True,
                  artifact_workload='elastic_step',
                  tuning_cache_path=os.path.join(base, 'cache.json'))
gen = MockInputGenerator(batch_size=8)
state = trainer.train(gen, max_train_steps=2)
artifact = trainer._train_step_artifact
assert artifact is not None, 'artifact bind failed'
if phase == 'compile':
    assert not artifact.from_cache, artifact.outcome
else:
    assert artifact.from_cache, artifact.outcome
# Rebuild-and-restore: a fresh trainer over the SAME host dir restores
# the committed checkpoint and probes one step through the store-bound
# executable — the exact flow the donation bug corrupted.
trainer.close()
probe = Trainer(MockT2RModel(device_type='cpu'), host_dir,
                use_fsdp=True, async_checkpoints=False,
                save_checkpoints_steps=10**9, log_every_n_steps=10**9,
                use_compiled_artifacts=True,
                artifact_workload='elastic_step',
                tuning_cache_path=os.path.join(base, 'cache.json'))
state = probe.train(gen, max_train_steps=3)
step = int(jax.device_get(state.step))
assert step == 3, 'restored+probed step skewed: %d' % step
probe.close()
print('PHASE_OK', phase, step)
'''.format(repo=REPO_ROOT)
    for phase in ('compile', 'deserialize'):
      proc = subprocess.run(
          [sys.executable, '-c', script, str(tmp_path), phase],
          cwd=REPO_ROOT, env=_subprocess_env(), capture_output=True,
          text=True, timeout=300)
      assert proc.returncode == 0, (phase, proc.stdout[-2000:],
                                    proc.stderr[-2000:])
      assert 'PHASE_OK ' + phase in proc.stdout


@pytest.mark.slow
class TestElasticAcceptance:

  def test_shrink_on_sigkill_then_grow_on_rejoin(self, tmp_path):
    """ISSUE 15 acceptance: 3 hosts, SIGKILL one mid-run -> exactly one
    t2r.recovery.v1 with world 3->2, phases summing to the total,
    survivors resuming past the pre-preemption step with zero XLA
    compiles, then a rejoin growing the mesh back to 3."""
    out = axes_lib.run_elastic_fleet(
        str(tmp_path), hosts=3, kill_host=1, local_device_count=2,
        boundary_steps=2, lease_ttl_secs=4.0, renew_secs=0.5,
        kill_after_step=2)
    axes = out['axes']
    assert axes['elastic_world_before'] == 3
    assert axes['elastic_world_after'] == 2
    assert axes['elastic_regrow_world'] == 3
    assert axes['elastic_shrinks'] >= 1
    assert axes['elastic_grows'] >= 2  # initial formation + regrow
    phases = axes['elastic_recovery_phases']
    total = axes['elastic_recovery_seconds']
    assert phases and total is not None
    assert abs(sum(phases.values()) - total) < 1e-6, (phases, total)
    # Zero-compile rebuilds on every SURVIVING host, and every
    # post-epoch-1 rebind served from the artifact store.
    assert axes['elastic_surviving_compiles'] == 0.0, axes
    assert axes['elastic_rebind_outcomes'], axes
    assert set(axes['elastic_rebind_outcomes']) == {'hit'}, axes
    # Exactly one recovery record for the one preemption.
    from tensor2robot_tpu.observability import fleet as fleet_lib
    records = fleet_lib.merged_records(fleet_lib.read_fleet(str(tmp_path)))
    recoveries = [r for r in records if r.get('kind') == 'recovery']
    assert len(recoveries) == 1, recoveries
    assert recoveries[0]['world_before'] == 3
    assert recoveries[0]['world_after'] == 2
    assert recoveries[0]['signum'] == membership.ELASTIC_LAPSE_SIGNUM
    # Survivors trained on past the pre-preemption step.
    for host in (0, 2):
      assert out['post_resume_steps'][host] > out['pre_preempt_step']
    assert all(code == 0 for code in out['exit_codes'].values()), out
    # The scaling curve covered both worlds it trained at.
    assert {'2', '3'} <= set(axes['elastic_world_curve']), axes
    # Doctor judges the whole run: no live pages after the stop.
    result = subprocess.run(
        [sys.executable, T2R_TELEMETRY, 'doctor', str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stdout + result.stderr

  def test_injected_preempt_site_drives_the_same_ladder(self, tmp_path):
    """The host.preempt FaultInjector alternative to SIGKILL: the victim
    dies through TrainingPreempted with no orderly leave, the lease
    lapses, and the coordinator runs the same shrink ladder."""
    base = str(tmp_path)
    stop = os.path.join(base, 'STOP')
    procs = [subprocess.Popen(
        _driver_cmd(base, host, 2, max_run_seconds=150.0,
                    extra=(('--inject_preempt_after', '6')
                           if host == 1 else ())),
        cwd=REPO_ROOT, env=_subprocess_env())
        for host in (0, 1)]
    try:
      deadline = time.monotonic() + 150.0
      shrunk = False
      while time.monotonic() < deadline and not shrunk:
        from tensor2robot_tpu.observability import fleet as fleet_lib
        try:
          records = fleet_lib.merged_records(fleet_lib.read_fleet(base))
        except OSError:
          records = []
        shrunk = any(r.get('kind') == 'elastic'
                     and r.get('event') == membership.EVENT_SHRINK
                     and r.get('departed') == [1] for r in records)
        time.sleep(1.0)
      assert shrunk, 'coordinator never completed the shrink ladder'
      with open(stop, 'w') as f:
        f.write('stop\n')
      assert procs[0].wait(timeout=90) == 0
      procs[1].wait(timeout=30)
    finally:
      for proc in procs:
        if proc.poll() is None:
          proc.kill()
