"""The closed actor<->learner loop (rl/loop.py, ISSUE 12).

Covers the tentpole claims with asserts, not prose:

  * every flushed transition round-trips the replay wire bit-exactly
    and re-assembles into exactly the learner's expected batch keys;
  * the acting path holds ONE jit executable across weight swaps
    (zero request-time compiles after warmup);
  * episode success measurably rises from the init-critic baseline
    within a CPU-budget run — the live QT-Opt cycle actually learns;
  * an armed ``actor.stall`` produces exactly one budgeted capture
    through the loop's watchdog while the learner keeps stepping, and
    a clean run takes zero captures;
  * a dropped ``learner.swap`` poll is retried and the loop converges
    anyway;
  * the ``check_rl_doctor`` fixtures replay against doctor in-process
    (stalled side named), and the CLI formats ``kind=rl`` records.
"""

import importlib.machinery
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip('jax')

from tensor2robot_tpu.envs import ScenarioConfig, VecGraspingEnv  # noqa: E402
from tensor2robot_tpu.observability import (  # noqa: E402
    doctor,
    read_telemetry,
)
from tensor2robot_tpu.observability.rl_metrics import (  # noqa: E402
    RL_RECORD_SCHEMA,
)
from tensor2robot_tpu.reliability.fault_injection import (  # noqa: E402
    FaultInjector,
    set_injector,
)
from tensor2robot_tpu.replay.client import LocalReplayClient  # noqa: E402
from tensor2robot_tpu.replay.service import (  # noqa: E402
    ReplayConfig,
    ReplayService,
)
from tensor2robot_tpu.replay import wire as replay_wire  # noqa: E402
from tensor2robot_tpu.research.qtopt import grasping_sim  # noqa: E402
from tensor2robot_tpu.rl.loop import (  # noqa: E402
    RLLoopConfig,
    build_grasping_loop,
    build_transition_record,
    make_act_step,
)
from tensor2robot_tpu.rl.offpolicy import (  # noqa: E402
    split_offpolicy_batch,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HEIGHT, WIDTH = 32, 40


@pytest.fixture(autouse=True)
def _clean_injector():
  set_injector(None)
  yield
  set_injector(None)


def _tiny_config(**overrides):
  kwargs = dict(cem_samples=8, cem_iters=2, num_elites=3, batch_size=8,
                num_candidates=8, publish_every_steps=10,
                swap_poll_steps=2, min_resident_examples=16,
                report_interval_s=2.0, seed=0)
  kwargs.update(overrides)
  return RLLoopConfig(**kwargs)


def _tiny_loop(tmp_path, config=None, **kwargs):
  kwargs.setdefault('num_envs', 8)
  kwargs.setdefault('height', HEIGHT)
  kwargs.setdefault('width', WIDTH)
  return build_grasping_loop(str(tmp_path / 'run'),
                             config=config or _tiny_config(), **kwargs)


def _transition_records(n, seed=0):
  """n synthetic transitions with per-record distinct height tags."""
  rng = np.random.RandomState(seed)
  records = []
  for i in range(n):
    records.append(build_transition_record(
        obs_image=rng.randint(0, 255, (HEIGHT, WIDTH, 3), dtype=np.uint8),
        obs_height=0.25 + i,  # unique per record: the round-trip join key
        action=rng.uniform(-1, 1, 8).astype(np.float32),
        reward=float(i % 2),
        terminal=bool(i % 2),
        next_image=rng.randint(0, 255, (HEIGHT, WIDTH, 3),
                               dtype=np.uint8),
        next_height=rng.uniform(0, 1.6)))
  return records


class TestTransitionWire:

  def test_round_trips_bit_exactly(self):
    """append -> sample returns every field of every transition with
    identical bytes (the ISSUE acceptance wording, asserted)."""
    records = _transition_records(12)
    service = ReplayService(ReplayConfig(num_shards=2, batch_size=12,
                                         seed=0))
    client = LocalReplayClient(service)
    for record in records:
      client.append(replay_wire.encode_example(record))
    batch = client.sample(batch_size=12)
    by_height = {float(r['features/action/height_to_bottom'][0]): r
                 for r in records}
    rows = len(batch.features['action/height_to_bottom'])
    assert rows == 12
    for row in range(rows):
      tag = float(batch.features['action/height_to_bottom'][row][0])
      original = by_height[tag]
      for key, value in original.items():
        side, _, rest = key.partition('/')
        stored = (batch.features if side == 'features'
                  else batch.labels)[rest][row]
        np.testing.assert_array_equal(
            np.asarray(stored), np.asarray(value),
            err_msg='field {} not bit-exact'.format(key))
        assert np.asarray(stored).dtype == np.asarray(value).dtype

  def test_sampled_batch_splits_into_learner_keys(self):
    """The sampled batch IS a valid off-policy batch: split yields the
    critic's own spec keys + next-state mirrors + done."""
    records = _transition_records(8)
    service = ReplayService(ReplayConfig(num_shards=1, batch_size=8,
                                         seed=0))
    client = LocalReplayClient(service)
    for record in records:
      client.append(replay_wire.encode_example(record))
    batch = client.sample(batch_size=8)
    train, nxt, done = split_offpolicy_batch(batch.features)
    expected = {'state/image'} | {
        'action/' + key for key, _ in grasping_sim.ACTION_DIM_LAYOUT} | {
        'action/gripper_closed', 'action/height_to_bottom'}
    assert set(train) == expected
    assert set(nxt) == {'state/image', 'action/gripper_closed',
                        'action/height_to_bottom'}
    assert done.shape == (8, 1)
    assert 'reward' in batch.labels

  def test_done_is_the_terminal_flag_not_episode_end(self):
    """Timeout transitions carry done=0 (bootstrap through the limit)."""
    timeout = build_transition_record(
        obs_image=np.zeros((HEIGHT, WIDTH, 3), np.uint8), obs_height=1.0,
        action=np.zeros(8, np.float32), reward=0.0, terminal=False,
        next_image=np.zeros((HEIGHT, WIDTH, 3), np.uint8),
        next_height=0.6)
    assert float(timeout['features/done'][0]) == 0.0
    grasp = build_transition_record(
        obs_image=np.zeros((HEIGHT, WIDTH, 3), np.uint8), obs_height=0.3,
        action=np.zeros(8, np.float32), reward=1.0, terminal=True,
        next_image=np.zeros((HEIGHT, WIDTH, 3), np.uint8),
        next_height=0.3)
    assert float(grasp['features/done'][0]) == 1.0


class TestLoopLearns:

  def test_success_rises_and_the_wire_holds(self, tmp_path):
    """The flagship acceptance run: CEM actor over scenario-randomized
    envs, transitions through the replay service, Bellman learner
    hot-swapping the actor — greedy success ends well above the
    init-critic baseline, with zero triggered captures and ONE acting
    executable."""
    loop = _tiny_loop(tmp_path)
    try:
      summary = loop.run(max_seconds=120, max_learner_steps=350)
      final_success = loop.measure_success(episodes=32)
    finally:
      loop.close()

    assert summary['learner_steps'] > 0
    assert summary['episodes'] > 100
    assert summary['transitions'] > 100
    # Hot swaps actually happened: the actor ended on learner weights.
    assert summary['swaps'] >= 1
    assert summary['actor_version'] > 1
    assert summary['dropped_swaps'] == 0
    # Zero request-time compiles after warmup: ONE acting executable.
    assert summary['act_jit_cache'] == 1.0
    # Clean run: the budgeted capture loop took nothing.
    assert loop.profiler.captures_taken == 0

    # Success rises measurably: the first report window is the
    # init-critic (~random argmax + exploration) baseline; the final
    # greedy probe is the learned policy.
    baseline = summary['windows'][0]['success_rate_cumulative']
    assert final_success >= baseline + 0.25, \
        'greedy {} vs baseline {}'.format(final_success, baseline)
    assert final_success >= 0.6
    # And the cumulative curve is visibly non-flat across the run.
    assert summary['windows'][-1]['success_rate_cumulative'] > baseline

    # Per-scenario telemetry: several difficulty buckets saw episodes.
    assert len(summary['buckets']) >= 3
    assert 'scenario_success_spread' in summary

    # The t2r.rl.v1 stream landed: lifecycle + schema'd windows.
    records = read_telemetry(
        os.path.join(str(tmp_path / 'run'), 'telemetry.jsonl'))
    kinds = [r.get('kind') for r in records]
    assert kinds[0] == 'rl_start'
    assert kinds[-1] == 'rl_stop'
    windows = [r for r in records if r.get('kind') == 'rl']
    assert windows
    for window in windows:
      assert window['schema'] == RL_RECORD_SCHEMA
      assert window['num_envs'] == 8
    # Doctor reads it as healthy (rl section INFO, exit-0 shape).
    findings = doctor.diagnose(str(tmp_path / 'run'))
    assert not any(f['severity'] == doctor.CRITICAL for f in findings)
    assert any('rl loop@' in f['message'] for f in findings)


class TestRerun:

  def test_second_run_starts_fresh_and_still_swaps(self, tmp_path):
    """run() is re-runnable: the second run's totals don't inherit the
    first's, and — the dangerous half — the actor adopts the second
    run's publishes instead of rejecting them against a stale high
    version from run one (post-review regression test)."""
    loop = _tiny_loop(tmp_path, config=_tiny_config(
        publish_every_steps=5, swap_poll_steps=1))
    try:
      first = loop.run(max_seconds=60, max_learner_steps=25)
      second = loop.run(max_seconds=60, max_learner_steps=25)
    finally:
      loop.close()
    assert first['episodes'] > 0 and second['episodes'] > 0
    # Fresh bookkeeping: the second run counts only itself.
    assert second['learner_steps'] == 25
    assert second['actor_steps'] < first['actor_steps'] + second['episodes']
    assert second['episodes'] < first['episodes'] + second['episodes']
    # And the swap path works again from version 1.
    assert second['swaps'] >= 1
    assert second['actor_version'] > 1


class TestLearnerStandinWindows:

  def test_wedged_actor_still_produces_named_windows(self, tmp_path,
                                                     monkeypatch):
    """A wedged actor emits no windows itself; the learner's stand-in
    reporter must keep the rl stream alive with actor_steps==0 windows
    — what makes doctor's rl_actor_stalled reachable on REAL telemetry
    (post-review regression test)."""
    from tensor2robot_tpu.reliability import fault_injection

    monkeypatch.setattr(fault_injection, 'ACTOR_STALL_SECONDS', 2.5)
    injector = FaultInjector()
    injector.fail('actor.stall', times=1, after=60)
    set_injector(injector)

    loop = _tiny_loop(tmp_path, config=_tiny_config(
        report_interval_s=0.3, publish_every_steps=5))
    try:
      loop.run(max_seconds=120, max_learner_steps=250)
    finally:
      loop.close()

    assert injector.fired_count('actor.stall') == 1
    records = read_telemetry(
        os.path.join(str(tmp_path / 'run'), 'telemetry.jsonl'))
    standins = [r for r in records if r.get('kind') == 'rl'
                and r.get('reporter') == 'learner']
    assert standins, 'no learner stand-in window during the 2.5 s stall'
    for record in standins:
      assert record['actor_steps'] == 0
      assert record['learner_steps'] > 0


class TestLearnerTailKeepsReporting:

  def test_actor_done_tail_heartbeats_without_paging(self, tmp_path):
    """When the actor finishes its episode target first, the learner's
    tail keeps the window/heartbeat stream alive — flagged actor_done
    so the doctor does NOT read the quiet actor as a stall
    (post-review regression test)."""
    loop = _tiny_loop(tmp_path, config=_tiny_config(
        report_interval_s=0.3, publish_every_steps=5))
    try:
      summary = loop.run(max_seconds=240, max_episodes=100,
                         max_learner_steps=150)
    finally:
      loop.close()
    assert summary['learner_steps'] == 150
    records = read_telemetry(
        os.path.join(str(tmp_path / 'run'), 'telemetry.jsonl'))
    tail = [r for r in records if r.get('kind') == 'rl'
            and r.get('reporter') == 'learner' and r.get('actor_done')]
    assert tail, 'no learner tail windows after the actor finished'
    for record in tail:
      assert record['actor_steps'] == 0
    findings = doctor.diagnose(str(tmp_path / 'run'))
    assert not any((f['detail'] or {}).get('kind') == 'rl_actor_stalled'
                   for f in findings)

  def test_learner_crash_fails_fast(self, tmp_path):
    """A dead learner must stop a deadline-only run promptly and
    re-raise — not collect unlearned episodes until the deadline
    (post-review regression test)."""
    import time as time_lib

    loop = _tiny_loop(tmp_path)
    calls = [0]
    real_step = loop.learner.train_step

    def dying_step(state, host_batch, rng):
      calls[0] += 1
      if calls[0] > 3:
        raise RuntimeError('injected learner death')
      return real_step(state, host_batch, rng)

    loop.learner.train_step = dying_step
    start = time_lib.perf_counter()
    try:
      with pytest.raises(RuntimeError, match='injected learner death'):
        loop.run(max_seconds=120)
    finally:
      loop.close()
    assert time_lib.perf_counter() - start < 60.0


class TestActStepStability:

  def test_jit_cache_stays_one_across_swaps(self, tmp_path):
    """Swapped snapshots (same structure, new values) must not compile
    a second acting executable — jit cache == 1 per acting signature."""
    loop = _tiny_loop(tmp_path, config=_tiny_config(
        publish_every_steps=3, swap_poll_steps=1))
    try:
      summary = loop.run(max_seconds=60, max_learner_steps=30)
    finally:
      loop.close()
    assert summary['swaps'] >= 1  # swaps really exercised the cache
    assert summary['act_jit_cache'] == 1.0


class TestFaultSites:

  @pytest.mark.slow  # 44 s measured (PR 21): past the tier-1 ~30 s rule
  def test_actor_stall_takes_exactly_one_budgeted_capture(
      self, tmp_path, monkeypatch):
    """ISSUE 12 satellite acceptance: an armed actor.stall inflates one
    acting window past the watchdog's regression ratio -> exactly one
    budgeted capture — while the concurrent learner keeps stepping.

    Load-hardened like test_forensics' step.slow acceptance: a 4 s
    stall against a jitter-proof 8x ratio (ambient suite load cannot
    arm a spurious capture and steal the budget), target-bounded run
    (no wallclock deadline deciding whether the learner got to step),
    and a budget of ONE so 'exactly one' is enforced, not hoped."""
    from tensor2robot_tpu.observability.watchdog import (
        Watchdog,
        WatchdogConfig,
    )
    from tensor2robot_tpu.reliability import fault_injection

    monkeypatch.setattr(fault_injection, 'ACTOR_STALL_SECONDS', 4.0)
    injector = FaultInjector()
    # after=150 acting steps: >= 4 report windows of healthy baseline
    # on a fast box (~7 ms/step vs 0.25 s windows), and the stall still
    # lands well before the 2000-episode actor target either way.
    injector.fail('actor.stall', times=1, after=150)
    set_injector(injector)

    loop = _tiny_loop(tmp_path, config=_tiny_config(
        report_interval_s=0.25, auto_profile=True, max_captures=1,
        publish_every_steps=5))
    loop.watchdog = Watchdog(WatchdogConfig(regression_ratio=8.0),
                             registry=loop._registry)
    try:
      summary = loop.run(max_seconds=240, max_episodes=2000,
                         max_learner_steps=30)
    finally:
      loop.close()

    assert injector.fired_count('actor.stall') == 1
    # Exactly ONE budgeted capture, through the loop's own
    # watchdog -> request_capture -> profiler window path.
    assert loop.profiler.captures_taken == 1
    assert not loop.profiler.broken

    records = read_telemetry(
        os.path.join(str(tmp_path / 'run'), 'telemetry.jsonl'))
    anomalies = [r for r in records if r.get('kind') == 'anomaly'
                 and r.get('anomaly') == 'step_time_regression']
    assert anomalies, 'the stall never tripped the watchdog'
    # The learner kept stepping right through the actor-side stall:
    # it reached its full step target, and the loop converged.
    assert summary['learner_steps'] >= 30
    assert summary['episodes'] >= 2000

  def test_dropped_swap_is_retried_and_converges(self, tmp_path):
    """A dropped learner.swap poll leaves the snapshot on the bus; the
    next poll adopts it — the loop still ends on learner weights."""
    injector = FaultInjector()
    injector.fail('learner.swap', times=1)
    set_injector(injector)

    loop = _tiny_loop(tmp_path, config=_tiny_config(
        publish_every_steps=5, swap_poll_steps=1))
    try:
      summary = loop.run(max_seconds=60, max_learner_steps=40)
    finally:
      loop.close()

    assert injector.fired_count('learner.swap') == 1
    assert summary['dropped_swaps'] == 1
    # Retried: the actor still adopted learner versions (>1 = not stuck
    # on the bootstrap weights) despite the dropped poll.
    assert summary['swaps'] >= 1
    assert summary['actor_version'] > 1


def _load_gate_module():
  path = os.path.join(REPO_ROOT, 'bin', 'check_rl_doctor')
  loader = importlib.machinery.SourceFileLoader('check_rl_doctor', path)
  spec = importlib.util.spec_from_loader('check_rl_doctor', loader)
  module = importlib.util.module_from_spec(spec)
  loader.exec_module(module)
  return module


class TestDoctorRlSection:

  def test_stalled_actor_fixture_names_the_actor(self, tmp_path):
    gate = _load_gate_module()
    gate.write_stalled_actor_fixture(str(tmp_path))
    findings = doctor.diagnose(str(tmp_path))
    crits = [f for f in findings if f['severity'] == doctor.CRITICAL
             and (f['detail'] or {}).get('kind') == 'rl_actor_stalled']
    assert crits and crits[0]['detail']['side'] == 'actor'

  def test_stalled_learner_fixture_names_the_learner(self, tmp_path):
    gate = _load_gate_module()
    gate.write_stalled_learner_fixture(str(tmp_path))
    findings = doctor.diagnose(str(tmp_path))
    crits = [f for f in findings if f['severity'] == doctor.CRITICAL
             and (f['detail'] or {}).get('kind') == 'rl_learner_stalled']
    assert crits and crits[0]['detail']['side'] == 'learner'

  def test_clean_fixture_is_healthy(self, tmp_path):
    gate = _load_gate_module()
    gate.write_clean_fixture(str(tmp_path))
    findings = doctor.diagnose(str(tmp_path))
    assert not any(f['severity'] == doctor.CRITICAL for f in findings)
    assert any('rl loop@' in f['message'] for f in findings)

  def test_stall_after_run_end_downgrades(self, tmp_path):
    """A stalled window followed by an orderly rl_stop is history, not
    a live page (the shared downgrade rule)."""
    from tensor2robot_tpu.observability import TelemetryLogger
    gate = _load_gate_module()
    logger = TelemetryLogger(str(tmp_path))
    logger.log('rl_start', num_envs=8)
    logger.log('rl', **gate._rl_record(40))
    logger.log('rl', **gate._rl_record(80, actor_steps=0, episodes=0,
                                       successes=0))
    logger.log('rl', **gate._rl_record(80, actor_steps=0, episodes=0,
                                       successes=0))
    logger.log('rl_stop', episodes=100, success_rate=0.5,
               learner_steps=60, swaps=4, dropped_swaps=0,
               actor_version=4)
    logger.close()
    findings = doctor.diagnose(str(tmp_path))
    stalls = [f for f in findings
              if (f['detail'] or {}).get('kind') == 'rl_actor_stalled']
    assert stalls and stalls[0]['severity'] == doctor.WARNING

  def test_finished_side_does_not_page(self, tmp_path):
    """A side that COMPLETED its configured target (the records'
    learner_done/actor_done flags) is a documented healthy mode — zero
    steps from it must not raise the stalled CRITICAL (post-review
    regression test)."""
    from tensor2robot_tpu.observability import TelemetryLogger
    gate = _load_gate_module()
    logger = TelemetryLogger(str(tmp_path))
    logger.log('rl_start', num_envs=8)
    logger.log('rl', **gate._rl_record(40))
    done = gate._rl_record(80, learner_steps=0)
    done['learner_done'] = True
    logger.log('rl', **done)
    done = gate._rl_record(120, learner_steps=0)
    done['learner_done'] = True
    logger.log('rl', **done)
    logger.heartbeat()
    logger.close()
    findings = doctor.diagnose(str(tmp_path))
    assert not any((f['detail'] or {}).get('kind') == 'rl_learner_stalled'
                   for f in findings)

  def test_act_cache_growth_is_flagged(self, tmp_path):
    from tensor2robot_tpu.observability import TelemetryLogger
    gate = _load_gate_module()
    record = gate._rl_record(40)
    record['act_jit_cache'] = 3.0
    logger = TelemetryLogger(str(tmp_path))
    logger.log('rl_start', num_envs=8)
    logger.log('rl', **record)
    logger.log('rl_stop', episodes=96, success_rate=0.5,
               learner_steps=20, swaps=4, dropped_swaps=0,
               actor_version=4)
    logger.close()
    findings = doctor.diagnose(str(tmp_path))
    assert any((f['detail'] or {}).get('kind') == 'rl_act_recompile'
               for f in findings)

  def test_gate_passes(self):
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin',
                                      'check_rl_doctor')],
        capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stdout + result.stderr


class TestCli:

  def _fixture_dir(self, tmp_path):
    gate = _load_gate_module()
    gate.write_clean_fixture(str(tmp_path))
    return str(tmp_path)

  def test_summarize_prints_rl_section(self, tmp_path):
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_telemetry'),
         'summarize', self._fixture_dir(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
    assert 'rl loop:' in result.stdout
    assert 'buckets:' in result.stdout

  def test_summarize_json_carries_the_record(self, tmp_path):
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_telemetry'),
         'summarize', '--json', self._fixture_dir(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
    data = json.loads(result.stdout)
    assert data['rl']['schema'] == RL_RECORD_SCHEMA

  def test_tail_formats_rl_records(self, tmp_path):
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_telemetry'),
         'tail', self._fixture_dir(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
    assert 'ep/s' in result.stdout
    assert 'swaps=' in result.stdout

  @pytest.mark.slow
  def test_rl_loop_selfcheck(self, tmp_path):
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 't2r_rl_loop'),
         '--selfcheck'],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS='cpu',
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert result.returncode == 0, result.stdout + result.stderr
    summary = json.loads(result.stdout)
    assert summary['episodes'] > 0 and summary['learner_steps'] > 0


class TestEnvShardingHelper:

  def test_trivial_data_axis_replicates(self):
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.rl.loop import env_sharding
    mesh = parallel.create_mesh()
    sharding = env_sharding(mesh, 8)
    if mesh.shape.get('data', 1) == 1:
      # P('data') outputs canonicalize to P() on a trivial axis; the
      # helper must therefore replicate (the jit-cache==1 invariant).
      assert sharding.spec == jax.sharding.PartitionSpec()
    assert env_sharding(None, 8) is None
