"""The live QT-Opt cycle: a device-resident actor <-> learner loop.

ISSUE 12's tentpole, closing ROADMAP item 1. One process runs the whole
off-policy organ set the previous PRs built, concurrently:

  * **Actor** — ONE jitted program per acting step (``make_act_step``):
    the CEM selector runs over every env slot (each slot its own full
    CEM loop, the ``make_batched_select_action`` megabatch shape),
    epsilon-exploration mixes in random actions per slot, and the
    vectorized environment (envs/) advances all B slots with auto-reset
    — collect-on-device, Anakin-style (arXiv:2104.06272). The actor
    acts under an atomically-swapped immutable ``(version, variables)``
    snapshot (the drain-free PR-7 serving pattern: a swap lands between
    acting steps, never inside one).
  * **Replay** — completed episodes flush as per-transition packed
    replay records (replay/wire.py) through a ``ReplayClient`` /
    ``LocalReplayClient``; timeouts are written with ``done=0``
    (bootstrap through the time limit), terminals with ``done=1`` —
    the grasping_sim convention, preserved end to end.
  * **Learner** — the Bellman trainer (rl/offpolicy.py) samples
    megabatches back via ``ReplayBatchIterator`` and steps CONCURRENTLY
    with the actor (its XLA dispatches release the GIL), publishing
    fresh ``(version, variables)`` snapshots on a cadence the actor
    polls — ``learner.swap`` drops one poll deterministically to prove
    the retry path.
  * **Observability** — a ``kind="rl"`` (``t2r.rl.v1``) record each
    report window (episodes/sec, per-scenario-bucket success,
    actor/learner step rates, swap versions — observability/
    rl_metrics.py), heartbeats, and the loop's own Watchdog +
    AutoProfiler: an ``actor.stall`` shows up as a step-time regression
    and claims exactly one budgeted capture while the learner keeps
    stepping (tests/test_rl_loop.py).

``bin/t2r_rl_loop`` is the entry point; docs/rl_loop.md is the operator
contract.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import TelemetryLogger, get_registry
from tensor2robot_tpu.observability import rl_metrics
from tensor2robot_tpu.observability.autoprofiler import AutoProfiler
from tensor2robot_tpu.observability.watchdog import Watchdog, WatchdogConfig
from tensor2robot_tpu.parallel import sharding as sharding_lib
from tensor2robot_tpu.reliability import fault_injection
from tensor2robot_tpu.reliability.logutil import log_warning
from tensor2robot_tpu.replay import wire as replay_wire
from tensor2robot_tpu.replay.client import LocalReplayClient, ReplayClient
from tensor2robot_tpu.replay.feed import ReplayBatchIterator
from tensor2robot_tpu.replay.service import ReplayEmpty, ReplayService
from tensor2robot_tpu.research.qtopt.grasping_sim import CLOSE_INDEX
from tensor2robot_tpu.research.qtopt.t2r_models import (
    ACTION_DIM_LAYOUT,
    CEM_ACTION_SIZE,
)
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.utils import cross_entropy

__all__ = ['RLLoop', 'RLLoopConfig', 'ParamBus', 'make_cem_select_fn',
           'make_act_step', 'build_transition_record',
           'build_grasping_loop']

_STATUS_KEYS = ('gripper_closed', 'height_to_bottom')


@dataclasses.dataclass
class RLLoopConfig:
  """Knobs for one closed loop (docs/rl_loop.md).

  Attributes:
    cem_samples / cem_iters / num_elites: the per-slot CEM budget.
    explore_prob: per-slot probability an acting step takes a random
      action instead of the CEM argmax (epsilon exploration).
    explore_close_prob: close-gripper probability of a random action
      (the SimGraspingRandomPolicy balance).
    batch_size: learner megabatch size (also the replay sample size).
    num_candidates: K for the Bellman target max (rl/offpolicy.py).
    gamma: discount.
    target_update_steps: lagged-target export interval (learner steps).
    publish_every_steps: learner snapshot-publish cadence.
    swap_poll_steps: actor weight-poll cadence (acting steps).
    min_resident_examples: replay occupancy the learner waits for
      before its first step (collectors boot first).
    report_interval_s: cadence of ``t2r.rl.v1`` telemetry windows.
    auto_profile / profile_window_steps / max_captures: the loop's own
      budgeted capture loop (an armed ``actor.stall`` claims exactly
      one window).
    artifact_workload: when set, the acting step cold-starts through
      the unified ``CompiledArtifact`` store (tensor2robot_tpu/compile,
      docs/performance.md "Cold start") under this workload name: a
      warm start DESERIALIZES the persisted acting executable — the
      first acting step executes without an XLA compile — and a miss
      compiles once and persists for the next process. The key carries
      the lowered-program hash, so a changed env/CEM config is a miss,
      never a wrong load.
    artifact_cache_path: the store location (default: the process
      tuning cache's directory).
    seed: all loop-side randomness.
  """

  cem_samples: int = 16
  cem_iters: int = 2
  num_elites: int = 4
  explore_prob: float = 0.15
  explore_close_prob: float = 0.4
  batch_size: int = 16
  num_candidates: int = 16
  gamma: float = 0.8
  target_update_steps: int = 20
  publish_every_steps: int = 10
  swap_poll_steps: int = 4
  min_resident_examples: int = 32
  report_interval_s: float = 5.0
  auto_profile: bool = False
  profile_window_steps: int = 2
  max_captures: int = 1
  artifact_workload: Optional[str] = None
  artifact_cache_path: Optional[str] = None
  seed: int = 0


class ParamBus:
  """One-slot atomic ``(version, variables)`` hand-off, learner->actor.

  The PR-7 snapshot pattern: the pair is ONE immutable tuple assigned
  atomically, so a reader can never observe version N paired with
  version M's weights. The learner publishes a COPY of its params
  (the jitted Bellman step donates its state buffers — a snapshot
  aliasing them would be invalidated one step later).
  """

  def __init__(self):
    self._snapshot: Tuple[int, Optional[Any]] = (0, None)

  def publish(self, version: int, variables) -> None:
    self._snapshot = (int(version), variables)

  @property
  def snapshot(self) -> Tuple[int, Optional[Any]]:
    return self._snapshot

  @property
  def version(self) -> int:
    return self._snapshot[0]


def make_cem_select_fn(model, cem_samples: int = 16, cem_iters: int = 2,
                       num_elites: int = 4):
  """One-slot CEM action selector over any Grasping44-shaped critic.

  The generic twin of ``Grasping44...make_on_device_select_action``:
  works for every model sharing the flagship's spec keys (the sim
  critic included) — the image tower runs once per state, each CEM
  iteration scores ``cem_samples`` candidates through the megabatch
  contract, the whole loop is one traceable function.

  Returns ``select(variables, obs, rng) -> (action [8], q)`` with
  ``obs`` = {'image' uint8 [H, W, 3], 'gripper_closed',
  'height_to_bottom'} (the env observation contract).
  """

  def select(variables, obs, rng):
    variables = dict(variables)
    avg_params = variables.pop('avg_params', None)
    if getattr(model, 'use_avg_model_params', False) and \
        avg_params is not None:
      variables['params'] = avg_params
    placeholder = SpecStruct()
    placeholder['state/image'] = jnp.asarray(obs['image'])[None]
    for key, size in ACTION_DIM_LAYOUT:
      placeholder['action/' + key] = jnp.zeros((1, size), jnp.float32)
    for key in _STATUS_KEYS:
      placeholder['action/' + key] = jnp.asarray(
          obs[key], jnp.float32).reshape(1, 1)
    processed, _ = model.preprocessor.preprocess(
        placeholder, None, ModeKeys.PREDICT, rng=None)
    image = processed['state/image']

    def objective(samples):
      features = SpecStruct()
      features['state/image'] = image
      offset = 0
      for key, size in ACTION_DIM_LAYOUT:
        features['action/' + key] = samples[:, offset:offset + size]
        offset += size
      for key in _STATUS_KEYS:
        features['action/' + key] = jnp.broadcast_to(
            jnp.asarray(obs[key], jnp.float32).reshape(1, 1),
            (samples.shape[0], 1))
      outputs, _ = model.inference_network_fn(
          variables, features, None, ModeKeys.PREDICT, None)
      return outputs['q_predicted']

    _, _, best = cross_entropy.jax_normal_cem(
        objective, jnp.zeros((CEM_ACTION_SIZE,), jnp.float32),
        jnp.ones((CEM_ACTION_SIZE,), jnp.float32), rng,
        num_samples=cem_samples, num_elites=num_elites,
        num_iterations=cem_iters)
    return best, objective(best[None])[0]

  return select


def env_sharding(mesh, num_envs: int):
  """Where env slots live: sharded over the data axis when it is
  non-trivial and divides B (env slots spread across chips, the Anakin
  layout), replicated otherwise. On a trivial data axis GSPMD
  canonicalizes ``P('data')`` outputs to ``P()`` — pinning the carry to
  batch sharding there would guarantee a signature mismatch, so the
  single-device case stays replicated."""
  if mesh is None:
    return None
  data_size = mesh.shape.get('data', 1)
  if data_size > 1 and num_envs % data_size == 0:
    return sharding_lib.batch_sharding(mesh)
  return sharding_lib.replicated(mesh)


def make_act_step(model, env, cem_samples: int = 16, cem_iters: int = 2,
                  num_elites: int = 4, explore_prob: float = 0.0,
                  explore_close_prob: float = 0.4, out_sharding=None):
  """The fused acting program: select + explore + step B envs, one jit.

  ``act(variables, env_state, obs, rng) -> (env_state', obs',
  transition)`` where ``transition`` carries everything the replay
  writer needs per slot (acted-from obs fields, action, reward,
  terminal/done, pre-reset successor fields, elite q). One call = one
  XLA dispatch; the jit cache must stay at ONE executable after warmup
  (``recompiles/act_step``) — which is why ``out_sharding`` pins the
  carried (env_state, obs) outputs to the sharding the caller places
  fresh env buffers with: jit cache keys include input shardings, so
  the carry must leave each call exactly as it arrives.
  """
  select = make_cem_select_fn(model, cem_samples=cem_samples,
                              cem_iters=cem_iters, num_elites=num_elites)
  batched_select = jax.vmap(select, in_axes=(None, 0, 0))
  explore_prob = float(explore_prob)
  num_envs = env.num_envs

  def act(variables, env_state, obs, rng):
    rng = jnp.asarray(rng)
    r_select, r_explore, r_uniform, r_close = jax.random.split(rng, 4)
    keys = jax.random.split(r_select, num_envs)
    action, q = batched_select(variables, obs, keys)
    if explore_prob > 0.0:
      uniform = jax.random.uniform(
          r_uniform, (num_envs, CEM_ACTION_SIZE), jnp.float32,
          minval=-1.0, maxval=1.0)
      close = jax.random.bernoulli(
          r_close, explore_close_prob, (num_envs,)).astype(jnp.float32)
      uniform = uniform.at[:, CLOSE_INDEX].set(close)
      explore = jax.random.bernoulli(r_explore, explore_prob, (num_envs,))
      action = jnp.where(explore[:, None], uniform, action)
    result = env.step(env_state, action)
    state_out, obs_out = result.state, result.obs
    if out_sharding is not None:
      state_out, obs_out = jax.lax.with_sharding_constraint(
          (state_out, obs_out), out_sharding)
    next_obs = result.info['next_obs']
    transition = {
        'obs_image': obs['image'],
        'obs_height': obs['height_to_bottom'],
        'action': action,
        'q': q,
        'reward': result.reward,
        'done': result.done,
        'terminal': result.info['terminal'],
        'next_image': next_obs['image'],
        'next_height': next_obs['height_to_bottom'],
    }
    return state_out, obs_out, transition

  return jax.jit(act)


def build_transition_record(obs_image: np.ndarray,
                            obs_height: float,
                            action: np.ndarray,
                            reward: float,
                            terminal: bool,
                            next_image: np.ndarray,
                            next_height: float) -> Dict[str, np.ndarray]:
  """One flushed transition as a flat replay-record dict.

  Keys are ``features/<critic spec key>`` + the off-policy extras
  (``features/next/...``, ``features/done``) and ``labels/reward`` —
  exactly what ``ReplayBatchIterator`` hands back as the learner batch
  (rl/offpolicy.split_offpolicy_batch's key convention). ``done`` on
  the wire is the env-TERMINAL flag, not episode end: timeouts
  bootstrap through (grasping_sim module docstring).
  """
  action = np.asarray(action, np.float32).ravel()
  entries: Dict[str, np.ndarray] = {
      'features/state/image': np.ascontiguousarray(obs_image),
      'features/next/state/image': np.ascontiguousarray(next_image),
      'features/next/action/gripper_closed': np.zeros((1,), np.float32),
      'features/next/action/height_to_bottom': np.asarray(
          [next_height], np.float32),
      'features/done': np.asarray([1.0 if terminal else 0.0], np.float32),
      'labels/reward': np.asarray([reward], np.float32),
  }
  offset = 0
  for key, size in ACTION_DIM_LAYOUT:
    entries['features/action/' + key] = action[offset:offset + size]
    offset += size
  entries['features/action/gripper_closed'] = np.zeros((1,), np.float32)
  entries['features/action/height_to_bottom'] = np.asarray(
      [obs_height], np.float32)
  return entries


class RLLoop:
  """Actor + learner + swap + telemetry for one closed run.

  ``model``/``trainer``/``learner`` are the critic, its harness
  ``Trainer``, and a ``BellmanQTOptTrainer``; ``env`` a ``VecEnv``;
  ``client`` a replay client (the append AND sample side). The loop
  owns no jax state at construction beyond the jitted acting program —
  ``run()`` is the lifecycle.
  """

  def __init__(self,
               model,
               env,
               client,
               trainer,
               learner,
               model_dir: str,
               config: Optional[RLLoopConfig] = None,
               telemetry: Optional[TelemetryLogger] = None,
               registry=None,
               owned_service: Optional[ReplayService] = None):
    self.model = model
    self.env = env
    self.client = client
    self.trainer = trainer
    self.learner = learner
    self.model_dir = model_dir
    self.config = config or RLLoopConfig()
    self._registry = registry or get_registry()
    self._owns_telemetry = telemetry is None
    self.telemetry = telemetry or TelemetryLogger(model_dir)
    self._owned_service = owned_service
    cfg = self.config
    self._env_sharding = env_sharding(trainer.mesh, env.num_envs)
    self._act = make_act_step(
        model, env, cem_samples=cfg.cem_samples, cem_iters=cfg.cem_iters,
        num_elites=cfg.num_elites, explore_prob=cfg.explore_prob,
        explore_close_prob=cfg.explore_close_prob,
        out_sharding=self._env_sharding)
    self._act_loaded = None  # CompiledArtifact when artifact_workload set
    self._greedy_act = None  # built lazily by measure_success
    self.watchdog = Watchdog(WatchdogConfig(), registry=self._registry)
    self.profiler = AutoProfiler(
        model_dir, window_steps=cfg.profile_window_steps,
        max_captures=cfg.max_captures if cfg.auto_profile else 0,
        min_interval_secs=0.0, emit_reports=False,
        registry=self._registry)

    registry = self._registry
    self._episode_counters = registry.counter_family(
        rl_metrics.RL_EPISODES_COUNTER, ('bucket',))
    self._success_counters = registry.counter_family(
        rl_metrics.RL_SUCCESSES_COUNTER, ('bucket',))
    self._env_steps = registry.counter(rl_metrics.RL_ENV_STEPS_COUNTER)
    self._actor_steps = registry.counter(rl_metrics.RL_ACTOR_STEPS_COUNTER)
    self._learner_steps_counter = registry.counter(
        rl_metrics.RL_LEARNER_STEPS_COUNTER)
    self._transitions = registry.counter(rl_metrics.RL_TRANSITIONS_COUNTER)
    self._swap_counter = registry.counter(rl_metrics.RL_SWAPS_COUNTER)
    self._dropped_counter = registry.counter(
        rl_metrics.RL_DROPPED_SWAPS_COUNTER)
    self._actor_version_gauge = registry.gauge(
        rl_metrics.RL_ACTOR_VERSION_GAUGE)
    self._learner_version_gauge = registry.gauge(
        rl_metrics.RL_LEARNER_VERSION_GAUGE)
    self._act_ms = registry.histogram(rl_metrics.RL_ACT_MS_HISTOGRAM)
    self._act_cache_gauge = registry.gauge(rl_metrics.ACT_RECOMPILE_GAUGE)

    # Host-side run state (re-zeroed by _reset_run_state per run()).
    self._stop = threading.Event()
    self._report_lock = threading.Lock()
    self._reset_run_state()

  def _reset_run_state(self) -> None:
    """Fresh per-run bookkeeping: a second run() must not inherit the
    first run's totals, windows, or — critically — its actor version
    (a stale high version would make _poll_swap silently reject every
    new publish until the fresh count caught up). Registry counters
    are process-cumulative by design, so the run reads them as deltas
    against baselines captured here."""
    self._actor_version = 0
    self._actor_variables = None
    self._swaps = 0
    self._dropped_swaps = 0
    self._episodes = 0
    self._successes = 0
    self._learner_steps = 0
    self._bucket_episodes: Dict[int, int] = {}
    self._bucket_successes: Dict[int, int] = {}
    self._windows: List[Dict[str, Any]] = []
    self.bus = ParamBus()
    self._counter_base = {
        'env_steps': self._env_steps.value,
        'actor_steps': self._actor_steps.value,
        'transitions': self._transitions.value,
    }
    # Shared report marks (actor reporter + learner stand-in): when the
    # last rl window landed, and the learner steps it covered through.
    self._last_report_mark = time.perf_counter()
    self._learner_steps_at_report = 0
    self._learner_errors: List[BaseException] = []
    self._actor_done = threading.Event()
    self._learner_done = threading.Event()

  # -- learner side ----------------------------------------------------------

  def _init_batch(self):
    """A synthetic in-spec batch: init_state needs shapes before any
    replay exists (the actor must act before the first transition)."""
    batch = self.config.batch_size
    height, width = self.env.height, self.env.width
    features: Dict[str, np.ndarray] = {
        'state/image': np.zeros((batch, height, width, 3), np.uint8)}
    for key, size in ACTION_DIM_LAYOUT:
      features['action/' + key] = np.zeros((batch, size), np.float32)
    for key in _STATUS_KEYS:
      features['action/' + key] = np.zeros((batch, 1), np.float32)
    labels = SpecStruct(reward=np.zeros((batch, 1), np.float32))
    return SpecStruct(**features), labels

  def _snapshot_variables(self, state):
    """An immutable on-device COPY of the serving variables (ParamBus)."""
    variables = {'params': state.params}
    if state.model_state:
      variables.update(state.model_state)
    return jax.tree.map(jnp.copy, variables)

  def _learner_loop(self, state, deadline: Optional[float],
                    max_learner_steps: Optional[int],
                    errors: List[BaseException]) -> None:
    cfg = self.config
    try:
      # Wait for the collectors: the actor is filling the store RIGHT
      # NOW, so poll occupancy instead of failing the first sample.
      # At least min_resident_examples AND at least one full batch —
      # a large knob must actually delay the first step (training on a
      # near-empty buffer is the failure mode the knob exists to avoid).
      resident_floor = max(cfg.min_resident_examples, cfg.batch_size, 1)
      while not self._stop.is_set():
        occupancy = self.client.stats().get('occupancy_examples', 0)
        if occupancy >= resident_floor:
          break
        if deadline is not None and time.perf_counter() >= deadline:
          return
        time.sleep(0.02)
      iterator = ReplayBatchIterator(self.client, cfg.batch_size,
                                     wait_timeout_s=60.0)
      rng = jax.random.PRNGKey(cfg.seed + 1)
      while not self._stop.is_set():
        if deadline is not None and time.perf_counter() >= deadline:
          break
        if max_learner_steps is not None and \
            self._learner_steps >= max_learner_steps:
          break
        try:
          features, labels = next(iterator)
        except ReplayEmpty:
          time.sleep(0.05)
          continue
        host_batch = {
            'features': {key: features[key] for key in features},
            'labels': {key: labels[key] for key in labels},
        }
        state, _ = self.learner.train_step(state, host_batch, rng)
        self._learner_steps += 1
        self._learner_steps_counter.inc()
        if self._learner_steps % cfg.publish_every_steps == 0:
          version = self.bus.version + 1
          self.bus.publish(version, self._snapshot_variables(state))
          self._learner_version_gauge.set(float(version))
        # Actor gone quiet? Keep the rl window stream (and heartbeat)
        # alive from this side so a wedged actor is a NAMED doctor
        # CRITICAL, not an anonymous stale heartbeat.
        self._learner_standin_report()
      # Final publish so a short run still hands the actor its last
      # learned weights (and the swap acceptance test converges).
      version = self.bus.version + 1
      self.bus.publish(version, self._snapshot_variables(state))
      self._learner_version_gauge.set(float(version))
    except BaseException as e:  # noqa: BLE001 — surfaced after join
      errors.append(e)
    finally:
      self._learner_done.set()
      self._check_targets()

  # -- actor side ------------------------------------------------------------

  def _place_env(self, env_state, obs):
    """Commits fresh env buffers to the acting carry's pinned sharding.

    jit cache keys include input shardings: the acting program pins its
    (env_state, obs) outputs to ``env_sharding(...)`` and the reset
    buffers must arrive committed to the SAME placement, or the first
    steady-state call compiles a second executable
    (``recompiles/act_step`` must stay at 1).
    """
    if self._env_sharding is None:
      return env_state, obs
    return jax.device_put((env_state, obs), self._env_sharding)

  def _poll_swap(self) -> None:
    version, variables = self.bus.snapshot
    if variables is None or version <= self._actor_version:
      return
    if fault_injection.fires(fault_injection.SITE_LEARNER_SWAP):
      # A dropped poll: the snapshot stays on the bus, the NEXT poll
      # adopts it — at-least-once, not exactly-once.
      self._dropped_swaps += 1
      self._dropped_counter.inc()
      return
    self._actor_variables = variables
    self._actor_version = version
    self._swaps += 1
    self._swap_counter.inc()
    self._actor_version_gauge.set(float(version))

  def _flush_slot(self, transition, slot: int,
                  buffers: List[List[Dict[str, np.ndarray]]]) -> None:
    buffers[slot].append(build_transition_record(
        obs_image=transition['obs_image'][slot],
        obs_height=float(transition['obs_height'][slot]),
        action=transition['action'][slot],
        reward=float(transition['reward'][slot]),
        terminal=bool(transition['terminal'][slot]),
        next_image=transition['next_image'][slot],
        next_height=float(transition['next_height'][slot])))
    if not bool(transition['done'][slot]):
      return
    # Episode complete: flush its transitions, book the outcome.
    for record in buffers[slot]:
      self.client.append(replay_wire.encode_example(record))
    self._transitions.inc(len(buffers[slot]))
    buffers[slot].clear()
    bucket = int(self.env.buckets[slot])
    success = bool(transition['terminal'][slot]) and \
        float(transition['reward'][slot]) > 0.5
    self._episodes += 1
    self._bucket_episodes[bucket] = \
        self._bucket_episodes.get(bucket, 0) + 1
    self._episode_counters.series(str(bucket)).inc()
    if success:
      self._successes += 1
      self._bucket_successes[bucket] = \
          self._bucket_successes.get(bucket, 0) + 1
      self._success_counters.series(str(bucket)).inc()

  def _bind_act_artifact(self, env_state, obs, base_rng) -> None:
    """Acting-step cold start through the CompiledArtifact store.

    Called once per process, right after the env buffers are committed
    to the carry's pinned sharding — the example args ARE the
    steady-state call's (variables, env_state, obs, rng), so the loaded
    executable serves every acting step. Best-effort: any store failure
    degrades to the stock jit path (one compile at the first call).
    """
    try:
      from tensor2robot_tpu.compile import artifact as artifact_lib

      self._act_loaded = artifact_lib.load_or_compile(
          self.config.artifact_workload, self._act,
          (self._actor_variables, env_state, obs,
           jax.random.fold_in(base_rng, 0)),
          cache_path=self.config.artifact_cache_path,
          telemetry=self.telemetry, program_key=True)
      log_warning('Acting step bound from CompiledArtifact store: %s '
                  '(%s).', self.config.artifact_workload,
                  'deserialized' if self._act_loaded.from_cache
                  else 'compiled + persisted')
    except Exception as e:  # noqa: BLE001 — never kill the loop
      log_warning('Acting-step artifact bind failed (%s); using the '
                  'stock jit path.', e)
      self._act_loaded = None

  def _sample_act_cache(self) -> float:
    if self._act_loaded is not None:
      # AOT path: exactly one executable exists by construction and the
      # jit cache stays empty — report the healthy 1 (same convention
      # as Trainer._sample_recompiles).
      self._act_cache_gauge.set(1.0)
      return 1.0
    try:
      size = float(self._act._cache_size())  # noqa: SLF001 — same probe
      # as Trainer._sample_recompiles; absent on some jax versions.
    except Exception:  # noqa: BLE001
      return self._act_cache_gauge.value
    self._act_cache_gauge.set(size)
    return size

  def _make_record(self, window_s: float, actor_steps: int,
                   episodes: int, successes: int, transitions: int,
                   act_seconds: float, learner_steps: int,
                   act_jit_cache: float, buckets,
                   reporter: str) -> Dict[str, Any]:
    """ONE t2r.rl.v1 record builder for both reporters — the actor's
    window reports and the learner's stand-ins must stay field-for-
    field identical or the jax-free readers see schema drift."""
    num_envs = self.env.num_envs
    window_s = max(window_s, 1e-9)
    record = {
        'schema': rl_metrics.RL_RECORD_SCHEMA,
        'window_seconds': round(window_s, 3),
        'num_envs': num_envs,
        'actor_steps': int(actor_steps),
        'actor_steps_per_sec': round(actor_steps / window_s, 2),
        'env_steps': int(actor_steps * num_envs),
        'env_steps_per_sec': round(actor_steps * num_envs / window_s, 2),
        'episodes': int(episodes),
        'episodes_per_sec': round(episodes / window_s, 2),
        'success_rate': round(successes / episodes, 4) if episodes else 0.0,
        'success_rate_cumulative': round(
            self._successes / self._episodes, 4) if self._episodes else 0.0,
        'transitions': int(transitions),
        'learner_steps': int(learner_steps),
        'learner_steps_per_sec': round(learner_steps / window_s, 2),
        'actor_version': int(self._actor_version),
        'learner_version': int(self.bus.version),
        'swaps': int(self._swaps),
        'dropped_swaps': int(self._dropped_swaps),
        'act_step_ms': round(act_seconds / actor_steps * 1e3, 3)
                       if actor_steps else 0.0,
        'act_jit_cache': act_jit_cache,
        'buckets': buckets,
        'reporter': reporter,
        # Completion flags, so the doctor can tell a side that FINISHED
        # its configured target (healthy, by design) from one that
        # stalled — zero steps from a finished side must not page.
        'actor_done': self._actor_done.is_set(),
        'learner_done': self._learner_done.is_set(),
    }
    spread = rl_metrics.scenario_success_spread(buckets)
    if spread is not None:
      record['scenario_success_spread'] = round(spread, 4)
    return record

  def _covered_learner_steps(self) -> int:
    """Learner steps since the LAST report of either reporter (shared
    mark — per-reporter baselines would double-count a stand-in's
    steps into the recovering actor's next window)."""
    steps = self._learner_steps - self._learner_steps_at_report
    self._learner_steps_at_report = self._learner_steps
    self._last_report_mark = time.perf_counter()
    return steps

  def _report_window(self, step_i: int, window: Dict[str, Any],
                     window_s: float) -> Dict[str, Any]:
    with self._report_lock:
      learner_steps = self._covered_learner_steps()
    buckets = rl_metrics.bucket_table(
        self._bucket_episodes, self._bucket_successes,
        window_episodes=window['bucket_episodes'])
    record = self._make_record(
        window_s, window['actor_steps'], window['episodes'],
        window['successes'], window['transitions'],
        window['act_seconds'], learner_steps,
        self._sample_act_cache(), buckets, reporter='actor')
    self.telemetry.log(rl_metrics.RL_RECORD_KIND, step=step_i, **record)
    # The loop's own symptom->capture path: the acting step time is the
    # actor's "step time"; an armed actor.stall inflates one window and
    # must claim exactly one budgeted capture while the learner keeps
    # stepping (docs/rl_loop.md).
    step_time_s = (window['act_seconds'] / window['actor_steps']
                   if window['actor_steps'] else None)
    for anomaly in self.watchdog.observe(step_i, step_time_s):
      log_warning('RL watchdog anomaly: %s', anomaly.message)
      self.telemetry.log('anomaly', step=step_i, anomaly=anomaly.kind,
                         message=anomaly.message, detail=anomaly.detail)
      self.profiler.request_capture(anomaly.kind, step_i, anomaly.detail)
    self.telemetry.heartbeat(step_i)
    self.telemetry.flush()
    self._windows.append(record)
    return record

  def _learner_standin_report(self) -> None:
    """A learner-side ``kind="rl"`` window when the actor has gone
    quiet for several report intervals.

    The actor thread owns the report cadence; an actor that stops
    stepping — wedged, or legitimately finished while the learner runs
    to its own target — would otherwise emit no windows and no
    heartbeats at all, so a live actor stall would degrade to an
    anonymous heartbeat_stale and a healthy learner tail would page the
    same way. The stand-in carries zero actor/episode activity by
    construction (the actor is the only episode bookkeeper), the
    learner's step delta since the last window (whoever wrote it), and
    the completion flags the doctor uses to tell 'finished' from
    'stalled'.
    """
    cfg = self.config
    with self._report_lock:
      now = time.perf_counter()
      window_s = now - self._last_report_mark
      if window_s < 3 * cfg.report_interval_s:
        return  # the actor reported recently (or another stand-in did)
      learner_steps = self._covered_learner_steps()
    step_i = int(self._actor_steps.value
                 - self._counter_base['actor_steps'])
    buckets = rl_metrics.bucket_table(self._bucket_episodes,
                                      self._bucket_successes)
    record = self._make_record(
        window_s, 0, 0, 0, 0, 0.0, learner_steps,
        self._act_cache_gauge.value, buckets, reporter='learner')
    self.telemetry.log(rl_metrics.RL_RECORD_KIND, step=step_i, **record)
    self.telemetry.heartbeat(step_i)
    self.telemetry.flush()
    self._windows.append(record)

  def _actor_loop(self, deadline: Optional[float],
                  max_episodes: Optional[int]) -> None:
    cfg = self.config
    base_rng = jax.random.PRNGKey(cfg.seed)
    env_state, obs = self._place_env(
        *self.env.reset(jax.random.fold_in(base_rng, 2**16)))
    if cfg.artifact_workload and self._act_loaded is None:
      self._bind_act_artifact(env_state, obs, base_rng)
    act_fn = (self._act_loaded.executable
              if self._act_loaded is not None else self._act)
    buffers: List[List[Dict[str, np.ndarray]]] = [
        [] for _ in range(self.env.num_envs)]
    step_i = 0
    window = self._fresh_window()
    window_start = time.perf_counter()
    try:
      while not self._stop.is_set():
        if deadline is not None and time.perf_counter() >= deadline:
          break
        if max_episodes is not None and self._episodes >= max_episodes:
          break
        if self._learner_errors:
          # Fail fast: a dead learner means nobody learns from these
          # episodes — collecting for the rest of a deadline-only run
          # and surfacing the error only at join would waste it all.
          break
        report_path = self.profiler.maybe_profile(step_i)
        if report_path is not None:
          self.telemetry.log('forensics', step=step_i, report=report_path)
          self.telemetry.flush()
        if step_i % cfg.swap_poll_steps == 0:
          self._poll_swap()
        stall_s = fault_injection.actor_stall_seconds()
        if stall_s > 0.0:
          time.sleep(stall_s)
        t0 = time.perf_counter()
        env_state, obs, transition = act_fn(
            self._actor_variables, env_state, obs,
            jax.random.fold_in(base_rng, step_i))
        fetched = jax.device_get(transition)
        act_s = time.perf_counter() - t0 + stall_s
        self._act_ms.record(act_s * 1e3)
        step_i += 1
        self._actor_steps.inc()
        self._env_steps.inc(self.env.num_envs)
        window['actor_steps'] += 1
        window['act_seconds'] += act_s
        episodes_before = self._episodes
        successes_before = self._successes
        transitions_before = self._transitions.value
        for slot in np.flatnonzero(np.asarray(fetched['done'])):
          bucket = int(self.env.buckets[int(slot)])
          window['bucket_episodes'][bucket] = \
              window['bucket_episodes'].get(bucket, 0) + 1
        for slot in range(self.env.num_envs):
          self._flush_slot(fetched, slot, buffers)
        window['episodes'] += self._episodes - episodes_before
        window['successes'] += self._successes - successes_before
        window['transitions'] += \
            self._transitions.value - transitions_before
        self._check_targets()
        now = time.perf_counter()
        if now - window_start >= cfg.report_interval_s:
          self._report_window(step_i, window, now - window_start)
          window = self._fresh_window()
          window_start = now
    finally:
      now = time.perf_counter()
      if window['actor_steps']:
        self._report_window(step_i, window, max(now - window_start, 1e-9))
      self.profiler.finish(step_i)
      self._actor_done.set()
      self._check_targets()

  def _fresh_window(self) -> Dict[str, Any]:
    return {'actor_steps': 0, 'act_seconds': 0.0, 'episodes': 0,
            'successes': 0, 'transitions': 0, 'bucket_episodes': {}}

  # -- lifecycle -------------------------------------------------------------

  def _check_targets(self) -> None:
    """Sets the shared stop flag once every SPECIFIED target is met.

    A deadline-only run (no episode/step targets) never stops early —
    both sides run to the deadline. With both targets set, whichever
    side finishes first keeps the other running until its own target.
    """
    max_episodes = self._targets['max_episodes']
    max_learner_steps = self._targets['max_learner_steps']
    if max_episodes is None and max_learner_steps is None:
      return
    episodes_done = (max_episodes is None
                     or self._episodes >= max_episodes
                     or self._actor_done.is_set())
    learner_done = (max_learner_steps is None
                    or self._learner_steps >= max_learner_steps
                    or self._learner_done.is_set())
    if episodes_done and learner_done:
      self._stop.set()

  def run(self,
          max_seconds: Optional[float] = None,
          max_episodes: Optional[int] = None,
          max_learner_steps: Optional[int] = None) -> Dict[str, Any]:
    """Runs the closed loop until every configured target is met (or
    the deadline passes); returns the run summary.

    At least one bound must be given. The actor runs in THIS thread
    (it owns the telemetry/watchdog cadence); the learner runs in a
    daemon thread whose exceptions re-raise here after join.
    """
    if max_seconds is None and max_episodes is None and \
        max_learner_steps is None:
      raise ValueError('give at least one of max_seconds / max_episodes /'
                       ' max_learner_steps')
    cfg = self.config
    self._stop.clear()
    self._reset_run_state()
    self._targets = {'max_episodes': max_episodes,
                     'max_learner_steps': max_learner_steps}
    start = time.perf_counter()
    deadline = None if max_seconds is None else start + max_seconds

    state = self.trainer.init_state(*self._init_batch())
    self.bus.publish(1, self._snapshot_variables(state))
    self._learner_version_gauge.set(1.0)
    # Bootstrap adoption is direct: v1 (init weights) is the loop's
    # starting point, not a hot swap — it neither counts in ``swaps``
    # nor passes the learner.swap drop site (the actor must never act
    # from nothing).
    self._actor_version, self._actor_variables = self.bus.snapshot
    self._actor_version_gauge.set(float(self._actor_version))
    self.telemetry.log(
        'rl_start', num_envs=self.env.num_envs,
        episode_length=self.env.episode_length,
        num_buckets=getattr(self.env, 'num_buckets', 1),
        config={'cem_samples': cfg.cem_samples,
                'cem_iters': cfg.cem_iters,
                'batch_size': cfg.batch_size,
                'explore_prob': cfg.explore_prob,
                'swap_poll_steps': cfg.swap_poll_steps,
                'publish_every_steps': cfg.publish_every_steps})
    self.telemetry.flush()

    self._learner_errors = []
    learner_thread = threading.Thread(
        target=self._learner_loop,
        args=(state, deadline, max_learner_steps, self._learner_errors),
        name='t2r-rl-learner', daemon=True)
    learner_thread.start()
    try:
      self._actor_loop(deadline, max_episodes)
    except BaseException:
      self._stop.set()
      raise
    finally:
      # The learner keeps running toward ITS target after the actor
      # finishes (both-targets runs); only deadline/targets stop it.
      learner_thread.join(timeout=300.0)
      self._stop.set()
    if self._learner_errors:
      raise self._learner_errors[0]
    if learner_thread.is_alive():
      raise RuntimeError('learner thread failed to stop')

    elapsed = max(time.perf_counter() - start, 1e-9)
    buckets = rl_metrics.bucket_table(self._bucket_episodes,
                                      self._bucket_successes)
    env_steps = self._env_steps.value - self._counter_base['env_steps']
    actor_steps = (self._actor_steps.value
                   - self._counter_base['actor_steps'])
    transitions = (self._transitions.value
                   - self._counter_base['transitions'])
    summary = {
        'seconds': round(elapsed, 3),
        'num_envs': self.env.num_envs,
        'episodes': self._episodes,
        'successes': self._successes,
        'success_rate': round(self._successes / self._episodes, 4)
                        if self._episodes else 0.0,
        'episodes_per_sec': round(self._episodes / elapsed, 3),
        'env_steps': int(env_steps),
        'env_steps_per_sec': round(env_steps / elapsed, 2),
        'actor_steps': int(actor_steps),
        'learner_steps': self._learner_steps,
        'transitions': int(transitions),
        'swaps': self._swaps,
        'dropped_swaps': self._dropped_swaps,
        'actor_version': self._actor_version,
        'learner_version': self.bus.version,
        'act_jit_cache': self._sample_act_cache(),
        'buckets': buckets,
        'windows': list(self._windows),
    }
    spread = rl_metrics.scenario_success_spread(buckets)
    if spread is not None:
      summary['scenario_success_spread'] = round(spread, 4)
    self.telemetry.log('rl_stop', **{
        key: summary[key] for key in
        ('episodes', 'success_rate', 'learner_steps', 'swaps',
         'dropped_swaps', 'actor_version')})
    self.telemetry.flush()
    return summary

  def measure_success(self, variables=None, episodes: int = 32,
                      seed: int = 1234, max_steps: int = 1000) -> float:
    """Greedy (no-exploration) success rate over fresh episodes.

    Probes a snapshot OUTSIDE the training loop — the before/after
    criterion the loop test uses ("success measurably rises"). Uses a
    separate jitted program (explore_prob=0), leaving the acting-path
    jit cache untouched.
    """
    if variables is None:
      variables = self._actor_variables
      if variables is None:
        raise ValueError('no variables: run() first or pass variables')
    if self._greedy_act is None:
      cfg = self.config
      self._greedy_act = make_act_step(
          self.model, self.env, cem_samples=cfg.cem_samples,
          cem_iters=cfg.cem_iters, num_elites=cfg.num_elites,
          explore_prob=0.0, out_sharding=self._env_sharding)
    rng = jax.random.PRNGKey(seed)
    env_state, obs = self._place_env(
        *self.env.reset(jax.random.fold_in(rng, 1)))
    done_episodes = 0
    wins = 0
    for step in range(max_steps):
      env_state, obs, transition = self._greedy_act(
          variables, env_state, obs, jax.random.fold_in(rng, 2 + step))
      fetched = jax.device_get({key: transition[key]
                                for key in ('reward', 'done', 'terminal')})
      done = np.asarray(fetched['done'])
      wins += int(((np.asarray(fetched['reward']) > 0.5)
                   & np.asarray(fetched['terminal'])).sum())
      done_episodes += int(done.sum())
      if done_episodes >= episodes:
        break
    return wins / max(done_episodes, 1)

  def close(self) -> None:
    self.trainer.close()
    if self._owns_telemetry:
      self.telemetry.close()
    if self._owned_service is not None:
      self._owned_service.close()


def build_grasping_loop(model_dir: str,
                        num_envs: int = 16,
                        height: int = 48,
                        width: int = 64,
                        episode_length: int = 3,
                        scenario_config=None,
                        replay=None,
                        config: Optional[RLLoopConfig] = None,
                        num_shards: int = 2,
                        mesh=None,
                        seed: int = 0) -> RLLoop:
  """Wires the whole closed loop over the sim grasping MDP.

  ``replay``: None (an in-process ReplayService is created and owned by
  the loop), a ``host:port``/URL endpoint string, a ReplayService, or
  any client-API object. The critic is the test-scale sim critic at the
  env resolution with the adam recipe tests/test_offpolicy.py uses; the
  env randomizes scenarios per slot unless ``scenario_config`` pins
  them.
  """
  import optax

  from tensor2robot_tpu.envs import ScenarioConfig, VecGraspingEnv
  from tensor2robot_tpu.replay.service import ReplayConfig
  from tensor2robot_tpu.research.qtopt import grasping_sim
  from tensor2robot_tpu.rl.offpolicy import BellmanQTOptTrainer
  from tensor2robot_tpu.trainer import Trainer

  config = config or RLLoopConfig(seed=seed)
  if scenario_config is None:
    scenario_config = ScenarioConfig.randomized()
  env = VecGraspingEnv(num_envs, height=height, width=width,
                       episode_length=episode_length,
                       scenario_config=scenario_config, seed=seed)
  owned_service = None
  if replay is None:
    owned_service = ReplayService(ReplayConfig(
        num_shards=num_shards, batch_size=config.batch_size,
        seed=seed))
    client = LocalReplayClient(owned_service)
  elif isinstance(replay, str):
    client = ReplayClient(replay)
  elif isinstance(replay, ReplayService):
    client = LocalReplayClient(replay)
  else:
    client = replay
  model = grasping_sim.make_sim_critic_model(
      height, width, create_optimizer_fn=lambda: optax.adam(3e-3))
  trainer = Trainer(model, model_dir, mesh=mesh, async_checkpoints=False,
                    save_checkpoints_steps=10**9,
                    log_every_n_steps=10**9, auto_profile=False,
                    enable_watchdog=False, enable_pipeline_xray=False,
                    write_metrics=False)
  learner = BellmanQTOptTrainer(
      model, trainer,
      grasping_sim.make_candidate_actions_fn(config.num_candidates),
      num_candidates=config.num_candidates, gamma=config.gamma,
      target_update_steps=config.target_update_steps)
  return RLLoop(model, env, client, trainer, learner, model_dir,
                config=config, owned_service=owned_service)
