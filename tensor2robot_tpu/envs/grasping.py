"""VecGraspingEnv: the numpy SimGraspingEnv as a pure-JAX batch of MDPs.

A per-slot parity port of ``research/qtopt/grasping_sim.SimGraspingEnv``
(tests/test_envs.py pins obs pixels, rewards, done/auto-reset semantics
and ``optimal_value`` agreement against the original), lifted to the
``envs.vec_env`` contract so the whole B-slot world advances inside one
jitted program:

  * **State is explicit**: ``GraspState(h, t, rng)`` with a leading
    ``num_envs`` dim; ``step`` is a pure function the actor fuses with
    CEM action selection (rl/loop.py) — the Anakin collect-on-device
    pattern (arXiv:2104.06272).
  * **Scenarios are a batch dimension**: every slot carries its own
    grasp threshold (object geometry), descent scale (dynamics), camera
    shift and sensor noise, sampled once from a seeded
    ``ScenarioConfig`` — one acting step sweeps ``num_envs`` DISTINCT
    scenarios, and each slot's difficulty ``bucket`` id keys the
    per-scenario success telemetry (``t2r.rl.v1``, docs/rl_loop.md).
  * **Replay semantics survive the port**: grasp attempts terminate
    with ``terminal=True``; timeouts end the episode (``done``) but are
    NOT env terminals — the loop writes them with ``done=0`` so value
    bootstraps through the time limit, exactly like the numpy
    collector path (grasping_sim module docstring).

Rendering reuses the numpy env's host-computed gradient background
(``grasping_sim.gradient_background``) and draws the object/gripper
blocks with index masks — the same float32 arithmetic as the numpy
slice assignments, so with matched noise the pixel parity is exact,
not approximate.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.envs.vec_env import VecEnv, VecStep
from tensor2robot_tpu.research.qtopt.grasping_sim import (
    CLOSE_INDEX,
    DESCENT_SCALE,
    GAMMA,
    H_MAX,
    THRESHOLD,
    WV_Z_INDEX,
    gradient_background,
)

__all__ = ['GraspState', 'ScenarioConfig', 'Scenarios', 'VecGraspingEnv',
           'sample_scenarios']


class GraspState(NamedTuple):
  """Per-slot env state; every leaf is [num_envs]-leading."""

  h: jnp.ndarray    # [B] float32 gripper height above the object
  t: jnp.ndarray    # [B] int32 step index within the episode
  rng: jnp.ndarray  # [B, 2] uint32 per-slot PRNG keys


@dataclasses.dataclass(frozen=True)
class ScenarioConfig:
  """Per-slot randomization ranges; the defaults reproduce the numpy
  env's fixed constants (no randomization — the parity configuration).

  ``randomized()`` is the scenario-sweep preset the RL loop uses: a
  spread of grasp thresholds (object geometry), descent scales
  (dynamics), small camera shifts and sensor-noise levels. Buckets
  partition ``threshold_range`` into ``num_buckets`` equal difficulty
  bins — the label per-scenario success telemetry aggregates by.
  """

  num_buckets: int = 8
  threshold_range: Tuple[float, float] = (THRESHOLD, THRESHOLD)
  descent_scale_range: Tuple[float, float] = (DESCENT_SCALE, DESCENT_SCALE)
  camera_shift_px: int = 0
  noise_scale_range: Tuple[float, float] = (4.0, 4.0)
  reset_h_range: Tuple[float, float] = (0.1, 1.1)

  @classmethod
  def randomized(cls, num_buckets: int = 8,
                 camera_shift_px: int = 2) -> 'ScenarioConfig':
    return cls(num_buckets=num_buckets,
               threshold_range=(0.35, 0.65),
               descent_scale_range=(0.25, 0.45),
               camera_shift_px=camera_shift_px,
               noise_scale_range=(0.0, 6.0))


class Scenarios(NamedTuple):
  """One sampled scenario per env slot (host numpy arrays)."""

  threshold: np.ndarray      # [B] float32
  descent_scale: np.ndarray  # [B] float32
  shift_y: np.ndarray        # [B] int32 camera shift (rows)
  shift_x: np.ndarray        # [B] int32 camera shift (cols)
  noise_scale: np.ndarray    # [B] float32 sensor noise stddev
  bucket: np.ndarray         # [B] int32 difficulty bucket id


def sample_scenarios(config: ScenarioConfig, num_envs: int,
                     seed: int = 0) -> Scenarios:
  """Draws ``num_envs`` scenarios from a seeded config, deterministically."""
  rng = np.random.RandomState(seed)
  lo, hi = config.threshold_range
  threshold = rng.uniform(lo, hi, num_envs).astype(np.float32)
  descent = rng.uniform(*config.descent_scale_range,
                        size=num_envs).astype(np.float32)
  shift = int(config.camera_shift_px)
  shift_y = rng.randint(-shift, shift + 1, num_envs).astype(np.int32)
  shift_x = rng.randint(-shift, shift + 1, num_envs).astype(np.int32)
  noise = rng.uniform(*config.noise_scale_range,
                      size=num_envs).astype(np.float32)
  if hi > lo:
    bucket = np.clip(((threshold - lo) / (hi - lo))
                     * config.num_buckets, 0,
                     config.num_buckets - 1).astype(np.int32)
  else:
    bucket = np.zeros(num_envs, np.int32)
  return Scenarios(threshold=threshold, descent_scale=descent,
                   shift_y=shift_y, shift_x=shift_x, noise_scale=noise,
                   bucket=bucket)


class _ScenarioSlot(NamedTuple):
  """The traced per-slot scenario leaves ``step``/``reset`` vmap over."""

  threshold: jnp.ndarray
  descent_scale: jnp.ndarray
  shift_y: jnp.ndarray
  shift_x: jnp.ndarray
  noise_scale: jnp.ndarray


class VecGraspingEnv(VecEnv):
  """B independent grasping MDPs, one jittable step (module docstring).

  Observations per slot match the numpy env (and the Grasping44 serving
  contract): ``image`` uint8 [H, W, 3], ``gripper_closed`` and
  ``height_to_bottom`` float32 scalars.
  """

  def __init__(self,
               num_envs: int,
               height: int = 64,
               width: int = 80,
               episode_length: int = 3,
               scenarios: Optional[Scenarios] = None,
               scenario_config: Optional[ScenarioConfig] = None,
               seed: int = 0,
               safe_region: Optional[Tuple[Tuple[int, int],
                                           Tuple[int, int]]] = None):
    if num_envs < 1:
      raise ValueError('num_envs must be >= 1; got {}'.format(num_envs))
    self._num_envs = int(num_envs)
    self._height = int(height)
    self._width = int(width)
    self._episode_length = int(episode_length)
    self.scenario_config = scenario_config or ScenarioConfig()
    if scenarios is None:
      scenarios = sample_scenarios(self.scenario_config, num_envs, seed)
    if len(scenarios.threshold) != num_envs:
      raise ValueError('scenarios carry {} slots for num_envs={}'.format(
          len(scenarios.threshold), num_envs))
    self.scenarios = scenarios
    if safe_region is None:
      # Same defaulting rule as SimGraspingEnv: the 512x640 camera frame
      # keeps scene content inside the crop-proof band.
      if (self._height, self._width) == (512, 640):
        safe_region = ((40, 472), (168, 472))
      else:
        safe_region = ((0, self._height), (0, self._width))
    self._safe = safe_region
    self._background = jnp.asarray(gradient_background(height, width))
    self._scn = _ScenarioSlot(
        threshold=jnp.asarray(scenarios.threshold),
        descent_scale=jnp.asarray(scenarios.descent_scale),
        shift_y=jnp.asarray(scenarios.shift_y),
        shift_x=jnp.asarray(scenarios.shift_x),
        noise_scale=jnp.asarray(scenarios.noise_scale))

  # -- properties ------------------------------------------------------------

  @property
  def num_envs(self) -> int:
    return self._num_envs

  @property
  def height(self) -> int:
    return self._height

  @property
  def width(self) -> int:
    return self._width

  @property
  def episode_length(self) -> int:
    return self._episode_length

  @property
  def buckets(self) -> np.ndarray:
    """Static per-slot difficulty bucket ids (host-side)."""
    return self.scenarios.bucket

  @property
  def num_buckets(self) -> int:
    return int(self.scenario_config.num_buckets)

  # -- rendering -------------------------------------------------------------

  def _render_one(self, h, scn: _ScenarioSlot):
    """One slot's pre-noise frame, float32 [H, W, 3].

    The same drawing the numpy env performs with slice assignment,
    expressed as index masks (jit/vmap-friendly); with zero camera
    shift the arithmetic is identical, which is what the pixel parity
    test relies on.
    """
    (y0, y1), (x0, x1) = self._safe
    band_h, band_w = y1 - y0, x1 - x0
    block = max(6, band_h // 14)
    cx = jnp.clip(x0 + band_w // 2 + scn.shift_x, x0 + block, x1 - block)
    obj_y = jnp.clip(y1 - 2 * block + scn.shift_y, y0, y1 - 2 * block)
    frac = jnp.clip(h / H_MAX, 0.0, 1.0)
    # int() truncation in the numpy env == floor here: the pre-clamp
    # value is >= y0 + block by construction (band geometry).
    grip_y = jnp.maximum(
        y0, jnp.floor(obj_y - block - frac * (band_h - 4 * block))
        .astype(jnp.int32))
    ys = jnp.arange(self._height)[:, None]
    xs = jnp.arange(self._width)[None, :]
    img = self._background
    obj = ((ys >= obj_y) & (ys < obj_y + block)
           & (xs >= cx - block) & (xs < cx + block))
    img = jnp.where(obj[..., None],
                    jnp.asarray((200.0, 40.0, 40.0), jnp.float32), img)
    grip = ((ys >= grip_y) & (ys < grip_y + block)
            & (xs >= cx - block // 2) & (xs < cx + block // 2))
    img = jnp.where(grip[..., None],
                    jnp.asarray((40.0, 200.0, 60.0), jnp.float32), img)
    return img

  def _finish_one(self, img, noise_scale, key):
    noise = jax.random.normal(
        key, (self._height, self._width, 1), jnp.float32)
    img = img + noise * noise_scale
    return jnp.clip(img, 0.0, 255.0).astype(jnp.uint8)

  def _obs_one(self, h, scn: _ScenarioSlot, key):
    image = self._finish_one(self._render_one(h, scn), scn.noise_scale,
                             key)
    return {'image': image,
            'gripper_closed': jnp.float32(0.0),
            'height_to_bottom': jnp.asarray(h, jnp.float32)}

  def render(self, h):
    """[B] heights -> uint8 frames under each slot's scenario, no noise
    (test/visualization helper; the step path uses the per-slot keys)."""
    def one(h_slot, scn):
      img = self._render_one(jnp.asarray(h_slot, jnp.float32), scn)
      return jnp.clip(img, 0.0, 255.0).astype(jnp.uint8)
    return jax.vmap(one)(jnp.asarray(h, jnp.float32), self._scn)

  # -- the contract ----------------------------------------------------------

  def state_for_heights(self, heights, rng) -> GraspState:
    """A fresh state pinned at explicit per-slot heights (parity tests)."""
    keys = jax.random.split(jnp.asarray(rng), self._num_envs)
    return GraspState(h=jnp.asarray(heights, jnp.float32),
                      t=jnp.zeros((self._num_envs,), jnp.int32),
                      rng=keys)

  def reset(self, rng):
    keys = jax.random.split(jnp.asarray(rng), self._num_envs)

    def one(key, scn):
      key, k_h, k_obs = jax.random.split(key, 3)
      lo, hi = self.scenario_config.reset_h_range
      h = jax.random.uniform(k_h, (), jnp.float32, lo, hi)
      return (h, jnp.int32(0), key), self._obs_one(h, scn, k_obs)

    (h, t, key), obs = jax.vmap(one)(keys, self._scn)
    return GraspState(h=h, t=t, rng=key), obs

  def step(self, state: GraspState, action) -> VecStep:
    """Advances every slot; auto-resets finished episodes (VecEnv)."""

    def one(h, t, key, scn, act):
      act = jnp.asarray(act, jnp.float32).reshape(-1)
      close = act[CLOSE_INDEX] > 0.5
      t1 = t + 1
      wv_z = jnp.clip(act[WV_Z_INDEX], -1.0, 1.0)
      h_moved = jnp.clip(h - scn.descent_scale * wv_z, 0.0, H_MAX)
      h_next = jnp.where(close, h, h_moved)
      terminal = close
      reward = jnp.where(close & (h <= scn.threshold), 1.0, 0.0)
      timeout = (~close) & (t1 >= self._episode_length)
      done = terminal | timeout
      key, k_next, k_obs, k_reset = jax.random.split(key, 4)
      next_obs = self._obs_one(h_next, scn, k_next)
      lo, hi = self.scenario_config.reset_h_range
      h_reset = jax.random.uniform(k_reset, (), jnp.float32, lo, hi)
      h_new = jnp.where(done, h_reset, h_next)
      t_new = jnp.where(done, jnp.int32(0), t1)
      reset_obs = self._obs_one(h_new, scn, k_obs)
      obs = jax.tree.map(
          lambda fresh, old: jnp.where(done, fresh, old), reset_obs,
          next_obs)
      return ((h_new, t_new, key), obs, reward, done,
              {'terminal': terminal, 'timeout': timeout,
               'next_obs': next_obs})

    (h, t, key), obs, reward, done, info = jax.vmap(one)(
        state.h, state.t, state.rng, self._scn, action)
    return VecStep(state=GraspState(h=h, t=t, rng=key), obs=obs,
                   reward=reward, done=done, info=info)

  # -- the analytic criterion ------------------------------------------------

  def steps_to_grasp(self, h):
    """Per-slot n(h) under each slot's threshold/descent (vectorized
    twin of grasping_sim.steps_to_grasp)."""
    h = jnp.asarray(h, jnp.float32)
    need = jnp.maximum(0.0, h - self._scn.threshold)
    return jnp.ceil(need / self._scn.descent_scale).astype(jnp.int32)

  def optimal_value(self, h, gamma: float = GAMMA):
    """V*(h) = gamma ** n(h) per slot (grasping_sim.optimal_value)."""
    return jnp.asarray(gamma, jnp.float32) ** self.steps_to_grasp(h)
