"""Synthetic visual grasping MDP with analytic Q*: the off-policy testbed.

The reference's QT-Opt numbers come from 580k real kuka grasps — data this
environment cannot replace. What it CAN do is make the off-policy
machinery *measurable*: a grasping-shaped MDP whose optimal Q-function is
known in closed form, so convergence benchmarks and tests have an exact
criterion instead of a saturating synthetic rule (the weakness VERDICT r4
item 3 called out in the supervised convergence field).

The MDP (grasp-descend semantics, matching the Grasping44 action layout of
t2r_models.py ACTION_DIM_LAYOUT):

  * State: gripper at height ``h`` above an object (``height_to_bottom``
    in the observation, drawn in the rendered camera frame).
  * ``close_gripper > 0.5``: the episode TERMINATES with reward
    ``1 if h <= threshold else 0`` (grasp attempted; movement ignored).
  * Otherwise the vertical component of ``world_vector`` descends the
    gripper: ``h' = clip(h - descent_scale * clip(wv_z, -1, 1), 0, h_max)``
    with reward 0, up to ``episode_length`` steps. TIMEOUT transitions are
    written with ``done=0`` (bootstrap through the time limit — timeouts
    are not environment terminals), the standard partial-episode fix.

Optimal values, with n(h) = ceil(max(0, h - threshold) / descent_scale):
    V*(h)            = gamma ** n(h)
    Q*(h, close)     = 1 if h <= threshold else 0
    Q*(h, no-close)  = gamma * V*(clip(h - descent_scale * wv_z, 0, h_max))

Learning Q* for n(h) = 2 states requires value to propagate through TWO
target-network generations — the benchmark cannot saturate before the
lagged-export machinery has turned over twice, by construction.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

from tensor2robot_tpu.data import wire
from tensor2robot_tpu.research.qtopt.t2r_models import (
    ACTION_DIM_LAYOUT,
)
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec
from tensor2robot_tpu.utils.image import numpy_to_image_string

# Constants chosen for BALANCE under random exploration: heights are
# ~uniform over [0, H_MAX] in steady state, so P(h <= THRESHOLD) ~ 0.4 —
# close-terminal positives and negatives arrive in comparable numbers.
# (The round-5 first cut used THRESHOLD=0.25/H_MAX=1.2: ~13% positives on
# a conjunction rule, and the full-scale critic regressed the dataset
# mean instead of the rule — measured in round 5.)
THRESHOLD = 0.5
DESCENT_SCALE = 0.35
H_MAX = 1.6
GAMMA = 0.8


def action_dim_offset(name: str) -> int:
  """Start offset of one ACTION_DIM_LAYOUT block in the flat CEM vector."""
  offset = 0
  for key, size in ACTION_DIM_LAYOUT:
    if key == name:
      return offset
    offset += size
  raise KeyError(name)


# Flat CEM-action indices, derived from the layout so a reordering of
# ACTION_DIM_LAYOUT cannot silently desynchronize the numpy env, the
# vectorized env (envs/grasping.py) and the actor's exploration
# (rl/loop.py) — all three import these.
WV_Z_INDEX = action_dim_offset('world_vector') + 2  # world_vector z
CLOSE_INDEX = action_dim_offset('close_gripper')
OPEN_INDEX = action_dim_offset('open_gripper')
TERMINATE_INDEX = action_dim_offset('terminate_episode')


def steps_to_grasp(h: float, threshold: float = THRESHOLD,
                   descent_scale: float = DESCENT_SCALE) -> int:
  return int(math.ceil(max(0.0, h - threshold) / descent_scale))


def optimal_value(h: float, gamma: float = GAMMA, **kwargs) -> float:
  return gamma ** steps_to_grasp(h, **kwargs)


def _action_vector(wv_z: float = 0.0, close: float = 0.0) -> np.ndarray:
  """8-dim CEM action per ACTION_DIM_LAYOUT with the used dims set."""
  action = np.zeros((8,), np.float32)
  action[WV_Z_INDEX] = wv_z
  action[CLOSE_INDEX] = close
  return action


def gradient_background(height: int, width: int) -> np.ndarray:
  """The camera frame's deterministic background, float32 [H, W, 3].

  Shared with the vectorized JAX port (envs/grasping.py): both envs
  render over the SAME host-computed constant, so the per-pixel parity
  contract reduces to the (pure, float32) scene drawing."""
  x = np.linspace(0, 1, width)
  y = np.linspace(0, 1, height)
  return (np.outer(y, x)[..., None]
          * np.array([140, 160, 180])).astype(np.float32)


class SimGraspingEnv:
  """Gym-style visual grasping env (reset() -> obs; step(a) -> o, r, d, i).

  Observations match the Grasping44 serving contract
  (t2r_models.pack_features_kuka_e2e): ``image`` uint8 [H, W, 3],
  ``gripper_closed`` and ``height_to_bottom`` scalars. ``info['terminal']``
  distinguishes a genuine grasp-attempt terminal from a timeout.

  ``safe_region``: ((y0, y1), (x0, x1)) pixel box guaranteed visible under
  every train-time random crop; scene content stays inside it so the
  crop never hides the task. Defaults to the 512x640 -> 472x472 band.
  """

  def __init__(self,
               height: int = 512,
               width: int = 640,
               episode_length: int = 3,
               threshold: float = THRESHOLD,
               descent_scale: float = DESCENT_SCALE,
               safe_region: Optional[Tuple[Tuple[int, int],
                                           Tuple[int, int]]] = None,
               noise_scale: float = 4.0,
               seed: Optional[int] = None):
    self._height = height
    self._width = width
    self._episode_length = episode_length
    self._threshold = threshold
    self._descent_scale = descent_scale
    self._noise_scale = float(noise_scale)
    if safe_region is None:
      if (height, width) == (512, 640):
        safe_region = ((40, 472), (168, 472))
      else:
        safe_region = ((0, height), (0, width))
    self._safe = safe_region
    self._rng = np.random.RandomState(seed)
    self._h = 0.0
    self._t = 0
    self._background = None

  @property
  def threshold(self) -> float:
    return self._threshold

  def _render(self, h: float) -> np.ndarray:
    """Camera-like frame: gradient + noise, object block, gripper at h."""
    height, width = self._height, self._width
    if self._background is None:
      self._background = gradient_background(height, width)
    img = self._background.copy()
    (y0, y1), (x0, x1) = self._safe
    band_h, band_w = y1 - y0, x1 - x0
    block = max(6, band_h // 14)
    cx = x0 + band_w // 2
    # Object sits on the "bin floor" at the bottom of the safe band.
    obj_y = y1 - 2 * block
    img[obj_y:obj_y + block, cx - block:cx + block] = (200, 40, 40)
    # Gripper height h in [0, H_MAX] maps to the band above the object.
    frac = min(max(h / H_MAX, 0.0), 1.0)
    grip_y = int(obj_y - block - frac * (band_h - 4 * block))
    grip_y = max(y0, grip_y)
    img[grip_y:grip_y + block, cx - block // 2:cx + block // 2] = (
        40, 200, 60)
    if self._noise_scale:
      img = img + self._rng.randn(height, width, 1) * self._noise_scale
    return np.clip(img, 0, 255).astype(np.uint8)

  def _obs(self) -> dict:
    return {'image': self._render(self._h),
            'gripper_closed': 0.0,
            'height_to_bottom': float(self._h)}

  def reset(self) -> dict:
    self._h = float(self._rng.uniform(0.1, 1.1))
    self._t = 0
    return self._obs()

  def step(self, action):
    action = np.asarray(action, np.float32).ravel()
    close = float(action[CLOSE_INDEX]) > 0.5
    self._t += 1
    if close:
      reward = 1.0 if self._h <= self._threshold else 0.0
      return self._obs(), reward, True, {'terminal': True}
    wv_z = float(np.clip(action[WV_Z_INDEX], -1.0, 1.0))
    self._h = float(np.clip(self._h - self._descent_scale * wv_z,
                            0.0, H_MAX))
    timeout = self._t >= self._episode_length
    return self._obs(), 0.0, timeout, {'terminal': False}

  def close(self):
    pass


class SimGraspingRandomPolicy:
  """Random exploration policy (collect_eval_loop policy protocol)."""

  def __init__(self, close_prob: float = 0.4, seed: Optional[int] = None):
    self._close_prob = close_prob
    self._rng = np.random.RandomState(seed)

  def reset(self):
    pass

  def restore(self) -> bool:
    return True

  def init_randomly(self) -> None:
    pass

  @property
  def global_step(self) -> int:
    return 0

  def sample_action(self, obs, explore_prob):
    del obs, explore_prob
    action = self._rng.uniform(-1.0, 1.0, 8).astype(np.float32)
    action[CLOSE_INDEX] = float(self._rng.rand() < self._close_prob)
    action[OPEN_INDEX] = float(self._rng.rand() < 0.5)
    action[TERMINATE_INDEX] = 0.0
    return action, None


# -- replay records ----------------------------------------------------------

# On-disk feature names for the off-policy extras. The state-side names
# follow the Grasping44 specs ('image_1', action key names).
NEXT_IMAGE_NAME = 'next/image_1'
NEXT_GRIPPER_CLOSED_NAME = 'next/gripper_closed'
NEXT_HEIGHT_NAME = 'next/height_to_bottom'
DONE_NAME = 'done'


def offpolicy_extra_feature_specs(image_spec: TensorSpec) -> SpecStruct:
  """Parsing specs for next-state + done, mirroring the raw image spec.

  Keyed so rl/offpolicy.split_offpolicy_batch renames ``next/<key>``
  straight back to critic in-spec keys.
  """
  extra = SpecStruct()
  extra['next/state/image'] = TensorSpec.from_spec(image_spec,
                                                   name=NEXT_IMAGE_NAME)
  extra['next/action/gripper_closed'] = TensorSpec(
      (1,), np.float32, name=NEXT_GRIPPER_CLOSED_NAME)
  extra['next/action/height_to_bottom'] = TensorSpec(
      (1,), np.float32, name=NEXT_HEIGHT_NAME)
  extra[DONE_NAME] = TensorSpec((1,), np.float32, name=DONE_NAME)
  return extra


def episode_to_transitions_grasping(episode_data,
                                    image_name: str = 'image_1',
                                    reward_name: str = 'grasp_success'
                                    ) -> List[bytes]:
  """(obs, action, reward, next_obs, done, info) -> transition Examples.

  Timeout transitions get ``done=0`` (module docstring): done reflects
  ``info['terminal']`` — whether the grasp was attempted — not whether
  the episode stopped.
  """
  transitions = []
  for obs, action, reward, next_obs, _done, info in episode_data:
    terminal = bool(info.get('terminal', False))
    example = {
        image_name: numpy_to_image_string(obs['image'], 'jpeg'),
        NEXT_IMAGE_NAME: numpy_to_image_string(next_obs['image'], 'jpeg'),
        NEXT_GRIPPER_CLOSED_NAME: np.asarray(
            [next_obs['gripper_closed']], np.float32),
        NEXT_HEIGHT_NAME: np.asarray(
            [next_obs['height_to_bottom']], np.float32),
        DONE_NAME: np.asarray([1.0 if terminal else 0.0], np.float32),
        reward_name: np.asarray([reward], np.float32),
    }
    flat_action = np.asarray(action, np.float32).ravel()
    offset = 0
    for key, size in ACTION_DIM_LAYOUT:
      example[key] = flat_action[offset:offset + size]
      offset += size
    example['gripper_closed'] = np.asarray(
        [obs['gripper_closed']], np.float32)
    example['height_to_bottom'] = np.asarray(
        [obs['height_to_bottom']], np.float32)
    transitions.append(wire.build_example(example))
  return transitions


def make_candidate_actions_fn(num_candidates: int):
  """Uniform CEM-style candidates for the Bellman max (rl/offpolicy.py).

  Returns all Grasping44 action keys flat [B*K, ...], state-major blocks;
  gripper status keys repeat the NEXT state's observed values.
  """
  import jax
  import jax.numpy as jnp

  def candidate_actions(rng, batch, next_features):
    k = num_candidates
    n = batch * k
    r_world, r_rot, r_disc = jax.random.split(rng, 3)
    out = {
        'action/world_vector': jax.random.uniform(
            r_world, (n, 3), minval=-1.0, maxval=1.0),
        'action/vertical_rotation': jax.random.uniform(
            r_rot, (n, 2), minval=-1.0, maxval=1.0),
    }
    disc = jax.random.bernoulli(r_disc, 0.5, (n, 3)).astype(jnp.float32)
    out['action/close_gripper'] = disc[:, 0:1]
    out['action/open_gripper'] = disc[:, 1:2]
    out['action/terminate_episode'] = jnp.zeros((n, 1), jnp.float32)
    for key in ('action/gripper_closed', 'action/height_to_bottom'):
      out[key] = jnp.repeat(
          jnp.asarray(next_features[key], jnp.float32).reshape(batch, 1),
          k, axis=0)
    return out

  return candidate_actions


# -- test-scale critic -------------------------------------------------------


def _small_image_preprocessor_cls(height: int, width: int):
  """A Grasping44-style jpeg-in/float-out preprocessor at test resolution."""
  from tensor2robot_tpu.modes import ModeKeys as _ModeKeys
  from tensor2robot_tpu.preprocessors.spec_transformation_preprocessor \
      import SpecTransformationPreprocessor

  class _SmallImagePreprocessor(SpecTransformationPreprocessor):

    def update_spec_transform(self, key, spec, mode):
      del mode
      if key == 'state/image':
        return TensorSpec.from_spec(spec, shape=(height, width, 3),
                                    dtype=np.uint8, data_format='jpeg')
      return spec

    def _preprocess_fn(self, features, labels, mode, rng=None):
      del mode, rng
      import jax.numpy as jnp
      features['state/image'] = jnp.asarray(
          features['state/image'], jnp.float32) / 255.0
      return features, labels

  return _SmallImagePreprocessor


def _build_sim_qnet():
  import flax.linen as nn
  import jax.numpy as jnp

  class SimQNet(nn.Module):
    """Tiny conv critic with the megabatch contract of GraspingQNetwork:
    the image tower runs once per STATE; flat [B*K] action rows reshape
    to [B, K, d] and score against the broadcast state embedding."""

    hidden: int = 64

    @nn.compact
    def __call__(self, features, mode: str = 'train', train: bool = False):
      del mode, train
      image = jnp.asarray(features['state/image'], jnp.float32)
      keys = [k for k, _ in ACTION_DIM_LAYOUT] + ['gripper_closed',
                                                  'height_to_bottom']
      params = jnp.concatenate(
          [jnp.asarray(features['action/' + key], jnp.float32).reshape(
              (jnp.asarray(features['action/' + key]).shape[0], -1))
           for key in keys], axis=-1)
      x = image
      for width in (8, 16):
        x = nn.relu(nn.Conv(width, (3, 3), strides=(2, 2))(x))
      x = x.reshape((x.shape[0], -1))
      x = nn.relu(nn.Dense(self.hidden)(x))
      batch = x.shape[0]
      if params.shape[0] != batch:
        params = params.reshape((batch, -1, params.shape[-1]))  # [B, K, d]
        x = jnp.broadcast_to(x[:, None, :],
                             (batch, params.shape[1], x.shape[-1]))
      h = jnp.concatenate([x, nn.relu(nn.Dense(self.hidden)(params))],
                          axis=-1)
      h = nn.relu(nn.Dense(self.hidden)(h))
      logits = nn.Dense(1)(h).reshape((-1,))
      return {'q_logits': logits, 'q_predicted': nn.sigmoid(logits)}

  return SimQNet


def make_sim_critic_model(height: int = 64, width: int = 80, **kwargs):
  """Test-scale CriticModel over SimGraspingEnv observations.

  Same spec keys and on-disk names as the Grasping44 flagship (so the
  replay/candidate helpers above work unchanged), tiny network, any
  resolution. Used by tests/test_offpolicy.py and the RL loop.
  """
  from tensor2robot_tpu.models.critic_model import CriticModel

  class SimGraspingCriticModel(CriticModel):

    def get_state_specification(self) -> SpecStruct:
      return SpecStruct(image=TensorSpec((height, width, 3), np.float32,
                                         name='image_1'))

    def get_action_specification(self) -> SpecStruct:
      spec = SpecStruct()
      for key, size in ACTION_DIM_LAYOUT + (('gripper_closed', 1),
                                            ('height_to_bottom', 1)):
        spec[key] = TensorSpec((size,), np.float32, name=key)
      return spec

    def get_label_specification(self, mode: str) -> SpecStruct:
      del mode
      return SpecStruct(reward=TensorSpec((1,), np.float32,
                                          name='grasp_success'))

    def create_network(self):
      return _build_sim_qnet()()

  kwargs.setdefault('preprocessor_cls',
                    _small_image_preprocessor_cls(height, width))
  kwargs.setdefault('device_type', 'cpu')
  return SimGraspingCriticModel(**kwargs)


# -- held-out criterion ------------------------------------------------------


def build_ranking_pairs(env: SimGraspingEnv,
                        per_type: int = 32,
                        seed: int = 7,
                        gamma: float = GAMMA
                        ) -> Sequence[Tuple[dict, dict]]:
  """Margin-robust (better, worse) feature batches with known Q* order.

  Three pair families, in increasing bootstrap depth:
    1. aligned (n=0):  close-now (Q*=1)        >  ascend (Q*=gamma**2)
    2. one step out:   descend (Q*=gamma)      >  ascend (Q*=gamma**3)
    3. two steps out:  descend (Q*=gamma**2)   >  ascend (Q*=gamma**4)
  Families 2 and 3 compare two BOOTSTRAPPED arms (descend vs ascend at
  the same height): both sit at the sigmoid's ~0.5 until real value has
  propagated, so they cannot be ordered by the supervised terminal
  signal alone — family 3 orders correctly only after value has flowed
  through two lagged-target generations, the non-saturation guarantee.
  (A close-at-misaligned worse arm would be learnable from terminal
  transitions alone — Q driven to 0 supervised — and was rejected for
  exactly that reason.) Margins are robust to the candidate-limited max
  (random candidates descend ~0.3-0.4 per step instead of the exact
  0.4) and hold for any gamma in (0, 1).
  """
  del gamma  # orderings hold for any gamma in (0, 1)
  rng = np.random.RandomState(seed)
  thr, scale = env.threshold, env._descent_scale
  descend = _action_vector(wv_z=1.0, close=0.0)
  ascend = _action_vector(wv_z=-1.0, close=0.0)
  families = [
      (rng.uniform(0.02, thr - 0.05, per_type),
       _action_vector(wv_z=0.0, close=1.0), ascend),
      (rng.uniform(thr + 0.25 * scale, thr + 0.75 * scale, per_type),
       descend, ascend),
      (rng.uniform(thr + 1.3 * scale, thr + 1.8 * scale, per_type),
       descend, ascend),
  ]
  pairs = []
  for heights, better_action, worse_action in families:
    images = np.stack([env._render(h) for h in heights])
    better, worse = {}, {}
    for feats, action in ((better, better_action), (worse, worse_action)):
      feats['state/image'] = images
      offset = 0
      for key, size in ACTION_DIM_LAYOUT:
        feats['action/' + key] = np.tile(
            action[offset:offset + size], (per_type, 1))
        offset += size
      feats['action/gripper_closed'] = np.zeros((per_type, 1), np.float32)
      feats['action/height_to_bottom'] = np.asarray(
          heights, np.float32).reshape(per_type, 1)
    pairs.append((better, worse))
  return pairs
