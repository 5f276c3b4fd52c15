"""QT-Opt T2R models: the Grasping44 critic wrapped for the T2R stack.

Parity target: /root/reference/research/qtopt/t2r_models.py:50-405
(``pack_features_kuka_e2e`` :50, ``LegacyGraspingModelWrapper`` :66,
``DefaultGrasping44ImagePreprocessor`` :246, the E2E open/close/terminate
model :316). The TF1 responsibilities map as:

  * legacy hparams + BuildOpt optimizer (ref :82-100) -> ``optimizer_builder``
    optax chain; MovingAverageOptimizer/swapping-saver becomes
    ``use_avg_model_params`` EMA in TrainState (eval/serve read averaged
    params), see optimizer_builder.py docstring.
  * ``q_func`` building the slim graph (ref :143-162,:370-397) -> a Flax
    module (``GraspingQNetwork``) extracting image + grasp params from the
    spec-validated feature struct and running ``Grasping44Network``.
  * slim REGULARIZATION_LOSSES picked up by tf.losses.get_total_loss()
    (ref model_train_fn :233-243) -> explicit ``l2_regularization_loss``
    added to the sigmoid-cross-entropy grasp loss.
  * CEM action tiling via contrib_seq2seq.tile_batch (ref networks.py:520-527,
    concat_axis=2 in PREDICT :380-385) -> the action megabatch: candidate
    actions arrive flat ``[B*action_batch, d]``, are reshaped to
    ``[B, action_batch, d]``, and the image tower runs ONCE per state —
    only the embedding is tiled, so the MXU sees one large fused batch.

The preprocessor takes 512x640 uint8 camera images (jpeg on disk), random-
crops (train) or center-crops (eval/predict) to 472x472, converts to [0,1]
float and applies the paper's photometric distortions — all inside the jitted
step on device (the reference does this on host CPU in tf.data).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensor2robot_tpu.models import abstract_model
from tensor2robot_tpu.models.critic_model import CriticModel
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.preprocessors import image_transformations
from tensor2robot_tpu.preprocessors import pallas_crop
from tensor2robot_tpu.preprocessors.spec_transformation_preprocessor import (
    SpecTransformationPreprocessor,
)
from tensor2robot_tpu.research.qtopt import networks
from tensor2robot_tpu.research.qtopt import optimizer_builder
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec

INPUT_SHAPE = (512, 640, 3)
TARGET_SHAPE = (472, 472)

# Flat [N, 10] action-vector layout used by pack_features_kuka_e2e: the
# first 8 dims are CEM-sampled controls, the last 2 are gripper status
# carried in the action spec (ref get_action_specification :341-364).
ACTION_DIM_LAYOUT = (
    ('world_vector', 3),
    ('vertical_rotation', 2),
    ('close_gripper', 1),
    ('open_gripper', 1),
    ('terminate_episode', 1),
)
CEM_ACTION_SIZE = 8  # world_vector + vertical_rotation + 3 discrete controls


def pack_features_kuka_e2e(t2r_model, state, context, timestep, actions
                           ) -> Dict[str, np.ndarray]:
  """Packs one observation + N candidate actions for the CEM predictor.

  The reference's implementation is stripped from the OSS release
  (ref t2r_models.py:50-61 raises NotImplementedError); this provides the
  behavior its callers (CEM policies, ref policies.py:139-172) require.

  Args:
    t2r_model: the model (unused; kept for the reference pack_fn signature).
    state: observation dict with 'image' (uint8 [512, 640, 3] camera frame),
      'gripper_closed' and 'height_to_bottom' scalars.
    context: unused.
    timestep: unused.
    actions: [N, 8] CEM samples laid out per ACTION_DIM_LAYOUT.

  Returns:
    Numpy feed dict matching the preprocessor's PREDICT in-spec: the raw
    image once (batch 1; the device-side preprocessor center-crops it) and
    the N candidate actions.
  """
  del t2r_model, context, timestep
  actions = np.asarray(actions, np.float32)
  num_samples = actions.shape[0]
  features = {'state/image': np.expand_dims(np.asarray(state['image']), 0)}
  offset = 0
  for key, size in ACTION_DIM_LAYOUT:
    features['action/' + key] = actions[:, offset:offset + size]
    offset += size
  for key in ('gripper_closed', 'height_to_bottom'):
    features['action/' + key] = np.full(
        (num_samples, 1), np.float32(state[key]))
  return features


class GraspingQNetwork(nn.Module):
  """Feature-struct adapter around ``Grasping44Network``.

  Extracts the grasp image and concatenates the action features (in the
  reference's ``grasp_model_input_keys`` order, networks.py:637), handling
  the PREDICT-mode action megabatch (see module docstring).
  """

  grasp_param_keys: Tuple[str, ...] = networks.E2E_GRASP_PARAM_KEYS
  grasp_param_names: Optional[Dict[str, Tuple[int, int]]] = None
  dtype: jnp.dtype = jnp.float32
  network_kwargs: Optional[Dict[str, Any]] = None

  @nn.compact
  def __call__(self, features, mode: str = ModeKeys.TRAIN,
               train: bool = False):
    image = jnp.asarray(features['state/image'])
    grasp_params = jnp.concatenate(
        [jnp.asarray(features['action/' + key], jnp.float32).reshape(
            (jnp.asarray(features['action/' + key]).shape[0], -1))
         for key in self.grasp_param_keys], axis=-1)
    batch = image.shape[0]
    if grasp_params.shape[0] != batch:
      # CEM megabatch: N candidate actions per state arrive flat [B*A, d].
      grasp_params = grasp_params.reshape(
          (batch, -1, grasp_params.shape[-1]))
    endpoints = networks.Grasping44Network(
        grasp_param_names=self.grasp_param_names, dtype=self.dtype,
        name='grasping44', **(self.network_kwargs or {}))(
            image, grasp_params, train=train)
    q_predicted = endpoints['predictions']
    q_logits = endpoints['logits']
    if q_logits.ndim > 1 and q_logits.shape[-1] == 1:
      q_logits = jnp.squeeze(q_logits, -1)
    # Megabatch outputs [B, A] flatten back to the caller's [B*A] layout.
    outputs = SpecStruct(
        q_predicted=q_predicted.reshape((-1,)),
        q_logits=q_logits.reshape((-1,)))
    outputs['pool2'] = endpoints['pool2']
    outputs['final_conv'] = endpoints['final_conv']
    return outputs


class DefaultGrasping44ImagePreprocessor(SpecTransformationPreprocessor):
  """The default Grasping44 image preprocessor (ref t2r_models.py:246-312).

  On disk: 512x640 uint8 jpeg frames. For the model: 472x472 float32 in
  [0, 1], randomly cropped in TRAIN (center otherwise) with optional
  photometric distortions — which, like the reference's
  ApplyPhotometricImageDistortions defaults (image_transformations.py:182),
  are ALL OFF unless configured. Pure JAX on device; the crop runs on the
  uint8 frame so the float conversion and any distortions only touch the
  472x472 window (1.47x less elementwise work + HBM traffic than
  converting the full 512x640 frame first).
  """

  def __init__(self, *args, distortion_kwargs: Optional[dict] = None,
               use_fused_crop: Optional[bool] = None, **kwargs):
    """``distortion_kwargs`` forward to
    apply_photometric_image_distortions (e.g. {'random_brightness': True,
    'random_noise_level': 0.05}); default empty == reference defaults.

    ``use_fused_crop``: route the TRAIN crop+convert through the fused
    Pallas pass (``preprocessors/pallas_crop.py``) instead of the vmapped
    dynamic-slice + separate float convert. Numerics match the XLA path
    to 1 ulp with identical crop-offset sampling — but measured in the
    FULL batch-512 train step the kernel is ~3% SLOWER (183.6/180.3 ms
    f32/bf16-out vs 178.4 ms on an installation that is gone; ROADMAP D3)
    despite being 7.5x faster in isolation: XLA fuses the convert into
    neighboring ops and the opaque pallas_call re-introduces a fusion
    barrier + conv1-input relayout. Default (``None``) therefore resolves
    to OFF; the flag stays for pipelines where the crop is NOT adjacent
    to a large fusible program.
    """
    super().__init__(*args, **kwargs)
    self._distortion_kwargs = dict(distortion_kwargs or {})
    self._use_fused_crop = use_fused_crop

  def update_spec_transform(self, key: str, spec: TensorSpec,
                            mode: str) -> TensorSpec:
    del mode
    if key == 'state/image':
      return TensorSpec.from_spec(
          spec, shape=INPUT_SHAPE, dtype=np.uint8, data_format='jpeg')
    return spec

  def _preprocess_fn(self, features, labels, mode: str, rng=None):
    image = jnp.asarray(features['state/image'])
    if mode == ModeKeys.TRAIN:
      if rng is None:
        raise ValueError('TRAIN-mode preprocessing requires an rng key.')
      crop_rng, distort_rng = jax.random.split(jnp.asarray(rng))
      # Default OFF: measured slower inside the full step (see __init__).
      use_fused = bool(self._use_fused_crop) and (
          image.dtype == jnp.uint8 and pallas_crop.supported(image.shape))
      if use_fused:
        offsets = image_transformations.random_crop_offsets(
            crop_rng, image.shape[0], image.shape[1:3], TARGET_SHAPE)
        image = pallas_crop.fused_crop_convert(image, offsets, TARGET_SHAPE)
      else:
        image = image_transformations.random_crop_images(
            crop_rng, [image], TARGET_SHAPE)[0]
        image = jnp.asarray(image, jnp.float32) / 255.0
      if self._distortion_kwargs:
        image = image_transformations.apply_photometric_image_distortions(
            distort_rng, [image], **self._distortion_kwargs)[0]
    else:
      image = image_transformations.center_crop_images(
          [image], TARGET_SHAPE)[0]
      image = jnp.asarray(image, jnp.float32) / 255.0
    features['state/image'] = image
    return features, labels


class LegacyGraspingModelWrapper(CriticModel):
  """T2R wrapper around the Grasping44 network family (ref :66-243).

  Subclasses declare ``legacy_network_kwargs``/state/action specs; training
  uses the legacy optimizer stack (momentum + staircase exponential decay +
  parameter averaging) via ``optimizer_builder.build_opt``.
  """

  def __init__(self,
               loss_function: Optional[Callable] = None,
               learning_rate: float = 1e-4,
               model_weights_averaging: float = 0.9999,
               momentum: float = 0.9,
               export_batch_size: int = 1,
               use_avg_model_params: bool = True,
               learning_rate_decay_factor: float = 0.999,
               action_batch_size: Optional[int] = None,
               preprocessor_cls=DefaultGrasping44ImagePreprocessor,
               optimizer_override: Optional[Callable] = None,
               **kwargs):
    """Hparam defaults mirror ref t2r_models.py:69-102.

    ``optimizer_override``: zero-arg optax factory replacing the legacy
    momentum + staircase-decay stack (e.g. ``lambda: optax.adam(3e-3)``)
    — for workloads that are not reproducing the paper's 2018 training
    recipe, such as the off-policy convergence benchmark, where adaptive
    steps learn action-conditional rules ~an order of magnitude faster
    (measured in round 5).
    """
    self.hparams = optimizer_builder.default_hparams(
        learning_rate=learning_rate,
        learning_rate_decay_factor=learning_rate_decay_factor,
        model_weights_averaging=model_weights_averaging,
        momentum=momentum,
        use_avg_model_params=use_avg_model_params)
    self._loss_function = loss_function
    self._export_batch_size = export_batch_size
    self._network_kwargs = dict(kwargs.pop('network_kwargs', {}))
    super().__init__(
        action_batch_size=action_batch_size,
        preprocessor_cls=preprocessor_cls,
        create_optimizer_fn=(optimizer_override or
                             (lambda: optimizer_builder.build_opt(
                                 self.hparams))),
        use_avg_model_params=use_avg_model_params,
        avg_model_params_decay=model_weights_averaging,
        **kwargs)

  @property
  def legacy_network_kwargs(self) -> dict:
    """Constructor kwargs for Grasping44Network (ref legacy_model_class)."""
    return dict(self._network_kwargs)

  def get_label_specification(self, mode: str) -> SpecStruct:
    """ref :125-130 — grasp_success served as the 'reward' label."""
    del mode
    return SpecStruct(reward=TensorSpec(
        (1,), np.float32, name='grasp_success'))

  @property
  def l2_regularization_scale(self) -> float:
    return self.legacy_network_kwargs.get(
        'l2_regularization', networks.Grasping44Network.l2_regularization)

  def model_train_fn(self, variables, features, labels, inference_outputs,
                     mode: str):
    """Grasp cross-entropy + l2 weight decay (ref :233-243).

    The reference's tf.losses.get_total_loss() sums the log loss with slim's
    REGULARIZATION_LOSSES; here both terms are explicit.
    """
    q_logits = inference_outputs['q_logits']
    targets = jnp.asarray(labels[self.reward_key],
                          jnp.float32).reshape(q_logits.shape)
    if self._loss_function is not None:
      grasp_loss = self._loss_function(
          targets, inference_outputs[self.q_key])
    else:
      grasp_loss = jnp.mean(optax.sigmoid_binary_cross_entropy(
          q_logits.astype(jnp.float32), targets))
    l2_loss = networks.l2_regularization_loss(
        variables['params'], self.l2_regularization_scale)
    return grasp_loss + l2_loss, SpecStruct(grasp_loss=grasp_loss,
                                            l2_loss=l2_loss)

  def create_export_outputs_fn(self, features, inference_outputs, mode: str):
    del features, mode
    return SpecStruct(q_predicted=inference_outputs['q_predicted'],
                      q_logits=inference_outputs['q_logits'])

  def predict_step(self, state, features):
    """No state tiling: the network runs the action megabatch internally
    (image tower once per state; ref networks.py:520-527)."""
    return abstract_model.AbstractT2RModel.predict_step(self, state, features)


def _tile_scalar(value, num_samples: int):
  return jnp.broadcast_to(jnp.asarray(value, jnp.float32).reshape(1, 1),
                          (num_samples, 1))


class Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
    LegacyGraspingModelWrapper):
  """The QT-Opt flagship critic (ref :316-404).

  Controls gripper open/close/terminate with gripper status + height to
  bottom carried in the state-conditioned action vector. The grasp-param
  embedding uses the per-block dense layout of the reference E2E network
  (networks.py:736-744).
  """

  def get_state_specification(self) -> SpecStruct:
    """ref :336-339."""
    return SpecStruct(image=TensorSpec(
        TARGET_SHAPE + (3,), np.float32, name='image_1'))

  def get_action_specification(self) -> SpecStruct:
    """ref :341-364."""
    spec = SpecStruct()
    for key, size in ACTION_DIM_LAYOUT + (('gripper_closed', 1),
                                          ('height_to_bottom', 1)):
      spec[key] = TensorSpec((size,), np.float32, name=key)
    return spec

  def create_network(self) -> nn.Module:
    return GraspingQNetwork(
        grasp_param_keys=networks.E2E_GRASP_PARAM_KEYS,
        grasp_param_names=networks.E2E_GRASP_PARAM_NAMES,
        dtype=jnp.dtype(self.compute_dtype),
        network_kwargs=self.legacy_network_kwargs or None)

  def pack_features(self, *policy_inputs):
    """ref :399-400."""
    return pack_features_kuka_e2e(self, *policy_inputs)

  def make_on_device_select_action(self,
                                   cem_samples: int = 64,
                                   cem_iters: int = 3,
                                   num_elites: int = 10):
    """Builds the one-dispatch CEM action selector (DeviceCEMPolicy).

    The reference's CEM loop round-trips host<->device per iteration
    (policies.py:139-172: numpy CEM calling session.run 3x); here the
    ENTIRE loop — preprocessing, the lax.scan of CEM iterations, each
    scoring 64 candidates through the megabatch critic — is one jitted
    XLA program, so a robot action costs one dispatch and one image
    upload.

    Returns ``select(variables, state_dict, rng) -> (action [8], q)``
    with ``state_dict`` = {'image' uint8 [512, 640, 3], 'gripper_closed',
    'height_to_bottom'} and ``q`` the selected action's Q-value.
    """
    from tensor2robot_tpu.utils import cross_entropy

    def select(variables, state, rng):
      # Same serving semantics as every other path: EMA-averaged params
      # when configured (TrainState.variables), and the model's OWN
      # preprocessor for the predict-mode image transform.
      variables = dict(variables)
      avg_params = variables.pop('avg_params', None)
      if self.use_avg_model_params and avg_params is not None:
        variables['params'] = avg_params
      placeholder = SpecStruct()
      placeholder['state/image'] = jnp.asarray(state['image'])[None]
      for key, size in ACTION_DIM_LAYOUT:
        placeholder['action/' + key] = jnp.zeros((1, size), jnp.float32)
      for key in ('gripper_closed', 'height_to_bottom'):
        placeholder['action/' + key] = _tile_scalar(state[key], 1)
      processed, _ = self.preprocessor.preprocess(
          placeholder, None, ModeKeys.PREDICT, rng=None)
      image = processed['state/image']

      def objective(samples):
        features = SpecStruct()
        features['state/image'] = image
        offset = 0
        for key, size in ACTION_DIM_LAYOUT:
          features['action/' + key] = samples[:, offset:offset + size]
          offset += size
        for key in ('gripper_closed', 'height_to_bottom'):
          features['action/' + key] = _tile_scalar(state[key],
                                                   samples.shape[0])
        outputs, _ = self.inference_network_fn(
            variables, features, None, ModeKeys.PREDICT, None)
        return outputs['q_predicted']

      _, _, best = cross_entropy.jax_normal_cem(
          objective, jnp.zeros((CEM_ACTION_SIZE,), jnp.float32),
          jnp.ones((CEM_ACTION_SIZE,), jnp.float32), rng,
          num_samples=cem_samples, num_elites=num_elites,
          num_iterations=cem_iters)
      # The elite Q for per-step monitoring (run_env reads debug['q']).
      return best, objective(best[None])[0]

    return select

  def serving_feature_spec(self, image_shape=(512, 640, 3)):
    """Per-REQUEST feature contract for the serving layer (ISSUE 8).

    ``{name: (shape, dtype)}`` with no batch dim — what one
    ``SelectAction`` request carries and what ``PolicyServer`` validates
    and pads. ``image_shape`` is the RAW camera frame (the selector's
    own preprocessor crops to TARGET_SHAPE on device), so it is a
    deployment knob, not a model constant. ``bin/t2r_serve`` derives
    its spec and AOT shapes from this hook; any model exposing it plus
    ``make_batched_select_action`` serves through the generic path.
    """
    return {
        'image': (tuple(image_shape), np.uint8),
        'gripper_closed': ((), np.float32),
        'height_to_bottom': ((), np.float32),
    }

  def make_batched_select_action(self,
                                 cem_samples: int = 64,
                                 cem_iters: int = 3,
                                 num_elites: int = 10):
    """The serving megabatch program: B independent CEM selects, one
    dispatch (ISSUE 8).

    ``vmap`` of :meth:`make_on_device_select_action` over a leading
    state-batch dim — each row runs its own full CEM loop (its own
    ``cem_samples x cem_iters`` critic megabatch), so a PolicyServer
    batch of B coalesced robot requests is ONE XLA program scoring
    ``B * cem_samples`` candidates per iteration on the MXU.

    Returns ``batch_select(variables, states, seed) -> outputs`` with
    ``states`` = {'image' uint8 [B, 512, 640, 3], 'gripper_closed' [B],
    'height_to_bottom' [B]}, ``seed`` a uint32 scalar (each row gets
    ``fold_in(seed, row)``), and outputs {'action' [B, 8], 'q' [B]} —
    the (variables, features, seed) contract
    ``serving.PolicyServer`` batches through and
    ``serving.artifact.load_or_compile`` AOT-compiles.
    """
    import jax

    select = self.make_on_device_select_action(
        cem_samples=cem_samples, cem_iters=cem_iters,
        num_elites=num_elites)
    batched = jax.vmap(select, in_axes=(None, 0, 0))

    def batch_select(variables, states, seed):
      batch = jax.tree_util.tree_leaves(states)[0].shape[0]
      keys = jax.vmap(
          lambda i: jax.random.fold_in(jax.random.PRNGKey(seed), i))(
              jnp.arange(batch, dtype=jnp.uint32))
      actions, q = batched(variables, dict(states), keys)
      return {'action': actions, 'q': q}

    return batch_select
