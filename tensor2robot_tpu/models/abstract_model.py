"""AbstractT2RModel: the user-facing model API, pure-functional for JAX.

Parity target: /root/reference/models/abstract_model.py:154-919 (the
Estimator-era template-method model). The TF1 responsibilities map as:

  reference model_fn (EstimatorSpec assembly :651-823)  -> trainer composes
      the pure fns below into one jitted train/eval/predict step
  create_train_op + optimizer creation (:327-370,:836)  -> create_optimizer()
      returning an optax chain; gradient psum is inserted by pjit sharding
  TPUT2RModelWrapper bf16 casts (tpu_model_wrapper.py)  -> deleted by
      construction: bf16 is first-class; models read self.compute_dtype
  MovingAverageOptimizer + swapping saver (:836-844)    -> optax.ema tracked
      in TrainState.avg_params; eval/serving read averaged params
  maybe_init_from_checkpoint warm start (:88-118,:372)  -> warm_start_fn
      merging a restored params subtree before training

Models hold *configuration only*. Parameters, mutable collections
(batch stats), optimizer slots, and the EMA live in :class:`TrainState`,
a pytree owned by the trainer and sharded over the mesh.

Subclasses implement either:
  * ``create_network() -> flax.linen.Module`` whose ``__call__(features,
    mode, train)`` returns an outputs dict — init/inference defaults then
    just work; or
  * ``init_variables`` + ``inference_network_fn`` directly for full control.
plus ``model_train_fn`` (the loss).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensor2robot_tpu.models import optimizers as opt_lib
from tensor2robot_tpu.models.model_interface import ModelInterface
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.preprocessors.abstract_preprocessor import (
    AbstractPreprocessor,
)
from tensor2robot_tpu.preprocessors.noop_preprocessor import NoOpPreprocessor
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import bfloat16


class TrainState(flax.struct.PyTreeNode):
  """All mutable training state, as one shardable pytree."""

  step: jnp.ndarray
  params: Any
  model_state: Any          # non-param collections (batch_stats, ...)
  opt_state: Any
  avg_params: Any = None    # EMA of params (use_avg_model_params)
  ema_state: Any = None

  def variables(self, use_avg_params: bool = False):
    params = self.avg_params if (use_avg_params and
                                 self.avg_params is not None) else self.params
    return {'params': params, **(self.model_state or {})}


def gradient_norms(grads):
  """{'grad_norm': global norm, 'grad_group_norm/<name>': norm of the subtree under
  each top-level key of ``grads``}, every leaf read once."""
  squares = {
      name: sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                for leaf in jax.tree.leaves(subtree))
      for name, subtree in grads.items()}
  norms = {'grad_group_norm/' + name: jnp.sqrt(value)
           for name, value in squares.items()}
  norms['grad_norm'] = jnp.sqrt(sum(squares.values()))
  return norms


class AbstractT2RModel(ModelInterface):
  """Base model: spec declarations + pure network/loss/metric functions."""

  # A model that sets this reports the norm of each step's gradient as
  # scalar step metrics: ``grad_norm`` (global) and ``grad_group_norm/<name>`` for
  # every top-level entry of the parameter tree, so that a check can tell
  # WHICH part's gradient is off; the others compute nothing.
  report_gradient_norm = False
  # Names of scalar step metrics whose values the trainer's step watcher
  # writes into each ``train.step_done`` event of the span ring (read on
  # the watcher's thread once the step has finished on the device).
  traced_step_metrics = ()

  def __init__(self,
               preprocessor_cls: Optional[Callable[..., AbstractPreprocessor]] = None,
               create_optimizer_fn: Callable[[], Any] = opt_lib.create_adam_optimizer,
               device_type: str = 'tpu',
               use_avg_model_params: bool = False,
               avg_model_params_decay: float = 0.9999,
               gradient_clip_norm: Optional[float] = None,
               warm_start_fn: Optional[Callable[[Any], Any]] = None,
               compute_dtype=None):
    """See class docstring.

    Args:
      preprocessor_cls: class constructed with the model's spec fns
        (ref abstract_model.py:255 — default NoOp).
      create_optimizer_fn: zero-arg factory returning an optax
        GradientTransformation (ref optimizer gin-injection :836).
      device_type: 'cpu' | 'gpu' | 'tpu' (ref :66-68).
      use_avg_model_params: serve/eval exponentially-averaged params
        (ref :836-844).
      avg_model_params_decay: EMA decay.
      gradient_clip_norm: optional global-norm clip (ref create_train_op).
      warm_start_fn: params -> params, merging restored values
        (ref maybe_init_from_checkpoint :372).
      compute_dtype: activations dtype for networks that honor it
        (default bfloat16 on TPU — the tpu_model_wrapper replacement).
    """
    self._preprocessor_cls = preprocessor_cls
    self._preprocessor: Optional[AbstractPreprocessor] = None
    self._create_optimizer_fn = create_optimizer_fn
    self._device_type = device_type
    self.use_avg_model_params = use_avg_model_params
    self.avg_model_params_decay = avg_model_params_decay
    self.gradient_clip_norm = gradient_clip_norm
    self._warm_start_fn = warm_start_fn
    if compute_dtype is None:
      compute_dtype = bfloat16 if device_type == 'tpu' else np.float32
    self.compute_dtype = compute_dtype

  # -- preprocessor ---------------------------------------------------------

  @property
  def preprocessor(self) -> AbstractPreprocessor:
    if self._preprocessor is None:
      cls = self._preprocessor_cls or NoOpPreprocessor
      self._preprocessor = cls(self.get_feature_specification,
                               self.get_label_specification)
    return self._preprocessor

  def set_preprocessor(self, preprocessor: AbstractPreprocessor) -> None:
    """Installs a (wrapped) preprocessor, e.g. the bf16 TPU wrapper."""
    self._preprocessor = preprocessor

  @property
  def warm_start_fn(self):
    return self._warm_start_fn

  @property
  def device_type(self) -> str:
    return self._device_type

  # -- network --------------------------------------------------------------

  def create_network(self) -> nn.Module:
    """Returns the flax module backing the default init/inference fns."""
    raise NotImplementedError(
        '{} must implement create_network() or override init_variables/'
        'inference_network_fn.'.format(type(self).__name__))

  def init_variables(self, rng, features, labels=None,
                     mode: str = ModeKeys.TRAIN):
    """Default: flax init through create_network (ref variable creation)."""
    del labels
    network = self.create_network()
    param_rng, dropout_rng = jax.random.split(rng)
    variables = network.init(
        {'params': param_rng, 'dropout': dropout_rng}, features, mode=mode,
        train=(mode == ModeKeys.TRAIN))
    variables = flax.core.unfreeze(variables)
    if self._warm_start_fn is not None and not any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(variables)):
      # Warm start does real checkpoint I/O; only run it on concrete values.
      # Under jit/eval_shape the trainer is responsible for applying it
      # eagerly exactly once (Trainer.init_state), never inside a trace
      # where the restored weights would be baked in as XLA constants.
      variables['params'] = self._warm_start_fn(variables['params'])
    return variables

  def inference_network_fn(self, variables, features, labels=None,
                           mode: str = ModeKeys.TRAIN, rng=None):
    """Default: flax apply; train mode updates batch stats.

    Returns (outputs, updated_model_state). ``updated_model_state`` is None
    outside train mode (nothing mutates).
    """
    del labels
    network = self.create_network()
    train = mode == ModeKeys.TRAIN
    rngs = {'dropout': rng} if rng is not None else None
    mutable = [k for k in variables if k != 'params'] if train else False
    if mutable:
      outputs, new_state = network.apply(
          variables, features, mode=mode, train=train, rngs=rngs,
          mutable=mutable)
      return outputs, flax.core.unfreeze(new_state)
    outputs = network.apply(variables, features, mode=mode, train=train,
                            rngs=rngs)
    return outputs, None

  # -- loss / metrics -------------------------------------------------------

  def model_train_fn(self, variables, features, labels, inference_outputs,
                     mode: str):
    raise NotImplementedError(
        '{} must implement model_train_fn.'.format(type(self).__name__))

  def model_eval_fn(self, variables, features, labels, inference_outputs,
                    mode: str) -> SpecStruct:
    """Default: the train loss as an eval metric (ref model_eval_fn :495)."""
    loss, _ = self.model_train_fn(variables, features, labels,
                                  inference_outputs, mode)
    return SpecStruct(loss=loss)

  # -- optimizer / state ----------------------------------------------------

  def create_optimizer(self):
    """optax chain per config (ref create_optimizer :836, clip :327)."""
    return opt_lib.maybe_clip_gradients(self._create_optimizer_fn(),
                                        self.gradient_clip_norm)

  def create_train_state(self, rng, features, labels=None,
                         mode: str = ModeKeys.TRAIN) -> TrainState:
    """Initializes variables + optimizer (+EMA) into one TrainState."""
    variables = self.init_variables(rng, features, labels, mode)
    params = variables.pop('params')
    model_state = variables
    optimizer = self.create_optimizer()
    opt_state = optimizer.init(params)
    avg_params = ema_state = None
    if self.use_avg_model_params:
      ema = opt_lib.create_ema(self.avg_model_params_decay)
      ema_state = ema.init(params)
      avg_params = params
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      model_state=model_state, opt_state=opt_state,
                      avg_params=avg_params, ema_state=ema_state)

  # -- pure step functions (composed & jitted by the trainer) ---------------

  def loss_fn(self, params, model_state, features, labels, mode, rng):
    variables = {'params': params, **(model_state or {})}
    outputs, new_model_state = self.inference_network_fn(
        variables, features, labels, mode, rng)
    loss, train_outputs = self.model_train_fn(
        variables, features, labels, outputs, mode)
    return loss, (train_outputs, outputs, new_model_state)

  def train_step(self, state: TrainState, features, labels, rng
                 ) -> Tuple[TrainState, SpecStruct]:
    """One SGD step. Pure; jit/pjit-sharded by the trainer.

    Under pjit with batch sharded over the mesh 'data' axis, the gradient
    all-reduce (the reference's CrossShardOptimizer, tpu_model_wrapper.py:50)
    is inserted automatically by XLA as a psum over ICI.
    """
    prng, _ = jax.random.split(rng)
    grad_fn = jax.value_and_grad(self.loss_fn, has_aux=True)
    (loss, (train_outputs, _, new_model_state)), grads = grad_fn(
        state.params, state.model_state, features, labels, ModeKeys.TRAIN,
        prng)
    optimizer = self.create_optimizer()
    updates, new_opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
    new_params = optax.apply_updates(state.params, updates)
    avg_params, ema_state = state.avg_params, state.ema_state
    if self.use_avg_model_params:
      ema = opt_lib.create_ema(self.avg_model_params_decay)
      avg_params, ema_state = ema.update(new_params, state.ema_state)
    metrics = SpecStruct(loss=loss)
    if self.report_gradient_norm:
      metrics.update(gradient_norms(grads))
    if isinstance(train_outputs, (dict, SpecStruct)):
      for key in train_outputs:
        value = train_outputs[key]
        if hasattr(value, 'ndim') and value.ndim == 0:
          metrics[key] = value
    new_state = state.replace(
        step=state.step + 1, params=new_params,
        model_state=new_model_state if new_model_state is not None
        else state.model_state,
        opt_state=new_opt_state, avg_params=avg_params, ema_state=ema_state)
    return new_state, metrics

  def eval_step(self, state: TrainState, features, labels) -> SpecStruct:
    """Per-batch eval metrics (averaged across batches by the harness)."""
    variables = state.variables(use_avg_params=self.use_avg_model_params)
    outputs, _ = self.inference_network_fn(variables, features, labels,
                                           ModeKeys.EVAL, None)
    return self.model_eval_fn(variables, features, labels, outputs,
                              ModeKeys.EVAL)

  def predict_step(self, state: TrainState, features) -> SpecStruct:
    """Serving forward pass -> export outputs (ref create_export_outputs_fn)."""
    variables = state.variables(use_avg_params=self.use_avg_model_params)
    outputs, _ = self.inference_network_fn(variables, features, None,
                                           ModeKeys.PREDICT, None)
    return self.create_export_outputs_fn(features, outputs, ModeKeys.PREDICT)
