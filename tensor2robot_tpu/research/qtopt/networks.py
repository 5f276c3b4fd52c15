"""QT-Opt grasping Q-networks (the Grasping44 PNN family).

Parity target: /root/reference/research/qtopt/networks.py:44-760
(GraspingModel, Grasping44FlexibleGraspParams :304, the E2E open/close/
terminate variant :623). The 19-layer conv architecture (NUM_LAYERS :35):

  conv1_1 64x6x6/2 -> bn(noscale) relu -> pool 3x3/3
  conv2..7 64x5x5 SAME (+bn relu) -> pool 3x3/3
  grasp params: per-block Dense 256 summed -> bn(noscale) relu
                -> Dense 64 (+bn relu) -> broadcast-add as [*,1,1,64] context
  conv8..13 64x3x3 SAME (+bn relu) -> pool 2x2/2
  conv14..16 64x3x3 VALID (+bn relu) -> flatten -> fc 64 x2 -> logit

TPU-first notes:
  * The CEM action-megabatch trick is preserved (ref :419-427, :520-527):
    with ``grasp_params`` of rank 3 [batch, action_batch, d], the image
    tower runs ONCE per state and only the embedding is tiled across the
    action batch — the MXU sees one large fused batch for the post-merge
    convs.
  * ``dtype`` selects the activations dtype (bfloat16 on TPU); the logit
    head and batch-norm statistics stay float32.
  * l2 regularization (ref slim weights_regularizer :438) is returned as
    an explicit ``l2_regularization_loss`` endpoint, added to the training
    loss by the model wrapper (the slim REGULARIZATION_LOSSES analog).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers.pooling import max_pool


NUM_LAYERS = 19
BATCH_SIZE = 64
# Action samples when estimating max_a Q(s, a) (ref :37-41).
NUM_SAMPLES = 100

# grasp_param block layout of the E2E variant (ref networks.py:736-744):
# name -> (offset, size) into the concatenated grasp params vector.
E2E_GRASP_PARAM_NAMES = {
    'fcgrasp_wv': (0, 3),
    'fcgrasp_vr': (3, 2),
    'fcgrasp_gripper_close': (5, 1),
    'fcgrasp_gripper_open': (6, 1),
    'fcgrasp_terminate_episode': (7, 1),
    'fcgrasp_gripper_closed': (8, 1),
    'fcgrasp_height_to_bottom': (9, 1),
}

# Concatenation order of action features (ref grasp_model_input_keys :637).
E2E_GRASP_PARAM_KEYS = (
    'world_vector', 'vertical_rotation', 'close_gripper', 'open_gripper',
    'terminate_episode', 'gripper_closed', 'height_to_bottom')


class _StemConv(nn.Module):
  """conv1_1 — 6x6/2 on [B, H, W, 3], bias kept for reference parity.

  Matches the reference stem exactly (ref networks.py:449-456:
  ``slim.conv2d(..., normalizer_fn=None)`` — so unlike every later conv
  this one HAS a bias). Two TPU notes:

  * In TRAIN mode the bias is applied through ``stop_gradient``: the
    following batch norm subtracts the batch mean, so the train loss is
    invariant to the bias and its true gradient is identically zero —
    but computing that zero costs a dead 1.8 GB reduction over the
    236x236 cotangent per step. The parameter still exists
    (checkpoint/parity) and still shifts the BN running statistics
    exactly as in the reference. With ``train=False`` (frozen-stats
    fine-tuning) the invariance does NOT hold — the bias gradient flows
    normally there.
  * ``packed=True`` computes the strided conv as 3x3/1 on the
    2x2-space-to-depth grid — every output is the same dot product over
    the same 108 inputs. Default OFF: on v5e, XLA's strided conv emitter
    beats the packed form (measured 3.4 ms vs 4.6 ms at batch 256 even
    with the packing relayout excluded); the option is kept, tested, for
    generations where it wins.
  """

  packed: bool = False
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x, train: bool = False):
    kernel = self.param('kernel',
                        nn.initializers.truncated_normal(stddev=0.01),
                        (6, 6, 3, 64), jnp.float32)
    bias = self.param('bias', nn.initializers.zeros, (64,), jnp.float32)
    b, h, w, c = x.shape
    x = jnp.asarray(x, self.dtype)
    if self.packed and h % 2 == 0 and w % 2 == 0:
      # [B, H, W, 3] -> [B, H/2, W/2, 12] with channel order (p, q, ch).
      xp = x.reshape(b, h // 2, 2, w // 2, 2, c)
      xp = xp.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
      # kernel[2a+p, 2b+q, ch, co] -> packed[a, b, (p, q, ch), co].
      kp = jnp.asarray(kernel, self.dtype).reshape(3, 2, 3, 2, c, 64)
      kp = kp.transpose(0, 2, 1, 3, 4, 5).reshape(3, 3, 4 * c, 64)
      # SAME for 6x6/2 on even H pads (2, 2); on the packed grid: (1, 1).
      out = jax.lax.conv_general_dilated(
          xp, kp, (1, 1), ((1, 1), (1, 1)),
          dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
          preferred_element_type=self.dtype)
    else:
      out = jax.lax.conv_general_dilated(
          x, jnp.asarray(kernel, self.dtype), (2, 2), 'SAME',
          dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
          preferred_element_type=self.dtype)
    bias = jnp.asarray(bias, self.dtype)
    return out + (jax.lax.stop_gradient(bias) if train else bias)


class _LayoutConv(nn.Module):
  """A body conv computed under NCHW/OIHW ``dimension_numbers``.

  Checkpoint-compatible with ``nn.Conv(use_bias=False)``: the parameter
  is the same ``kernel`` of shape [k, k, in, out] with the same init —
  only the CONV COMPUTATION runs through
  ``dimension_numbers=('NCHW', 'OIHW', 'NCHW')`` (operand/kernel
  transposed in-trace, result transposed back). Numerically this is the
  same contraction in a different loop order; its point is to hand XLA's
  layout assignment a different starting layout, one of the compile-
  config autotuner's sweepable variants (tuning/search_space.py
  'conv-nchw'). On the autotuner's sweep the transposes either fuse away
  (and the variant measures what the layout is worth) or they don't (and
  the candidate loses honestly).
  """

  features: int
  kernel_size: int
  stride: int = 1
  padding: str = 'SAME'
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x):
    k = self.kernel_size
    kernel = self.param('kernel',
                        nn.initializers.truncated_normal(stddev=0.01),
                        (k, k, x.shape[-1], self.features), jnp.float32)
    x = jnp.asarray(x, self.dtype).transpose(0, 3, 1, 2)
    kernel = jnp.asarray(kernel, self.dtype).transpose(3, 2, 0, 1)
    out = jax.lax.conv_general_dilated(
        x, kernel, (self.stride, self.stride), self.padding,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
        preferred_element_type=self.dtype)
    return out.transpose(0, 2, 3, 1)


class _PrePoolStatsBatchNorm(nn.Module):
  """No-scale BatchNorm whose TRAIN statistics come from the pre-pool map.

  Grasping44's first block is conv1 -> bn1(no scale) -> relu -> maxpool.
  Normalize-then-relu is a per-channel NON-DECREASING map, so it commutes
  exactly with max pooling; evaluating it AFTER the pool touches the
  79x79 map instead of the 236x236 one (8.9x less elementwise/HBM work)
  while the batch statistics are still computed over the full pre-pool
  tensor — bit-identical outputs and running stats. Parameter and
  batch_stats trees match ``nn.BatchNorm(use_scale=False)``.
  """

  momentum: float = 0.9997
  epsilon: float = 0.001
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, pre_pool, pooled, train: bool):
    features = (pre_pool.shape[-1],)
    ra_mean = self.variable('batch_stats', 'mean',
                            lambda: jnp.zeros(features, jnp.float32))
    ra_var = self.variable('batch_stats', 'var',
                           lambda: jnp.ones(features, jnp.float32))
    bias = self.param('bias', nn.initializers.zeros, features, jnp.float32)
    if train:
      xf = jnp.asarray(pre_pool, jnp.float32)
      axes = tuple(range(pre_pool.ndim - 1))
      mean = jnp.mean(xf, axis=axes)
      var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean)
      if not self.is_initializing():
        ra_mean.value = (self.momentum * ra_mean.value +
                         (1.0 - self.momentum) * mean)
        ra_var.value = (self.momentum * ra_var.value +
                        (1.0 - self.momentum) * var)
    else:
      mean, var = ra_mean.value, ra_var.value
      # Same eval-mode fusion pathology guard as Grasping44Network._bn.
      pooled = jax.lax.optimization_barrier(pooled)
    # Same arithmetic flax's BatchNorm applies: operands cast to the
    # module dtype first, normalize computed in that dtype.
    x = jnp.asarray(pooled, self.dtype)
    mul = jax.lax.rsqrt(jnp.asarray(var, self.dtype) +
                        jnp.asarray(self.epsilon, self.dtype))
    return ((x - jnp.asarray(mean, self.dtype)) * mul +
            jnp.asarray(bias, self.dtype))


class Grasping44Network(nn.Module):
  """The Grasping44 Q-network (ref Grasping44FlexibleGraspParams :304)."""

  num_classes: int = 1
  num_convs: Sequence[int] = (6, 6, 3)
  hid_layers: int = 2
  batch_norm_decay: float = 0.9997
  batch_norm_epsilon: float = 0.001
  l2_regularization: float = 0.00007
  grasp_param_names: Optional[Dict[str, Tuple[int, int]]] = None
  softmax: bool = False
  dtype: jnp.dtype = jnp.float32
  # Optional exact space-to-depth rewrite of the stem conv; see
  # _StemConv for the trade-off measurements.
  space_to_depth: bool = False
  # Body-conv dimension_numbers/layout variant: 'nhwc' (stock nn.Conv)
  # or 'nchw' (_LayoutConv — same params, NCHW/OIHW compute). Sweepable
  # by the compile-config autotuner (tuning/search_space.py).
  conv_variant: str = 'nhwc'

  def _conv(self, features, kernel, stride, padding, name):
    # BN-normalized convs carry NO bias, exactly like slim.conv2d under
    # the reference's normalizer_fn=batch_norm arg_scope (ref :441-446).
    if self.conv_variant == 'nchw':
      return _LayoutConv(features=features, kernel_size=kernel,
                         stride=stride, padding=padding, dtype=self.dtype,
                         name=name)
    if self.conv_variant != 'nhwc':
      raise ValueError(
          "conv_variant must be 'nhwc' or 'nchw'; got {!r}.".format(
              self.conv_variant))
    return nn.Conv(
        features=features, kernel_size=(kernel, kernel),
        strides=(stride, stride), padding=padding, use_bias=False,
        kernel_init=nn.initializers.truncated_normal(stddev=0.01),
        dtype=self.dtype, name=name)

  def _dense(self, features, name, use_bias=True):
    # use_bias=False for the BN-normalized denses (fcgrasp2, fc0/fc1 —
    # same slim arg_scope rule); the per-block grasp-param denses and
    # the logit head keep theirs (ref :497-503, :575-581).
    return nn.Dense(
        features, use_bias=use_bias,
        kernel_init=nn.initializers.truncated_normal(stddev=0.01),
        dtype=self.dtype, name=name)

  def _bn(self, net, train, scale, name):
    if not train:
      # Keep XLA from fusing the eval-mode (running-stat) normalize INTO
      # the producing conv: on v5e that demotes the 5x5 convs from the
      # native conv emitter to a loop fusion — measured 98 ms -> 33 ms
      # for the full eval forward at batch 256 with this barrier. The
      # barrier is the identity; numerics are untouched.
      net = jax.lax.optimization_barrier(net)
    return nn.BatchNorm(
        use_running_average=not train, momentum=self.batch_norm_decay,
        epsilon=self.batch_norm_epsilon, use_scale=scale,
        dtype=self.dtype, name=name)(net)

  @nn.compact
  def __call__(self, image, grasp_params, train: bool = False):
    """Args:
      image: [batch, H, W, 3] grasp image (472x472 nominal).
      grasp_params: [batch, d] or [batch, action_batch, d] (CEM megabatch).
      train: batch-norm mode.

    Returns:
      endpoints dict with 'logits', 'predictions' (sigmoid/softmax, shaped
      [batch, action_batch] in megabatch mode), 'pool2', 'final_conv'.
      Weight decay is NOT an endpoint: compute it from the params pytree
      with the module-level ``l2_regularization_loss(params, scale)``.
    """
    endpoints = {}
    tile_batch = grasp_params.ndim == 3
    action_batch_size = grasp_params.shape[1] if tile_batch else 1
    if tile_batch:
      grasp_params = grasp_params.reshape((-1, grasp_params.shape[-1]))

    net = jnp.asarray(image, self.dtype)
    net = _StemConv(packed=self.space_to_depth, dtype=self.dtype,
                    name='conv1_1')(net, train=train)
    # Pool the RAW conv output; normalize+relu (a non-decreasing
    # per-channel map — bn1 has no scale) on the 8.9x smaller pooled map
    # with statistics still taken over the full pre-pool tensor.
    pooled = max_pool(net, (3, 3), strides=(3, 3), padding='SAME')
    net = nn.relu(_PrePoolStatsBatchNorm(
        momentum=self.batch_norm_decay, epsilon=self.batch_norm_epsilon,
        dtype=self.dtype, name='bn1')(net, pooled, train))
    layer = 2
    for _ in range(self.num_convs[0]):
      net = self._conv(64, 5, 1, 'SAME', 'conv{}'.format(layer))(net)
      net = self._bn(net, train, True, 'bn{}'.format(layer))
      net = nn.relu(net)
      layer += 1
    net = max_pool(net, (3, 3), strides=(3, 3), padding='SAME')
    endpoints['pool2'] = net

    grasp_params = jnp.asarray(grasp_params, self.dtype)
    if self.grasp_param_names is None:
      blocks = [('fcgrasp', grasp_params)]
    else:
      # Sorted for deterministic parameter creation (ref :482-486).
      blocks = [
          (name, grasp_params[:, offset:offset + size])
          for name, (offset, size) in sorted(self.grasp_param_names.items())
      ]
    fcgrasp = sum(self._dense(256, name)(block) for name, block in blocks)
    fcgrasp = nn.relu(self._bn(fcgrasp, train, scale=False, name='bngrasp'))
    fcgrasp = self._dense(64, 'fcgrasp2', use_bias=False)(fcgrasp)
    fcgrasp = nn.relu(self._bn(fcgrasp, train, True, 'bngrasp2'))
    endpoints['fcgrasp'] = fcgrasp
    context = fcgrasp.reshape((-1, 1, 1, 64))

    if tile_batch:
      # Tile the IMAGE EMBEDDING (not the raw image) across the action
      # batch: [B, h, w, c] -> [B * action_batch, h, w, c] with each
      # state's block contiguous (ref contrib_seq2seq.tile_batch :526).
      net = jnp.repeat(net, action_batch_size, axis=0)
    net = net + context
    endpoints['vsum'] = net

    for _ in range(self.num_convs[1]):
      net = self._conv(64, 3, 1, 'SAME', 'conv{}'.format(layer))(net)
      net = self._bn(net, train, True, 'bn{}'.format(layer))
      net = nn.relu(net)
      layer += 1
    net = max_pool(net, (2, 2), strides=(2, 2), padding='SAME')
    for _ in range(self.num_convs[2]):
      net = self._conv(64, 3, 1, 'VALID', 'conv{}'.format(layer))(net)
      net = self._bn(net, train, True, 'bn{}'.format(layer))
      net = nn.relu(net)
      layer += 1
    endpoints['final_conv'] = net

    net = net.reshape((net.shape[0], -1))
    for l in range(self.hid_layers):
      net = self._dense(64, 'fc{}'.format(l), use_bias=False)(net)
      net = self._bn(net, train, True, 'bnfc{}'.format(l))
      net = nn.relu(net)
    name = 'logit' if self.num_classes == 1 else 'logit_{}'.format(
        self.num_classes)
    logits = nn.Dense(
        self.num_classes,
        kernel_init=nn.initializers.truncated_normal(stddev=0.01),
        dtype=jnp.float32, name=name)(jnp.asarray(net, jnp.float32))
    endpoints['logits'] = logits
    predictions = (nn.softmax(logits) if self.softmax
                   else nn.sigmoid(logits))
    if tile_batch:
      new_shape = ((-1, action_batch_size) if self.num_classes == 1 else
                   (-1, action_batch_size, self.num_classes))
      predictions = predictions.reshape(new_shape)
      logits = logits.reshape(new_shape)
      endpoints['logits'] = logits
    elif self.num_classes == 1:
      predictions = jnp.squeeze(predictions, -1)
    endpoints['predictions'] = predictions
    return endpoints


def l2_regularization_loss(params, scale: float) -> jnp.ndarray:
  """slim REGULARIZATION_LOSSES analog: ``scale * sum ||kernel||^2 / 2``.

  Applied to conv/dense kernels only (slim regularizes weights, not biases
  or batch-norm params; ref arg_scope :438).
  """
  import jax

  total = 0.0
  for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
    if str(getattr(path[-1], 'key', '')) == 'kernel':
      total = total + jnp.sum(jnp.square(jnp.asarray(leaf, jnp.float32)))
  return scale * 0.5 * total
