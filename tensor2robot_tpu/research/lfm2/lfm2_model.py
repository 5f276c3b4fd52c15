"""LFM2-style language backbone, trained by next-token prediction.

The architecture of LiquidAI's LFM2-8B-A1B (its public ``config.json``,
``model_type`` ``lfm2_moe``; README.md beside this file has the equations,
every assumption and every departure): a stack of pre-norm blocks whose
token mixer is, by ``layer_types``, a GATED SHORT CONVOLUTION (``conv``: two
multiplicative gates around a depthwise causal filter of three taps, no
positions, no attention) or grouped-query attention (``full_attention``:
q/k norm, rotary positions, causal); whose feed-forward is one dense SwiGLU
in the ``num_dense_layers`` leading layers and routed SwiGLU experts after
them; whose router scores every expert with a SIGMOID, chooses by score +
a BIAS that no gradient trains (auxiliary-loss-free balancing: after each
training step every layer's bias moves by a fixed rate towards the experts
that got too little) and weighs by the scores alone, renormalised; RMS
norms; ONE matrix as embedding and head (tied).

All of it is ``layers/transformer.py::MoEBlock`` with its mixer, its
feed-forward and its router as fields; the convolution's core is the Pallas
kernel pair of ``parallel/short_conv.py``. The bias is the model's only
state the optimizer does not own: it lives in the ``router_state``
collection, travels in ``TrainState.model_state`` and comes back from the
train step updated.

The model can hold one chip's SHARE of an expert-parallel, vocabulary-split
deployment, as ``research/smallthinker`` does: ``experts_held`` (first
index, count) of the ``num_experts`` the router scores and the first
``vocab_rows`` rows of the embedding. A block is under ``jax.checkpoint``
with that model's policy: the residual stream and what the attention
backward kernels read are kept (a convolution block keeps the stream
alone), the rest is computed again.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.models import optimizers as opt_lib
from tensor2robot_tpu.models.abstract_model import AbstractT2RModel
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.research.smallthinker.smallthinker_model import (
    CheckpointedBlock,
    next_token_loss,
)
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec

LAYER_TYPES = {'conv': 'short_conv', 'full_attention': 'attention'}
STEP_METRICS = ('moe/chosen_load_max_over_mean', 'moe/router_bias_abs_mean',
                'moe/pairs_held', 'moe/expert_load_max_over_mean',
                'moe/dropped_pairs', 'moe/rows_in_use')
# The LFM2 family's published pattern: 18 convolution and 6 attention layers.
PUBLISHED_LAYER_TYPES = tuple(
    'full_attention' if layer in (2, 6, 10, 14, 18, 21) else 'conv'
    for layer in range(24))


class LFM2Net(nn.Module):
  """tokens [B, L] int32 -> {'loss', the expert layers' stats} (and
  ``last_logits`` [B, V] when predicting)."""

  hidden_size: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  dense_dim: int
  expert_dim: int
  num_experts: int
  experts_held: Tuple[int, int]
  top_k: int
  mixers: Tuple[str, ...]           # per layer: 'short_conv' | 'attention'
  num_dense_layers: int
  rope_theta: float
  eps: float
  vocab_rows: int
  router_bias_rate: float = 1e-3
  loss_block_tokens: int = 2048
  moe_block_rows: int = 256
  embedding_init_std: float = 0.02
  residual_init_std: float = 0.02
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, features, mode: str = ModeKeys.TRAIN,
               train: bool = False):
    del train  # no dropout; the router's bias moves where it is mutable
    tokens = features['tokens']
    embedding = self.param(
        'embedding', nn.initializers.normal(self.embedding_init_std),
        (self.vocab_rows, self.hidden_size), jnp.float32)
    x = jnp.take(embedding, tokens, axis=0).astype(self.dtype)
    stats = []
    for layer, mixer in enumerate(self.mixers):
      x, layer_stats = CheckpointedBlock(
          num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
          head_dim=self.head_dim, num_experts=self.num_experts,
          experts_held=tuple(self.experts_held), expert_dim=self.expert_dim,
          top_k=self.top_k, rope_theta=self.rope_theta, eps=self.eps,
          mixer=mixer, feed_forward='dense' if layer < self.num_dense_layers
          else 'experts', dense_dim=self.dense_dim, router_reads='normed',
          router='sigmoid_bias', router_bias_rate=self.router_bias_rate,
          qk_norm=True, gate_activation='silu',
          moe_block_rows=self.moe_block_rows,
          residual_init_std=self.residual_init_std, dtype=self.dtype,
          name='block{}'.format(layer))(x)
      if layer_stats:
        stats.append(layer_stats)
    hidden = transformer_lib.RMSNorm(self.eps, name='norm_final')(x)
    total = lambda name: sum((s[name] for s in stats), jnp.float32(0))
    mean = lambda name: total(name) / max(len(stats), 1)
    outputs = {
        # Tied: the head is the embedding, transposed; its gradient is the
        # sum over both uses.
        'loss': next_token_loss(hidden, embedding.T, tokens,
                                self.loss_block_tokens, self.dtype),
        'moe/router_bias_abs_mean': mean('router_bias_abs_mean'),
        'moe/chosen_load_max_over_mean': mean('chosen_load_max_over_mean'),
        'moe/pairs_held': total('pairs_held'),
        'moe/expert_load_max_over_mean': mean('load_max_over_mean'),
        'moe/dropped_pairs': total('dropped_pairs'),
        'moe/rows_in_use': total('rows_in_use'),
    }
    if mode == ModeKeys.PREDICT:
      outputs['last_logits'] = jnp.dot(
          hidden[:, -1].astype(self.dtype), embedding.T.astype(self.dtype),
          preferred_element_type=jnp.float32)
    return outputs


class LFM2Model(AbstractT2RModel):
  """The network above as a T2R model: spec ``tokens`` int32 [L], no labels
  (the targets are the tokens shifted by one, inside the model).

  The keyword names are the public config's where it has one.
  ``layer_types`` may be longer than ``num_hidden_layers`` (the config's is
  24 long); ``first_layer`` says which published layer is this model's
  first, the ``num_hidden_layers`` from there are built, and
  ``num_dense_layers`` counts the dense ones AMONG THEM. ``experts_held``
  (first, count) and ``vocab_rows`` say what this chip holds.
  ``router_bias_rate`` is the balancing rule's step (the config has no key
  for it). Initialisation and ``learning_rate`` as ``SmallThinkerModel``'s:
  every matrix normal(0.02) unless ``embedding_init_std`` or
  ``residual_init_layers`` (N: the mixers' output projections, the dense
  ``w2`` and the experts' ``w_down`` start at 0.02 / sqrt(2 N)) say
  otherwise; a convolution's taps start uniform on +-1/sqrt(3).

  ``traced_step_metrics``: the step metric the trainer's step watcher
  writes into the ``train.step_done`` event of the span ring (what the
  bias balances; the bias's own magnitude grows with the steps run and
  goes to the training log with the other step metrics)."""

  report_gradient_norm = True
  traced_step_metrics = STEP_METRICS[:1]

  def __init__(self,
               hidden_size: int = 2048,
               num_attention_heads: int = 32,
               num_key_value_heads: int = 8,
               head_dim: Optional[int] = None,
               intermediate_size: int = 7168,
               moe_intermediate_size: int = 1792,
               num_experts: int = 32,
               experts_held: Optional[Sequence[int]] = None,
               num_experts_per_tok: int = 4,
               num_hidden_layers: int = 24,
               num_dense_layers: int = 2,
               layer_types: Sequence[str] = PUBLISHED_LAYER_TYPES,
               first_layer: int = 0,
               conv_L_cache: int = 3,
               conv_bias: bool = False,
               norm_topk_prob: bool = True,
               use_expert_bias: bool = True,
               routed_scaling_factor: float = 1.0,
               rope_theta: float = 1e6,
               norm_eps: float = 1e-5,
               vocab_rows: int = 65536,
               sequence_length: int = 8192,
               router_bias_rate: float = 1e-3,
               loss_block_tokens: int = 2048,
               moe_block_rows: int = 256,
               embedding_init_std: float = 0.02,
               residual_init_layers: Optional[int] = None,
               learning_rate: float = 1e-4,
               **kwargs):
    kwargs.setdefault('create_optimizer_fn', functools.partial(
        opt_lib.create_adam_optimizer, learning_rate))
    super().__init__(**kwargs)
    if (conv_L_cache != 3 or conv_bias or routed_scaling_factor != 1 or
        not (norm_topk_prob and use_expert_bias)):
      raise ValueError(
          'only the published form is built: conv_L_cache 3, conv_bias '
          'false, norm_topk_prob and use_expert_bias true, '
          'routed_scaling_factor 1.')
    kinds = tuple(layer_types[first_layer:first_layer + num_hidden_layers])
    if len(kinds) < num_hidden_layers or set(kinds) - set(LAYER_TYPES):
      raise ValueError(
          'layer_types {} do not name {} layers of {} from layer {} on.'
          .format(tuple(layer_types), num_hidden_layers, sorted(LAYER_TYPES),
                  first_layer))
    self._net_kwargs = dict(
        hidden_size=hidden_size, num_heads=num_attention_heads,
        num_kv_heads=num_key_value_heads,
        head_dim=head_dim or hidden_size // num_attention_heads,
        dense_dim=intermediate_size, expert_dim=moe_intermediate_size,
        num_experts=num_experts,
        experts_held=tuple(experts_held or (0, num_experts)),
        top_k=num_experts_per_tok,
        mixers=tuple(LAYER_TYPES[kind] for kind in kinds),
        num_dense_layers=num_dense_layers, rope_theta=float(rope_theta),
        eps=norm_eps, vocab_rows=vocab_rows,
        router_bias_rate=router_bias_rate,
        loss_block_tokens=loss_block_tokens, moe_block_rows=moe_block_rows,
        embedding_init_std=embedding_init_std,
        residual_init_std=0.02 if residual_init_layers is None else
        0.02 / float(np.sqrt(2 * residual_init_layers)))
    self._sequence_length = sequence_length

  def get_feature_specification(self, mode: str) -> SpecStruct:
    del mode
    return SpecStruct(tokens=TensorSpec(
        shape=(self._sequence_length,), dtype=np.int32, name='tokens'))

  def get_label_specification(self, mode: str) -> SpecStruct:
    del mode
    return SpecStruct()

  def create_network(self) -> nn.Module:
    return LFM2Net(dtype=jnp.dtype(self.compute_dtype), **self._net_kwargs)

  def model_train_fn(self, variables, features, labels, inference_outputs,
                     mode: str):
    del variables, features, labels, mode
    return inference_outputs['loss'], {
        name: inference_outputs[name] for name in STEP_METRICS}

  def create_export_outputs_fn(self, features, inference_outputs, mode: str
                               ) -> SpecStruct:
    del features, mode
    return SpecStruct(last_logits=inference_outputs['last_logits'])
