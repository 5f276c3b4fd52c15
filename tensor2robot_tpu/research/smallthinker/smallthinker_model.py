"""SmallThinker-style language backbone, trained on next-token prediction.

The architecture of PowerInfer's SmallThinker-21BA3B-Instruct (its public
``config.json``; README.md beside this file has the equations, every
assumption and every departure): a stack of pre-norm blocks whose ROUTER
reads the block's input, before attention; grouped-query attention in two
kinds of layer, full layers that carry no positions at all and sliding-
window layers with rotary positions; top-k routed gated (ReGLU) experts
with no capacity and no auxiliary loss; RMS norms; an untied embedding and
head.

The model can hold one chip's SHARE of an expert-parallel, vocabulary-split
deployment: ``experts_held`` (first index, count) of the ``num_experts`` the
router scores, and the first ``vocab_rows`` rows of embedding and head. The
router keeps its full width, pairs routed to an absent expert are left out,
and token ids, logits and loss are over the held rows. Nothing stands in
for the absent chips.

Training cost is kept in bounds by three things: ``jax.checkpoint`` around
every block, the flash kernels (no [L, L] scores), and a head whose logits
and loss are computed over blocks of tokens, so that the float32 logits of
a whole batch never exist at once. A block's checkpoint keeps, beside the
residual stream between blocks, what the attention backward kernels read
(q, k, v as the kernels see them, ``out`` and the log-sum-exp: the arrays
parallel/flash_attention.py names, BACKWARD_READS), so the backward pass
runs neither the flash forward kernel nor the q, k, v projections a second
time; everything else in the block (norms, router, the expert layer) is
computed again. A block whose attention is not the flash kernel carries no
such names and keeps the residual stream alone. The same checkpoint keeps,
in a block of several residual streams (research/xing), what the stream
kernels name (parallel/hyper_connections.py, BACKWARD_READS); this model's
blocks have one stream and give none of those names.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.models import optimizers as opt_lib
from tensor2robot_tpu.models.abstract_model import AbstractT2RModel
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.parallel import hyper_connections as hc_lib
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec

MOE_STATS = ('moe/pairs_held', 'moe/expert_load_max_over_mean',
             'moe/dropped_pairs', 'moe/rows_in_use')

# A block under ``jax.checkpoint`` that keeps, for its backward pass, the
# residuals the flash kernels' forward rule names (at 2 x 8192 tokens, 28 and
# 4 heads of 128, bf16: 117.4 MB each for q and out, 16.8 each for k and v,
# 1.8 for the log-sum-exp, a layer) and, in a block of several residual
# streams, what the stream kernels' forward rules name (h, the maps and f of
# each sublayer and the state between the two: 100,608 bytes a token at
# four streams of 3,584, 412 MB a layer at 4,096 tokens), so that its
# backward runs neither stream forward kernel nor the products that made f
# again; it computes the rest of itself again. A block that gives none of
# these names (no flash kernel, one stream) keeps its input alone.
CheckpointedBlock = nn.remat(
    transformer_lib.MoEBlock,
    policy=jax.checkpoint_policies.save_only_these_names(
        *transformer_lib.flash_lib.BACKWARD_READS, *hc_lib.BACKWARD_READS))


def next_token_loss(hidden, head, tokens, block_tokens: int, dtype,
                    shift: int = 1):
  """Mean cross-entropy of position i's logits against token i + ``shift``,
  in f32, over positions 0..L-1-shift of every sequence (``shift`` 2: a
  multi-token-prediction module's loss, ``layers/mtp.py``).

  hidden [B, L, d], head [d, V], tokens [B, L]; the logits are formed
  ``block_tokens`` tokens at a time (``blocked_cross_entropy``)."""
  b, l, _ = hidden.shape
  counted = jnp.broadcast_to(jnp.arange(l) < l - shift, (b, l))
  return transformer_lib.blocked_cross_entropy(
      hidden, head, jnp.roll(tokens, -shift, axis=1), counted, block_tokens,
      dtype) / (b * (l - shift))


class SmallThinkerNet(nn.Module):
  """tokens [B, L] int32 -> {'loss', the expert layers' stats} (and
  ``last_logits`` [B, V] when predicting)."""

  hidden_size: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  expert_dim: int
  num_experts: int
  experts_held: Tuple[int, int]
  top_k: int
  window_layers: Tuple[bool, ...]   # per layer: sliding window?
  rope_layers: Tuple[bool, ...]     # per layer: rotary positions?
  window: int
  rope_theta: float
  eps: float
  vocab_rows: int
  loss_block_tokens: int = 2048
  moe_block_rows: int = 256
  embedding_init_std: float = 0.02
  residual_init_std: float = 0.02
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, features, mode: str = ModeKeys.TRAIN,
               train: bool = False):
    del train  # no dropout, no batch statistics
    tokens = features['tokens']
    init = nn.initializers.normal(0.02)
    embedding = self.param(
        'embedding', nn.initializers.normal(self.embedding_init_std),
        (self.vocab_rows, self.hidden_size), jnp.float32)
    head = self.param('head', init, (self.hidden_size, self.vocab_rows),
                      jnp.float32)
    x = jnp.take(embedding, tokens, axis=0).astype(self.dtype)
    stats = []
    for layer, (windowed, rotary) in enumerate(
        zip(self.window_layers, self.rope_layers)):
      x, layer_stats = CheckpointedBlock(
          num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
          head_dim=self.head_dim, num_experts=self.num_experts,
          experts_held=tuple(self.experts_held), expert_dim=self.expert_dim,
          top_k=self.top_k, window=self.window if windowed else None,
          rope_theta=self.rope_theta if rotary else None, eps=self.eps,
          moe_block_rows=self.moe_block_rows,
          residual_init_std=self.residual_init_std, dtype=self.dtype,
          name='block{}'.format(layer))(x)
      stats.append(layer_stats)
    hidden = transformer_lib.RMSNorm(self.eps, name='norm_final')(x)
    outputs = {
        'loss': next_token_loss(hidden, head, tokens, self.loss_block_tokens,
                                self.dtype),
        'moe/pairs_held': sum(s['pairs_held'] for s in stats),
        'moe/expert_load_max_over_mean':
            sum(s['load_max_over_mean'] for s in stats) / len(stats),
        'moe/dropped_pairs': sum(s['dropped_pairs'] for s in stats),
        'moe/rows_in_use': sum(s['rows_in_use'] for s in stats),
    }
    if mode == ModeKeys.PREDICT:
      outputs['last_logits'] = jnp.dot(
          hidden[:, -1].astype(self.dtype), head.astype(self.dtype),
          preferred_element_type=jnp.float32)
    return outputs


class SmallThinkerModel(AbstractT2RModel):
  """The network above as a T2R model: spec ``tokens`` int32 [L], no labels
  (the targets are the tokens shifted by one, inside the model).

  The keyword names are the public config's where it has one. The layer
  patterns may be longer than ``num_hidden_layers`` (the config's are 52
  long); the first ``num_hidden_layers`` entries are used.

  Initialisation: every matrix normal(0.02) (the config states no
  initialiser), unless the caller says otherwise for the two things that
  set the size of the residual stream: ``embedding_init_std`` (1 gives a
  stream of unit size, what scaling a 0.02 embedding by sqrt(hidden) gives:
  Vaswani et al. 2017, section 3.4) and ``residual_init_layers`` (N: the two
  matrices of a block that write into the stream, attention's ``out`` and
  the experts' ``w_down``, start at 0.02 / sqrt(2 N), GPT-2's and
  Megatron-LM's scaling by depth; N is the depth of the WHOLE model where
  this one holds a few of its layers). README.md says what they do to the
  routing of an untrained stack. ``learning_rate`` is Adam's
  (``create_optimizer_fn`` still overrides)."""

  report_gradient_norm = True

  def __init__(self,
               hidden_size: int = 2560,
               num_attention_heads: int = 28,
               num_key_value_heads: int = 4,
               head_dim: int = 128,
               moe_ffn_hidden_size: int = 768,
               moe_num_primary_experts: int = 64,
               experts_held: Optional[Sequence[int]] = None,
               moe_num_active_primary_experts: int = 6,
               num_hidden_layers: int = 52,
               sliding_window_layout: Sequence[int] = (0, 1, 1, 1) * 13,
               rope_layout: Sequence[int] = (0, 1, 1, 1) * 13,
               sliding_window_size: int = 4096,
               rope_theta: float = 1.5e6,
               rms_norm_eps: float = 1e-6,
               vocab_rows: int = 151936,
               sequence_length: int = 8192,
               loss_block_tokens: int = 2048,
               moe_block_rows: int = 256,
               embedding_init_std: float = 0.02,
               residual_init_layers: Optional[int] = None,
               learning_rate: float = 1e-4,
               **kwargs):
    kwargs.setdefault('create_optimizer_fn', functools.partial(
        opt_lib.create_adam_optimizer, learning_rate))
    super().__init__(**kwargs)
    if min(len(sliding_window_layout), len(rope_layout)) < num_hidden_layers:
      raise ValueError('the layer patterns are shorter than {} layers.'
                       .format(num_hidden_layers))
    self._net_kwargs = dict(
        hidden_size=hidden_size, num_heads=num_attention_heads,
        num_kv_heads=num_key_value_heads, head_dim=head_dim,
        expert_dim=moe_ffn_hidden_size, num_experts=moe_num_primary_experts,
        experts_held=tuple(experts_held or (0, moe_num_primary_experts)),
        top_k=moe_num_active_primary_experts,
        window_layers=tuple(
            bool(v) for v in sliding_window_layout[:num_hidden_layers]),
        rope_layers=tuple(bool(v) for v in rope_layout[:num_hidden_layers]),
        window=sliding_window_size, rope_theta=float(rope_theta),
        eps=rms_norm_eps, vocab_rows=vocab_rows,
        loss_block_tokens=loss_block_tokens,
        moe_block_rows=moe_block_rows,
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
    return SmallThinkerNet(dtype=jnp.dtype(self.compute_dtype),
                           **self._net_kwargs)

  def model_train_fn(self, variables, features, labels, inference_outputs,
                     mode: str):
    del variables, features, labels, mode
    return inference_outputs['loss'], {
        name: inference_outputs[name] for name in MOE_STATS}

  def create_export_outputs_fn(self, features, inference_outputs, mode: str
                               ) -> SpecStruct:
    del features, mode
    return SpecStruct(last_logits=inference_outputs['last_logits'])
