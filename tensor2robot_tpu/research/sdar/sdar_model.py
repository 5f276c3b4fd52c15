"""SDAR-style language backbone, trained by masked block diffusion.

The architecture of JetLM's SDAR-30B-A3B-Chat (its public ``config.json``,
``model_type`` ``sdar_moe``, which follows Qwen3-MoE; README.md beside this
file has the equations, every assumption and every departure): a stack of
pre-norm blocks of grouped-query attention with an RMS norm over the head
dimension of q and k before the rotation, a router that reads the normed
post-attention stream, top-k routed SwiGLU experts with no capacity and no
auxiliary loss; RMS norms; an untied embedding and head.

What differs from the next-token models here is the OBJECTIVE (BD3-LM,
Arriola et al. 2025): a clean sequence of L tokens in L / B blocks; every
block draws a noise level t, each of its tokens becomes the mask id with
probability t; the stack runs ONCE over the 2L positions [noised ; clean],
both halves carrying position ids 0..L-1, under the block-diffusion mask
(``parallel/flash_attention.py``: ``block_diffusion``); logits are formed at
the L noised positions only, against the clean token at the SAME position,
and the loss is the cross-entropy over the masked positions weighted by
1 / t, over L.

The corruption is a pure function of a sequence and a key (``corrupt``), and
a sequence's key is the step's rng with a checksum of the sequence's own ids
folded in (``sequence_key``): the noise is new every step, the same under
any batch layout, and anyone holding the step's rng and one row can draw it
again (the benchmark's plain reference does). It is drawn in the model's
``inference_network_fn``, from the rng ``loss_fn`` is given, not inside the
flax module.

The model can hold one chip's SHARE of an expert-parallel, vocabulary-split
deployment, as ``research/smallthinker`` does: ``experts_held`` (first
index, count) of the ``num_experts`` the router scores and the first
``vocab_rows`` rows of embedding and head. A block is that model's
``CheckpointedBlock``: the residual stream and what the attention backward
kernels read are kept, the rest is computed again.
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
from tensor2robot_tpu.research.smallthinker.smallthinker_model import (
    CheckpointedBlock,
)
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec

STEP_METRICS = ('moe/pairs_held', 'moe/expert_load_max_over_mean',
                'moe/dropped_pairs', 'moe/rows_in_use',
                'diffusion/masked_positions', 'diffusion/mean_noise_level')

def sequence_key(rng, tokens_row):
  """The key of one sequence's noise: ``rng`` with a checksum of the
  sequence's ids folded in (sum of id x (index + 1), modulo 2^32), so it
  depends on the step and on the sequence, never on its place in a batch."""
  weights = jnp.arange(1, tokens_row.shape[0] + 1, dtype=jnp.uint32)
  return jax.random.fold_in(
      rng, jnp.sum(tokens_row.astype(jnp.uint32) * weights, dtype=jnp.uint32))


def corrupt(tokens_row, key, block_length: int = 4, noise_eps: float = 1e-3,
            mask_token_id: int = 0):
  """(noised_row [L], t_by_block [L / B] f32, masked [L] bool) of one clean
  sequence: t = eps + (1 - eps) U per block, every token of the block
  becomes ``mask_token_id`` with probability t, independently. What is
  masked is decided by the draw, never by comparing ids."""
  length = tokens_row.shape[0]
  level_key, token_key = jax.random.split(key)
  t = noise_eps + (1.0 - noise_eps) * jax.random.uniform(
      level_key, (length // block_length,), jnp.float32)
  masked = jax.random.uniform(token_key, (length,), jnp.float32) < jnp.repeat(
      t, block_length)
  noised = jnp.where(masked, jnp.asarray(mask_token_id, tokens_row.dtype),
                     tokens_row)
  return noised, t, masked


class SDARNet(nn.Module):
  """features {'tokens', 'noised_tokens' [B, L] int32, 'noise_level'
  [B, L / block] f32, 'masked' [B, L] bool} -> {'loss', the step's stats}
  (and ``block_logits`` [B, block, V], the last block's, when predicting)."""

  hidden_size: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  expert_dim: int
  num_experts: int
  experts_held: Tuple[int, int]
  top_k: int
  num_layers: int
  block_length: int
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
    tokens, noised = features['tokens'], features['noised_tokens']
    length = tokens.shape[1]
    init = nn.initializers.normal(0.02)
    embedding = self.param(
        'embedding', nn.initializers.normal(self.embedding_init_std),
        (self.vocab_rows, self.hidden_size), jnp.float32)
    head = self.param('head', init, (self.hidden_size, self.vocab_rows),
                      jnp.float32)
    x = jnp.take(embedding, jnp.concatenate([noised, tokens], axis=1),
                 axis=0).astype(self.dtype)
    positions = jnp.tile(jnp.arange(length), 2)
    stats = []
    for layer in range(self.num_layers):
      x, layer_stats = CheckpointedBlock(
          num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
          head_dim=self.head_dim, num_experts=self.num_experts,
          experts_held=tuple(self.experts_held), expert_dim=self.expert_dim,
          top_k=self.top_k, rope_theta=self.rope_theta, eps=self.eps,
          router_reads='normed', qk_norm=True,
          block_diffusion=(length, self.block_length),
          gate_activation='silu', moe_block_rows=self.moe_block_rows,
          residual_init_std=self.residual_init_std, dtype=self.dtype,
          name='block{}'.format(layer))(x, positions)
      stats.append(layer_stats)
    hidden = transformer_lib.RMSNorm(self.eps, name='norm_final')(
        x[:, :length])
    masked = features['masked']
    weights = masked / jnp.repeat(features['noise_level'], self.block_length,
                                  axis=1)
    outputs = {
        'loss': transformer_lib.blocked_cross_entropy(
            hidden, head, tokens, weights, self.loss_block_tokens,
            self.dtype) / (tokens.shape[0] * length),
        'moe/pairs_held': sum(s['pairs_held'] for s in stats),
        'moe/expert_load_max_over_mean':
            sum(s['load_max_over_mean'] for s in stats) / len(stats),
        'moe/dropped_pairs': sum(s['dropped_pairs'] for s in stats),
        'moe/rows_in_use': sum(s['rows_in_use'] for s in stats),
        'diffusion/masked_positions': jnp.sum(masked, dtype=jnp.float32),
        'diffusion/mean_noise_level': jnp.mean(features['noise_level']),
    }
    if mode == ModeKeys.PREDICT:
      outputs['block_logits'] = jnp.dot(
          hidden[:, -self.block_length:].astype(self.dtype),
          head.astype(self.dtype), preferred_element_type=jnp.float32)
    return outputs


class SDARModel(AbstractT2RModel):
  """The network above as a T2R model: spec ``tokens`` int32 [L], no labels
  (the targets are the clean tokens themselves, inside the model).

  The keyword names are the public config's where it has one;
  ``experts_held`` (first, count) and ``vocab_rows`` say what this chip
  holds, ``block_length``, ``noise_eps`` and ``mask_token_id`` (None: the
  last row held) are the objective's. Initialisation and ``learning_rate``
  as ``SmallThinkerModel``'s: every matrix normal(0.02) unless
  ``embedding_init_std`` or ``residual_init_layers`` (N: attention's ``out``
  and the experts' ``w_down`` start at 0.02 / sqrt(2 N)) say otherwise.

  ``traced_step_metrics``: the step metrics the trainer's step watcher
  writes into the ``train.step_done`` event of the span ring."""

  report_gradient_norm = True
  traced_step_metrics = ('moe/pairs_held', 'diffusion/masked_positions')

  def __init__(self,
               hidden_size: int = 2048,
               num_attention_heads: int = 32,
               num_key_value_heads: int = 4,
               head_dim: int = 128,
               moe_intermediate_size: int = 768,
               num_experts: int = 128,
               experts_held: Optional[Sequence[int]] = None,
               num_experts_per_tok: int = 8,
               num_hidden_layers: int = 48,
               rope_theta: float = 1e6,
               rms_norm_eps: float = 1e-6,
               vocab_rows: int = 151936,
               sequence_length: int = 8192,
               block_length: int = 4,
               noise_eps: float = 1e-3,
               mask_token_id: Optional[int] = None,
               loss_block_tokens: int = 2048,
               moe_block_rows: int = 256,
               embedding_init_std: float = 0.02,
               residual_init_layers: Optional[int] = None,
               learning_rate: float = 1e-4,
               **kwargs):
    kwargs.setdefault('create_optimizer_fn', functools.partial(
        opt_lib.create_adam_optimizer, learning_rate))
    super().__init__(**kwargs)
    if sequence_length % block_length:
      raise ValueError('block_length {} does not divide the sequence length '
                       '{}.'.format(block_length, sequence_length))
    self._net_kwargs = dict(
        hidden_size=hidden_size, num_heads=num_attention_heads,
        num_kv_heads=num_key_value_heads, head_dim=head_dim,
        expert_dim=moe_intermediate_size, num_experts=num_experts,
        experts_held=tuple(experts_held or (0, num_experts)),
        top_k=num_experts_per_tok, num_layers=num_hidden_layers,
        block_length=block_length, rope_theta=float(rope_theta),
        eps=rms_norm_eps, vocab_rows=vocab_rows,
        loss_block_tokens=loss_block_tokens, moe_block_rows=moe_block_rows,
        embedding_init_std=embedding_init_std,
        residual_init_std=0.02 if residual_init_layers is None else
        0.02 / float(np.sqrt(2 * residual_init_layers)))
    self._sequence_length = sequence_length
    self._corruption = dict(
        block_length=block_length, noise_eps=noise_eps,
        mask_token_id=vocab_rows - 1 if mask_token_id is None
        else mask_token_id)

  def get_feature_specification(self, mode: str) -> SpecStruct:
    del mode
    return SpecStruct(tokens=TensorSpec(
        shape=(self._sequence_length,), dtype=np.int32, name='tokens'))

  def get_label_specification(self, mode: str) -> SpecStruct:
    del mode
    return SpecStruct()

  def create_network(self) -> nn.Module:
    return SDARNet(dtype=jnp.dtype(self.compute_dtype), **self._net_kwargs)

  def corrupted(self, features, rng):
    """``features`` with every sequence's noised copy, noise levels and
    mask beside it, each drawn from ``sequence_key(rng, the sequence)``."""
    tokens = features['tokens']
    with jax.named_scope('diffusion_corrupt'):
      noised, level, masked = jax.vmap(lambda row: corrupt(
          row, sequence_key(rng, row), **self._corruption))(tokens)
    return {'tokens': tokens, 'noised_tokens': noised, 'noise_level': level,
            'masked': masked}

  def init_variables(self, rng, features, labels=None,
                     mode: str = ModeKeys.TRAIN):
    return super().init_variables(rng, self.corrupted(features, rng), labels,
                                  mode)

  def inference_network_fn(self, variables, features, labels=None,
                           mode: str = ModeKeys.TRAIN, rng=None):
    # Outside training there is no step rng; evaluation and prediction then
    # draw one fixed noise a sequence.
    rng = jax.random.PRNGKey(0) if rng is None else rng
    return super().inference_network_fn(
        variables, self.corrupted(features, rng), labels, mode, None)

  def model_train_fn(self, variables, features, labels, inference_outputs,
                     mode: str):
    del variables, features, labels, mode
    return inference_outputs['loss'], {
        name: inference_outputs[name] for name in STEP_METRICS}

  def create_export_outputs_fn(self, features, inference_outputs, mode: str
                               ) -> SpecStruct:
    del features, mode
    return SpecStruct(block_logits=inference_outputs['block_logits'])
