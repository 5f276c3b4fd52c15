"""Xing4.0-style language backbone, trained by next-token prediction.

The architecture of XingChen-AGI's Xing4.0-29B-A4B (its public
``config.json``, ``model_type`` ``xing4_0``; README.md beside this file has
the equations, every assumption and every departure): a stack of blocks
whose token mixer is multi-head LATENT attention (low-rank queries, a
compressed key/value with ONE rotary key shared by all heads, keys of 192
against values of 128, YaRN's frequencies and temperature); whose
feed-forward is one dense SwiGLU in the ``first_k_dense_replace`` leading
layers and, after them, routed SwiGLU experts chosen by a sigmoid with a
selection bias and weighed by the renormalised sigmoids times
``routed_scaling_factor``, beside ONE shared expert every token goes
through; and whose residual path is FOUR streams a token
(manifold-constrained hyper-connections: every sublayer reads a sigmoid
mix of the streams, writes back through a second map, and the streams are
mixed by a Sinkhorn-Knopp doubly stochastic matrix). The embedding enters
as four copies of the token's row; the untied head reads the RMS norm of
the streams' sum.

All of it is ``layers/transformer.py::MoEBlock`` with its mixer
(``latent_attention``), its feed-forward, its router, its shared expert and
its streams as fields; the stream maps and mixes are the Pallas kernels of
``parallel/hyper_connections.py``, the attention the flash kernels at two
widths. The router's bias is state the optimizer does not own (the
``router_state`` collection, in ``TrainState.model_state``), moved by the
auxiliary-loss-free rule after each step as in ``research/lfm2``.

The model can hold one chip's SHARE of an expert-parallel, vocabulary-split
deployment: ``experts_held`` (first index, count) of the
``n_routed_experts`` the router scores, and the first ``vocab_rows`` rows of
the embedding and of the head. A block is under ``jax.checkpoint`` with
``research/smallthinker``'s policy, which keeps its input (the four
streams), what the attention backward kernels read, and what the stream
kernels' backward and the two sublayers' backward read: h and the maps of
each sublayer, f (attention's ``out`` projection, the feed-forward's
output) as the post kernel reads it, and the four streams between the two
sublayers. At four streams of 3,584 and f in bf16 that is 100,608 bytes a
token, 412 MB a block at 4,096 tokens (the gauge
``hc/kept_bytes_per_token``); it spares the backward both pre kernels and
the first post kernel of each block, the ``out`` projection and the
feed-forward's last product (the dense or shared ``w2``; the routed
experts' down product still runs again, its rows being what the routing
weights' gradient reads). The rest is computed again. Multi-token
prediction is not built (``num_nextn_predict_layers`` must be 0).
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

STEP_METRICS = ('hc/res_stochastic_error', 'moe/chosen_load_max_over_mean',
                'moe/router_bias_abs_mean', 'moe/pairs_held',
                'moe/expert_load_max_over_mean', 'moe/dropped_pairs',
                'moe/rows_in_use')
ROPE_SCALING_KEYS = ('factor', 'original_max_position_embeddings',
                     'beta_fast', 'beta_slow', 'mscale', 'mscale_all_dim')


class XingNet(nn.Module):
  """tokens [B, L] int32 -> {'loss', the step metrics} (and ``last_logits``
  [B, V] when predicting)."""

  hidden_size: int
  num_heads: int
  q_lora_rank: int
  kv_lora_rank: int
  qk_nope_head_dim: int
  qk_rope_head_dim: int
  v_head_dim: int
  rope_theta: float
  rope_scaling: Optional[Tuple[float, ...]]
  dense_dim: int
  expert_dim: int
  shared_expert_dim: int
  num_experts: int
  experts_held: Tuple[int, int]
  top_k: int
  num_layers: int
  num_dense_layers: int
  routed_scaling: float
  streams: int
  stream_iters: int
  stream_eps: float
  stream_clamp: float
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
    self._set_gauges()
    tokens = features['tokens']
    embedding = self.param(
        'embedding', nn.initializers.normal(self.embedding_init_std),
        (self.vocab_rows, self.hidden_size), jnp.float32)
    head = self.param('head', nn.initializers.normal(0.02),
                      (self.hidden_size, self.vocab_rows), jnp.float32)
    # Every stream starts as the token's row.
    x = jnp.tile(jnp.take(embedding, tokens, axis=0), (1, 1, self.streams))
    stats, errors = [], []
    for layer in range(self.num_layers):
      x, layer_stats = CheckpointedBlock(
          num_heads=self.num_heads, num_kv_heads=self.num_heads,
          head_dim=self.qk_nope_head_dim + self.qk_rope_head_dim,
          num_experts=self.num_experts,
          experts_held=tuple(self.experts_held), expert_dim=self.expert_dim,
          top_k=self.top_k, rope_theta=self.rope_theta, eps=self.eps,
          mixer='latent_attention',
          feed_forward='dense' if layer < self.num_dense_layers
          else 'experts', dense_dim=self.dense_dim, router_reads='normed',
          router='sigmoid_bias', router_bias_rate=self.router_bias_rate,
          routed_scaling=self.routed_scaling,
          shared_expert_dim=self.shared_expert_dim,
          q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
          qk_nope_head_dim=self.qk_nope_head_dim,
          qk_rope_head_dim=self.qk_rope_head_dim,
          v_head_dim=self.v_head_dim, rope_scaling=self.rope_scaling,
          hc_streams=self.streams, hc_iters=self.stream_iters,
          hc_eps=self.stream_eps, hc_clamp=self.stream_clamp,
          gate_activation='silu', moe_block_rows=self.moe_block_rows,
          residual_init_std=self.residual_init_std, dtype=self.dtype,
          name='block{}'.format(layer))(x)
      errors.append(layer_stats.pop('res_stochastic_error'))
      if layer_stats:
        stats.append(layer_stats)
    b, l, _ = x.shape
    summed = jnp.sum(x.reshape(b, l, self.streams, self.hidden_size), axis=2)
    hidden = transformer_lib.RMSNorm(self.eps, name='norm_final')(summed)
    total = lambda name: sum((s[name] for s in stats), jnp.float32(0))
    mean = lambda name: total(name) / max(len(stats), 1)
    outputs = {
        'loss': next_token_loss(hidden, head, tokens, self.loss_block_tokens,
                                self.dtype),
        'hc/res_stochastic_error': jnp.max(jnp.stack(errors)),
        'moe/router_bias_abs_mean': mean('router_bias_abs_mean'),
        'moe/chosen_load_max_over_mean': mean('chosen_load_max_over_mean'),
        'moe/pairs_held': total('pairs_held'),
        'moe/expert_load_max_over_mean': mean('load_max_over_mean'),
        'moe/dropped_pairs': total('dropped_pairs'),
        'moe/rows_in_use': total('rows_in_use'),
    }
    if mode == ModeKeys.PREDICT:
      outputs['last_logits'] = jnp.dot(
          hidden[:, -1].astype(self.dtype), head.astype(self.dtype),
          preferred_element_type=jnp.float32)
    return outputs

  def _set_gauges(self):
    """Host side, when the model is traced: the streams and SK's
    iterations (the kernels set their own bytes a token)."""
    from tensor2robot_tpu.observability import get_registry

    registry = get_registry()
    registry.gauge('hc/streams').set(float(self.streams))
    registry.gauge('hc/sinkhorn_iters').set(float(self.stream_iters))


class XingModel(AbstractT2RModel):
  """The network above as a T2R model: spec ``tokens`` int32 [L], no labels
  (the targets are the tokens shifted by one, inside the model).

  The keyword names are the public config's where it has one; the model
  builds the published form only and refuses another value of the keys that
  name one (``attention_bias``, ``scoring_func``, ``topk_method``,
  ``n_group``, ``topk_group``, ``norm_topk_prob``, ``n_shared_experts``,
  ``tie_word_embeddings``, ``moe_layer_freq``, ``hidden_act``, the YaRN
  ``rope_scaling``) and multi-token prediction (``num_nextn_predict_layers``
  0 only). ``n_routed_experts`` is the router's width, ``experts_held``
  (first, count) what this chip holds, ``vocab_rows`` the rows of embedding
  and head held; ``num_hidden_layers`` layers are built, the first
  ``first_k_dense_replace`` of them dense. ``router_bias_rate`` is the
  balancing rule's step (the config has no key for it).

  Initialisation as the other token models': every matrix normal(0.02)
  unless ``embedding_init_std`` or ``residual_init_layers`` (N: attention's
  ``out``, the dense and shared ``w2`` and the experts' ``w_down`` start at
  0.02 / sqrt(2 N)) say otherwise; the stream maps' gains start at 1 and
  their biases normal(1).

  ``traced_step_metrics``: the step metrics the trainer's step watcher
  writes into the ``train.step_done`` event: the largest distance of a res
  map's row or column sums from 1, over the step's tokens and layers, and
  the tokens that chose the most chosen of all the router's experts over
  the mean, a layer (what the selection bias balances)."""

  report_gradient_norm = True
  traced_step_metrics = STEP_METRICS[:2]

  def __init__(self,
               hidden_size: int = 3584,
               num_attention_heads: int = 32,
               num_key_value_heads: int = 32,
               q_lora_rank: int = 768,
               kv_lora_rank: int = 512,
               qk_nope_head_dim: int = 128,
               qk_rope_head_dim: int = 64,
               v_head_dim: int = 128,
               intermediate_size: int = 9216,
               moe_intermediate_size: int = 1024,
               n_routed_experts: int = 64,
               experts_held: Optional[Sequence[int]] = None,
               n_shared_experts: int = 1,
               num_experts_per_tok: int = 4,
               num_hidden_layers: int = 40,
               first_k_dense_replace: int = 2,
               moe_layer_freq: int = 1,
               routed_scaling_factor: float = 2.0,
               norm_topk_prob: bool = True,
               scoring_func: str = 'sigmoid',
               topk_method: str = 'noaux_tc',
               n_group: int = 1,
               topk_group: int = 1,
               hidden_act: str = 'silu',
               attention_bias: bool = False,
               tie_word_embeddings: bool = False,
               rope_theta: float = 10000.0,
               rope_scaling: Optional[dict] = None,
               rms_norm_eps: float = 1e-6,
               hc_mult: int = 4,
               hc_sinkhorn_iters: int = 20,
               hc_eps: float = 1e-6,
               mhc_h_res_clamp_min: float = -30.0,
               mhc_h_res_clamp_max: float = 30.0,
               num_nextn_predict_layers: int = 0,
               vocab_rows: int = 131072,
               sequence_length: int = 4096,
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
    rope_scaling = dict(rope_scaling or dict(
        type='yarn', factor=64, original_max_position_embeddings=4096,
        beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1))
    published = (
        num_key_value_heads == num_attention_heads and n_shared_experts == 1
        and norm_topk_prob and scoring_func == 'sigmoid' and
        topk_method == 'noaux_tc' and n_group == 1 and topk_group == 1 and
        moe_layer_freq == 1 and hidden_act == 'silu' and
        not attention_bias and not tie_word_embeddings and
        rope_scaling.get('type') == 'yarn' and
        mhc_h_res_clamp_min == -mhc_h_res_clamp_max)
    if not published:
      raise ValueError(
          'only the published form is built: one key/value head a query '
          'head, one shared expert, a sigmoid noaux_tc router over one group '
          'with norm_topk_prob, every layer after the dense ones an expert '
          'layer, SwiGLU, no attention bias, an untied head, YaRN, a clamp '
          'symmetric about 0.')
    if num_nextn_predict_layers:
      raise ValueError('multi-token prediction is not built: '
                       'num_nextn_predict_layers must be 0.')
    self._net_kwargs = dict(
        hidden_size=hidden_size, num_heads=num_attention_heads,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim, rope_theta=float(rope_theta),
        rope_scaling=tuple(float(rope_scaling[key])
                           for key in ROPE_SCALING_KEYS),
        dense_dim=intermediate_size, expert_dim=moe_intermediate_size,
        shared_expert_dim=moe_intermediate_size * n_shared_experts,
        num_experts=n_routed_experts,
        experts_held=tuple(experts_held or (0, n_routed_experts)),
        top_k=num_experts_per_tok, num_layers=num_hidden_layers,
        num_dense_layers=first_k_dense_replace,
        routed_scaling=float(routed_scaling_factor), streams=hc_mult,
        stream_iters=hc_sinkhorn_iters, stream_eps=hc_eps,
        stream_clamp=float(mhc_h_res_clamp_max), eps=rms_norm_eps,
        vocab_rows=vocab_rows, router_bias_rate=router_bias_rate,
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
    return XingNet(dtype=jnp.dtype(self.compute_dtype), **self._net_kwargs)

  def model_train_fn(self, variables, features, labels, inference_outputs,
                     mode: str):
    del variables, features, labels, mode
    return inference_outputs['loss'], {
        name: inference_outputs[name] for name in STEP_METRICS}

  def create_export_outputs_fn(self, features, inference_outputs, mode: str
                               ) -> SpecStruct:
    del features, mode
    return SpecStruct(last_logits=inference_outputs['last_logits'])
