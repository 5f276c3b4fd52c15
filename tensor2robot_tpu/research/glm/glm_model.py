"""GLM-4.7-Flash-style language backbone, trained by next-token prediction
and multi-token prediction.

The architecture of zai-org's GLM-4.7-Flash (its public ``config.json``,
``model_type`` ``glm4_moe_lite``; README.md beside this file has the
equations, every assumption and every departure): a stack of pre-norm blocks
whose token mixer is multi-head LATENT attention (low-rank queries, a
compressed key/value with ONE rotary key shared by all heads, keys and values
both 256 wide, plain rotary positions); whose feed-forward is one dense
SwiGLU in the ``first_k_dense_replace`` leading layers and, after them,
routed SwiGLU experts chosen by a sigmoid with a selection bias and weighed
by the renormalised sigmoids times ``routed_scaling_factor``, beside ONE
shared expert every token goes through; an untied embedding and head. On top
of the trunk, ONE multi-token-prediction module (``layers/mtp.py``): the
normed trunk output and the next token's embedding through ``eh_proj`` and
one more expert block, read by the same head against the token two places
ahead. The step's loss is L_main + ``mtp_loss_weight`` x L_mtp.

Every block, the MTP's among them, is ``layers/transformer.py::MoEBlock``
under ``jax.checkpoint`` with ``research/smallthinker``'s policy (its input
and what the flash kernels' backward reads are kept, the rest computed
again). The routers' biases are state the optimizer does not own (the
``router_state`` collection, in ``TrainState.model_state``), moved by the
auxiliary-loss-free rule after each step as in ``research/lfm2``.

The model can hold one chip's SHARE of an expert-parallel, vocabulary-split
deployment: ``experts_held`` (first index, count) of the
``n_routed_experts`` the routers score, and the first ``vocab_rows`` rows of
the embedding and of the head.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers import mtp as mtp_lib
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

STEP_METRICS = ('moe/chosen_load_max_over_mean', 'main/loss', 'mtp/loss',
                'moe/router_bias_abs_mean', 'moe/pairs_held',
                'moe/expert_load_max_over_mean', 'moe/dropped_pairs',
                'moe/rows_in_use')


class GlmNet(nn.Module):
  """tokens [B, L] int32 -> {'loss', 'main/loss', 'mtp/loss', the expert
  layers' stats} (and ``last_logits`` [B, V] when predicting)."""

  hidden_size: int
  num_heads: int
  q_lora_rank: int
  kv_lora_rank: int
  qk_nope_head_dim: int
  qk_rope_head_dim: int
  v_head_dim: int
  rope_theta: float
  dense_dim: int
  expert_dim: int
  shared_expert_dim: int
  num_experts: int
  experts_held: Tuple[int, int]
  top_k: int
  num_layers: int
  num_dense_layers: int
  mtp_loss_weight: float
  routed_scaling: float
  eps: float
  vocab_rows: int
  router_bias_rate: float = 1e-3
  loss_block_tokens: int = 2048
  moe_block_rows: int = 256
  embedding_init_std: float = 0.02
  residual_init_std: float = 0.02
  dtype: jnp.dtype = jnp.float32

  def _block(self, feed_forward: str, **name):
    return CheckpointedBlock(
        num_heads=self.num_heads, num_kv_heads=self.num_heads,
        head_dim=self.qk_nope_head_dim + self.qk_rope_head_dim,
        num_experts=self.num_experts, experts_held=tuple(self.experts_held),
        expert_dim=self.expert_dim, top_k=self.top_k,
        rope_theta=self.rope_theta, eps=self.eps, mixer='latent_attention',
        feed_forward=feed_forward, dense_dim=self.dense_dim,
        router_reads='normed', router='sigmoid_bias',
        router_bias_rate=self.router_bias_rate,
        routed_scaling=self.routed_scaling,
        shared_expert_dim=self.shared_expert_dim,
        q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
        qk_nope_head_dim=self.qk_nope_head_dim,
        qk_rope_head_dim=self.qk_rope_head_dim, v_head_dim=self.v_head_dim,
        gate_activation='silu', moe_block_rows=self.moe_block_rows,
        residual_init_std=self.residual_init_std, dtype=self.dtype, **name)

  @nn.compact
  def __call__(self, features, mode: str = ModeKeys.TRAIN,
               train: bool = False):
    del train  # no dropout; the router's bias moves where it is mutable
    tokens = features['tokens']
    embedding = self.param(
        'embedding', nn.initializers.normal(self.embedding_init_std),
        (self.vocab_rows, self.hidden_size), jnp.float32)
    head = self.param('head', nn.initializers.normal(0.02),
                      (self.hidden_size, self.vocab_rows), jnp.float32)
    x = jnp.take(embedding, tokens, axis=0).astype(self.dtype)
    stats = []
    for layer in range(self.num_layers):
      x, layer_stats = self._block(
          'dense' if layer < self.num_dense_layers else 'experts',
          name='block{}'.format(layer))(x)
      if layer_stats:
        stats.append(layer_stats)
    hidden = transformer_lib.RMSNorm(self.eps, name='norm_final')(x)
    main = next_token_loss(hidden, head, tokens, self.loss_block_tokens,
                           self.dtype)
    ahead, mtp_stats = mtp_lib.MultiTokenPrediction(
        self._block('experts', parent=None), eps=self.eps, dtype=self.dtype,
        name='mtp')(hidden, tokens, embedding)
    stats.append(mtp_stats)
    # Under the module's scope, so that a trace finds the second head pass
    # with the rest of the MTP's work.
    with jax.named_scope('mtp'):
      mtp = next_token_loss(ahead, head, tokens, self.loss_block_tokens,
                            self.dtype, shift=2)
    total = lambda name: sum((s[name] for s in stats), jnp.float32(0))
    mean = lambda name: total(name) / len(stats)
    outputs = {
        'loss': main + self.mtp_loss_weight * mtp,
        'main/loss': main,
        'mtp/loss': mtp,
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


class GlmModel(AbstractT2RModel):
  """The network above as a T2R model: spec ``tokens`` int32 [L], no labels
  (the targets are the tokens shifted by one and by two, inside the model).

  The keyword names are the public config's where it has one; the model
  builds the published form only and refuses another value of the keys that
  name one (``attention_bias``, ``topk_method``, ``n_group``, ``topk_group``,
  ``norm_topk_prob``, ``n_shared_experts``, ``tie_word_embeddings``,
  ``hidden_act``, ``partial_rotary_factor``, ``rope_scaling``, one key/value
  head a query head) and ``num_nextn_predict_layers`` other than 1.
  ``n_routed_experts`` is the routers' width, ``experts_held`` (first, count)
  what this chip holds, ``vocab_rows`` the rows of embedding and head held;
  ``num_hidden_layers`` trunk layers are built, the first
  ``first_k_dense_replace`` of them dense, and the MTP module beside them.
  ``mtp_loss_weight`` is lambda (the config has no key for it; 0.3 is
  DeepSeek-V3's for most of its pre-training), ``router_bias_rate`` the
  balancing rule's step (no key either).

  Initialisation as the other token models': every matrix normal(0.02)
  unless ``embedding_init_std`` or ``residual_init_layers`` (N: attention's
  ``out``, the dense and shared ``w2`` and the experts' ``w_down`` start at
  0.02 / sqrt(2 N)) say otherwise.

  ``traced_step_metrics``: the step metrics the trainer's step watcher
  writes into the ``train.step_done`` event: the tokens that chose the most
  chosen of all the router's experts over the mean, a layer (what the
  selection bias balances), and the two losses."""

  report_gradient_norm = True
  traced_step_metrics = STEP_METRICS[:3]

  def __init__(self,
               hidden_size: int = 2048,
               num_attention_heads: int = 20,
               num_key_value_heads: int = 20,
               q_lora_rank: int = 768,
               kv_lora_rank: int = 512,
               qk_nope_head_dim: int = 192,
               qk_rope_head_dim: int = 64,
               v_head_dim: int = 256,
               intermediate_size: int = 10240,
               moe_intermediate_size: int = 1536,
               n_routed_experts: int = 64,
               experts_held: Optional[Sequence[int]] = None,
               n_shared_experts: int = 1,
               num_experts_per_tok: int = 4,
               num_hidden_layers: int = 47,
               first_k_dense_replace: int = 1,
               routed_scaling_factor: float = 1.8,
               norm_topk_prob: bool = True,
               topk_method: str = 'noaux_tc',
               n_group: int = 1,
               topk_group: int = 1,
               hidden_act: str = 'silu',
               attention_bias: bool = False,
               tie_word_embeddings: bool = False,
               rope_theta: float = 1e6,
               rope_scaling: Optional[dict] = None,
               partial_rotary_factor: float = 1.0,
               rms_norm_eps: float = 1e-5,
               num_nextn_predict_layers: int = 1,
               mtp_loss_weight: float = 0.3,
               vocab_rows: int = 154880,
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
    published = (
        num_key_value_heads == num_attention_heads and n_shared_experts == 1
        and norm_topk_prob and topk_method == 'noaux_tc' and n_group == 1 and
        topk_group == 1 and hidden_act == 'silu' and not attention_bias and
        not tie_word_embeddings and rope_scaling is None and
        partial_rotary_factor == 1)
    if not published:
      raise ValueError(
          'only the published form is built: one key/value head a query '
          'head, one shared expert, a sigmoid noaux_tc router over one group '
          'with norm_topk_prob, SwiGLU, no attention bias, an untied head, '
          'plain rotary positions over all the rope dimensions.')
    if num_nextn_predict_layers != 1:
      raise ValueError('one multi-token-prediction module is built: '
                       'num_nextn_predict_layers must be 1; got {}.'.format(
                           num_nextn_predict_layers))
    self._net_kwargs = dict(
        hidden_size=hidden_size, num_heads=num_attention_heads,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim, rope_theta=float(rope_theta),
        dense_dim=intermediate_size, expert_dim=moe_intermediate_size,
        shared_expert_dim=moe_intermediate_size * n_shared_experts,
        num_experts=n_routed_experts,
        experts_held=tuple(experts_held or (0, n_routed_experts)),
        top_k=num_experts_per_tok, num_layers=num_hidden_layers,
        num_dense_layers=first_k_dense_replace,
        mtp_loss_weight=float(mtp_loss_weight),
        routed_scaling=float(routed_scaling_factor), eps=rms_norm_eps,
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
    return GlmNet(dtype=jnp.dtype(self.compute_dtype), **self._net_kwargs)

  def model_train_fn(self, variables, features, labels, inference_outputs,
                     mode: str):
    del variables, features, labels, mode
    return inference_outputs['loss'], {
        name: inference_outputs[name] for name in STEP_METRICS}

  def create_export_outputs_fn(self, features, inference_outputs, mode: str
                               ) -> SpecStruct:
    del features, mode
    return SpecStruct(last_logits=inference_outputs['last_logits'])
