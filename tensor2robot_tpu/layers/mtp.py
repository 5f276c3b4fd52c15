"""Multi-token prediction: a second loss over the token two places ahead.

The depth-1 module of DeepSeek-V3 (arXiv:2412.19437, section 2.2), laid out
as the published checkpoints lay it out (vLLM's ``deepseek_mtp.py``: ``enorm``,
``hnorm``, ``eh_proj``, one decoder block, ``shared_head.norm``). On the
trunk's output h [B, L, d] and the tokens t [B, L]:

  m_i = W_eh [ RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i) ]    (2d -> d, embedding first)
  g   = block(m)                                          (causal over i)
  L_mtp = mean over i <= L-3 of CE(RMSNorm_s(g_i) W_head, t_{i+2})

E and W_head are the trunk's own embedding and head: the module holds
neither. ``MultiTokenPrediction`` gives RMSNorm_s(g) and the block's stats;
the trunk's next-token loss at shift 2 (``research/smallthinker``'s
``next_token_loss``) gives L_mtp through the fused head loss. The last two
positions of a sequence carry weight 0; their rows (the embedding of t_0
wrapped round at position L-1) reach no earlier position, the block's
attention being causal."""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from tensor2robot_tpu.layers import transformer as transformer_lib


class MultiTokenPrediction(nn.Module):
  """The module above. ``block`` is an unbound block, x [B, L, d] -> (x,
  stats), adopted under the name ``block``; ``depth`` is the config's
  ``num_nextn_predict_layers`` and only 1 is built.

  ``__call__(hidden [B, L, d], tokens [B, L], embedding [V, d])`` ->
  (RMSNorm_s(g) [B, L, d] f32, the block's stats)."""

  block: nn.Module
  depth: int = 1
  eps: float = 1e-6
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, hidden, tokens, embedding):
    if self.depth != 1:
      raise ValueError('multi-token prediction is built at depth 1 only; '
                       'got {} modules.'.format(self.depth))
    following = jnp.take(embedding, jnp.roll(tokens, -1, axis=1), axis=0)
    joined = jnp.concatenate(
        [transformer_lib.RMSNorm(self.eps, name='enorm')(following),
         transformer_lib.RMSNorm(self.eps, name='hnorm')(hidden)], axis=-1)
    m = nn.Dense(hidden.shape[-1], use_bias=False, dtype=self.dtype,
                 kernel_init=nn.initializers.normal(0.02),
                 name='eh_proj')(joined.astype(self.dtype))
    g, stats = self.block(m)
    return transformer_lib.RMSNorm(self.eps, name='shared_head_norm')(g), stats
