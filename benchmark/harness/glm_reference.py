"""The plain reference of the GLM-4.7-Flash-style backbone with its
multi-token-prediction module: ``jax.numpy`` only.

Float32 under ``jax.default_matmul_precision('highest')``, written from the
equations of ``tensor2robot_tpu/research/glm/README.md`` line by line; it
imports nothing of the program and reads the program's PARAMETER TREE
(data, not code), so that ``jax.grad`` of it has a leaf for every leaf of
the program's.

  block      pre-norm: x1 = x + attention(rmsnorm(x)); u = rmsnorm(x1);
             x' = x1 + ff(u), the residual x [L, C] float32
  attention  latent: c_q = rmsnorm(h W_qa), q = c_q W_qb; [c_kv ; k_pe] =
             h W_kva, [k_nope ; v] = rmsnorm(c_kv) W_kvb; ONE rotary key for
             all heads at theta^(-2i/64); scale 1 / sqrt(nope + rope); dense
             causal softmax over blocks of query rows
  experts    a sigmoid router, the top_k of score + bias chosen, weighed by
             the scores over their sum + 1e-6 times ``routed_scaling``; the
             held experts as ONE scanned body (each over every position,
             weighted by the position's weight for it, 0 where it was not
             chosen; pairs of absent experts are left out); the shared
             expert over every position, unweighted
  head       untied, over rmsnorm(x) with the final norm's scale, in blocks
             of rows, against the next token
  MTP        m = [rmsnorm_e(E[t_{i+1}]) ; rmsnorm_h(hidden_i)] W_eh, one
             more expert block, rmsnorm_s, the SAME head against t_{i+2},
             positions 0..L-3; the loss is L_main + mtp_weight x L_mtp

``settings`` is a plain dict: hidden_size, num_heads, q_lora_rank,
kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rope_theta,
dense_dim, expert_dim, shared_expert_dim, num_experts, experts_held (first,
count), top_k, num_dense_layers, window_layers (one False a trunk layer:
the driver logs its length), routed_scaling, eps, vocab_rows, mtp_weight,
query_block and head_block (rows at a time, memory only), and keys that
name the mathematics and have one right value each, so that a test or a
chip script can compute ANOTHER model and see the comparison refuse it:
``mtp_target_shift`` (2; the MTP's targets t_{i+shift}), ``mtp_embedding_shift``
(1; the MTP reads E[t_{i+shift}]), ``mtp_concat`` ('embedding_first';
'hidden_first' swaps the halves W_eh reads), ``enorm`` and ``hnorm`` (True;
False leaves that norm out), ``mtp_reads`` ('normed': the trunk's output
after its final norm; 'residual': before it), ``mtp_head_norm`` ('own':
shared_head.norm; 'trunk': the trunk's final norm), ``shared_expert`` (True;
False leaves it out) and ``scale_width`` ('key': 1 / sqrt(nope + rope);
'nope': 1 / sqrt(nope)). The routers' biases are state:
``settings['router_bias']`` (one [num_experts] row an expert layer, the
MTP's last) where a test has one, zeros otherwise, the published initial
value. ``dtype`` below float32 gives the reference at a lower precision:
bfloat16 computes in bfloat16; a one-byte float (``jnp.float8_e4m3fn``)
rounds every weight and activation to it and multiplies in bfloat16.
"""

import math

import jax
import jax.numpy as jnp


def rounded(x, dtype):
  """x at ``dtype``'s precision; one-byte floats are carried in bfloat16."""
  x = x.astype(dtype)
  return x.astype(jnp.bfloat16) if jnp.dtype(dtype).itemsize == 1 else x


def rms_norm(x, scale, eps):
  x = x.astype(jnp.float32)
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_frequencies(settings):
  """theta^(-2i/D), i < D/2, D the rotary width."""
  dim, theta = settings['qk_rope_head_dim'], float(settings['rope_theta'])
  return jnp.asarray([theta ** (-2.0 * i / dim) for i in range(dim // 2)],
                     jnp.float32)


def attention_scale(settings):
  s = settings
  width = s['qk_nope_head_dim'] + (
      s['qk_rope_head_dim'] if s['scale_width'] == 'key' else 0)
  return 1.0 / math.sqrt(width)


def rope(x, frequency):
  """[L, H, D] at positions 0..L-1: dimension i pairs with i + D/2."""
  length, _, d = x.shape
  angle = jnp.arange(length, dtype=jnp.float32)[:, None] * frequency[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b = x[..., :d // 2], x[..., d // 2:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, scale, settings):
  """[L, H, Dk] x [L, H, Dk] x [L, H, Dv] -> [L, H, Dv]; dense scores,
  ``query_block`` rows at a time, each block under ``jax.checkpoint``."""
  length, heads, d = q.shape
  block = min(settings['query_block'], length)
  while length % block:
    block -= 1
  columns = jnp.arange(length)[None, :]

  def rows(args):
    q_rows, first = args
    mask = columns <= (first + jnp.arange(block))[:, None]
    scores = jnp.einsum('qhd,khd->hqk', q_rows, k) * scale
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum('hqk,khd->qhd', weights, v)

  out = jax.lax.map(jax.checkpoint(rows),
                    (q.reshape(length // block, block, heads, d),
                     jnp.arange(0, length, block)))
  return out.reshape(length, heads, v.shape[-1])


def latent_attention(p, h, settings, dtype):
  """Latent attention on one sequence: h [L, hidden]."""
  s = settings
  length = h.shape[0]
  heads, nope, rope_width, value = (s['num_heads'], s['qk_nope_head_dim'],
                                    s['qk_rope_head_dim'], s['v_head_dim'])
  cast = lambda w: rounded(w, dtype)
  c_q = rounded(rms_norm(h @ cast(p['q_a']['kernel']),
                         p['q_a_norm']['scale'], s['eps']), dtype)
  q = (c_q @ cast(p['q_b']['kernel'])).reshape(length, heads,
                                               nope + rope_width)
  compressed = h @ cast(p['kv_a']['kernel'])
  c_kv = rms_norm(compressed[:, :s['kv_lora_rank']], p['kv_a_norm']['scale'],
                  s['eps'])
  kv = (rounded(c_kv, dtype) @ cast(p['kv_b']['kernel'])).reshape(
      length, heads, nope + value)
  frequency = rotary_frequencies(s)
  q_pe = rounded(rope(q[..., nope:], frequency), dtype)
  k_pe = rounded(rope(compressed[:, None, s['kv_lora_rank']:], frequency),
                 dtype)
  q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
  k = jnp.concatenate(
      [kv[..., :nope], jnp.broadcast_to(k_pe, (length, heads, rope_width))],
      axis=-1)
  a = rounded(causal_attention(q, k, kv[..., nope:], attention_scale(s), s),
              dtype)
  return a.reshape(length, -1) @ cast(p['out']['kernel'])


def swiglu(u, w_gate, w_up, w_down, dtype):
  cast = lambda w: rounded(w, dtype)
  return (jax.nn.silu(u @ cast(w_gate)) * (u @ cast(w_up))) @ cast(w_down)


def routing_weights(router_logits, bias, settings):
  """[N, E]: every expert's sigmoid score, the top_k largest of score + bias
  chosen, weighed by routed_scaling x their scores over (their sum + 1e-6);
  zero elsewhere."""
  s = settings
  scores = jax.nn.sigmoid(router_logits)
  left = scores + bias
  chosen = jnp.zeros_like(scores)
  for _ in range(s['top_k']):
    pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), left.shape[-1],
                          dtype=scores.dtype)
    chosen = chosen + pick
    left = jnp.where(pick > 0, -jnp.inf, left)
  kept = scores * chosen
  return s['routed_scaling'] * kept / (
      jnp.sum(kept, axis=-1, keepdims=True) + 1e-6)


def experts(p, u, weights, settings, dtype):
  """Sum over the experts held of weight x expert(u): [N, hidden] f32."""
  first, count = settings['experts_held']

  def add(y, expert):
    w_gate, w_up, w_down, weight = expert
    out = swiglu(u, w_gate, w_up, w_down, dtype)
    return y + weight[:, None] * out.astype(jnp.float32), None

  y, _ = jax.lax.scan(
      jax.checkpoint(add), jnp.zeros(u.shape, jnp.float32),
      (p['w_gate'], p['w_up'], p['w_down'],
       weights[:, first:first + count].T))
  return y


def layer(p, x, bias, dense, settings, dtype):
  """One block on one sequence: x [L, C] f32."""
  s = settings
  h = rounded(rms_norm(x, p['norm_attn']['scale'], s['eps']), dtype)
  x = x + rounded(latent_attention(p['attn'], h, s, dtype),
                  dtype).astype(jnp.float32)
  u = rms_norm(x, p['norm_moe']['scale'], s['eps'])
  if dense:
    mlp = p['mlp']
    y = swiglu(rounded(u, dtype), mlp['w1']['kernel'], mlp['w3']['kernel'],
               mlp['w2']['kernel'], dtype).astype(jnp.float32)
  else:
    weights = routing_weights(u @ p['router']['kernel'], bias, s)
    y = experts(p['moe'], rounded(u, dtype), weights, s, dtype)
    if s['shared_expert']:
      shared = p['shared_expert']
      y = y + swiglu(rounded(u, dtype), shared['w1']['kernel'],
                     shared['w3']['kernel'], shared['w2']['kernel'],
                     dtype).astype(jnp.float32)
  return x + rounded(y, dtype).astype(jnp.float32)


def mtp_input(p, embedding, hidden, tokens, settings, dtype):
  """m [L, C] of the multi-token-prediction module: W_eh over the normed
  embedding of the following token and the normed trunk output."""
  s = settings
  following = embedding[jnp.roll(tokens, -s['mtp_embedding_shift'])].astype(
      jnp.float32)
  e = (rms_norm(following, p['enorm']['scale'], s['eps']) if s['enorm']
       else following)
  h = rms_norm(hidden, p['hnorm']['scale'], s['eps']) if s['hnorm'] else hidden
  halves = [e, h] if s['mtp_concat'] == 'embedding_first' else [h, e]
  return rounded(jnp.concatenate(halves, axis=-1), dtype) @ rounded(
      p['eh_proj']['kernel'], dtype)


def rows_loss_sum(hidden, head, targets, weights, settings):
  """Sum of weights x the cross-entropy of ``hidden`` @ ``head`` against
  ``targets``, ``head_block`` rows at a time."""
  size = min(settings['head_block'], hidden.shape[0])
  total = jnp.float32(0)
  for start in range(0, hidden.shape[0], size):
    total = total + jax.checkpoint(_rows_loss)(
        hidden[start:start + size], head, targets[start:start + size],
        weights[start:start + size])
  return total


def _rows_loss(hidden, head, targets, weights):
  logits = (hidden @ head).astype(jnp.float32)
  log_z = jax.nn.logsumexp(logits, axis=-1)
  picked = logits[jnp.arange(logits.shape[0]), targets]
  return jnp.sum(weights * (log_z - picked))


def sequence_losses(params, tokens, settings, dtype=jnp.float32):
  """(sum over positions 0..L-2 of the next-token cross-entropy, sum over
  positions 0..L-3 of the MTP's against t_{i + mtp_target_shift}) of one
  sequence, in float32."""
  s = settings
  length = tokens.shape[0]
  x = params['embedding'][tokens].astype(jnp.float32)
  biases = iter(s.get('router_bias') or ())
  static = _frozen({k: v for k, v in s.items() if k != 'router_bias'})
  run = jax.checkpoint(layer, static_argnums=(3, 4, 5))

  def bias():
    if s.get('router_bias'):
      return jnp.asarray(next(biases), jnp.float32)
    return jnp.zeros((s['num_experts'],), jnp.float32)

  for index in range(len(s['window_layers'])):
    dense = index < s['num_dense_layers']
    x = run(params['block{}'.format(index)], x,
            None if dense else bias(), dense, static, dtype)
  hidden = rms_norm(x, params['norm_final']['scale'], s['eps'])
  head = rounded(params['head'], dtype)
  positions = jnp.arange(length)
  main = rows_loss_sum(rounded(hidden, dtype), head, jnp.roll(tokens, -1),
                       (positions < length - 1).astype(jnp.float32), s)
  mtp = params['mtp']
  m = mtp_input(mtp, params['embedding'], hidden if s['mtp_reads'] ==
                'normed' else x, tokens, s, dtype)
  g = run(mtp['block'], m.astype(jnp.float32), bias(), False, static, dtype)
  norm = (mtp['shared_head_norm'] if s['mtp_head_norm'] == 'own' else
          params['norm_final'])
  ahead = rms_norm(g, norm['scale'], s['eps'])
  second = rows_loss_sum(rounded(ahead, dtype), head,
                         jnp.roll(tokens, -s['mtp_target_shift']),
                         (positions < length - 2).astype(jnp.float32), s)
  return main, second


class _frozen(dict):
  """A dict ``jax.checkpoint`` can take as a static argument."""

  def __hash__(self):
    return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def loss(params, tokens, settings, dtype=jnp.float32):
  """The step's loss on ``tokens`` [B, L]: the next-token loss, a mean over
  the B x (L - 1) counted positions, plus ``mtp_weight`` x the MTP's, a mean
  over B x (L - 2), one sequence after another."""
  b, length = tokens.shape
  with jax.default_matmul_precision('highest'):
    sums = [sequence_losses(params, tokens[i], settings, dtype)
            for i in range(b)]
  main = sum(m for m, _ in sums) / (b * (length - 1))
  mtp = sum(t for _, t in sums) / (b * (length - 2))
  return main + settings['mtp_weight'] * mtp
