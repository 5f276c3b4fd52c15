"""The plain reference of the Xing4.0-style backbone: ``jax.numpy`` only.

Float32 under ``jax.default_matmul_precision('highest')``, written from the
equations of ``tensor2robot_tpu/research/xing/README.md`` line by line; it
imports nothing of the program and reads the program's PARAMETER TREE
(data, not code), so that ``jax.grad`` of it has a leaf for every leaf of
the program's.

  streams    the state X [L, n, C] of one sequence; n copies of the
             embedding row to start; per sublayer x' = vec(X) / rms, z = x'
             phi, pre = sigmoid(a_pre z + b), post = 2 sigmoid(a_post z + b),
             res = SK(exp(clip(a_res z + b, -30, 30))) with SK a loop of
             ``sinkhorn_iters`` (rows, then columns, + eps in each sum);
             h = sum_j pre_j X_j, X' = res X + post f
  attention  latent: c_q = rmsnorm(h W_qa), q = c_q W_qb; [c_kv ; k_pe] =
             h W_kva, [k_nope ; v] = rmsnorm(c_kv) W_kvb; ONE rotary key for
             all heads at YaRN's frequencies (computed here from the
             published rope_scaling); scale = mscale^2 / sqrt(192); dense
             causal softmax over blocks of query rows
  experts    a sigmoid router, the top_k of score + bias chosen, weighed by
             the scores over their sum + 1e-6 times ``routed_scaling``; the
             held experts as ONE scanned body (each over every position,
             weighted by the position's weight for it, 0 where it was not
             chosen; pairs of absent experts are left out); the shared
             expert over every position, unweighted
  head       untied, over the RMS norm of the streams' sum, in blocks of rows

``settings`` is a plain dict: hidden_size, num_heads, q_lora_rank,
kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rope_theta,
rope_scaling (the published dict), dense_dim, expert_dim, shared_expert_dim,
num_experts, experts_held (first, count), top_k, num_dense_layers,
window_layers (one False a layer held: the driver logs its length),
routed_scaling, streams, sinkhorn_iters, stream_eps, clamp, eps, vocab_rows,
query_block and head_block (rows at a time, memory only), and seven that
name the mathematics and have one right value each, so that a test or a chip
script can compute ANOTHER model and see the comparison refuse it:
``sinkhorn`` ('sinkhorn'; 'row_softmax' makes res a softmax over each row),
``post_factor`` (2), ``mscale_squared`` (True; False drops mscale^2 from the
scale), ``yarn`` (True; False rotates at the plain frequencies),
``shared_expert`` (True; False leaves it out), ``k_pe`` ('shared'; 'per_head'
gives head h the rotary key rolled by 2 h of its 64 dimensions: a key of its
own) and ``kv_norm`` (True; False leaves out the norm of c_kv). The router's
bias is state: ``settings['router_bias']`` (one [num_experts] row an expert
layer) where a test has one, zeros otherwise, the published initial value.
``dtype`` below float32 gives the reference at a lower precision: bfloat16
computes in bfloat16; a one-byte float (``jnp.float8_e4m3fn``) rounds every
weight and activation to it and multiplies in bfloat16.
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
  """The 32 frequencies of the 64 rotary dimensions: YaRN's from the
  published rope_scaling (a ramp between the dimensions low and high over
  which f_i / factor gives way to f_i), or theta^(-2i/64) plain."""
  s = settings
  dim, theta = s['qk_rope_head_dim'], float(s['rope_theta'])
  plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
  if not s['yarn']:
    return jnp.asarray(plain, jnp.float32)
  y = s['rope_scaling']
  original = y['original_max_position_embeddings']

  def turns(rotations):
    return dim * math.log(original / (rotations * 2 * math.pi)) / (
        2 * math.log(theta))

  low = max(math.floor(turns(y['beta_fast'])), 0)
  high = min(math.ceil(turns(y['beta_slow'])), dim - 1)
  out = []
  for i, f in enumerate(plain):
    ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
    out.append(f / y['factor'] * ramp + f * (1 - ramp))
  return jnp.asarray(out, jnp.float32)


def attention_scale(settings):
  s = settings
  width = s['qk_nope_head_dim'] + s['qk_rope_head_dim']
  factor, mscale_all = (s['rope_scaling']['factor'],
                        s['rope_scaling']['mscale_all_dim'])
  mscale = 0.1 * mscale_all * math.log(factor) + 1.0
  return (mscale * mscale if s['mscale_squared'] else 1.0) / math.sqrt(width)


def rope(x, frequency):
  """[L, H, D] at positions 0..L-1: dimension i pairs with i + D/2."""
  length, _, d = x.shape
  angle = jnp.arange(length, dtype=jnp.float32)[:, None] * frequency[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b = x[..., :d // 2], x[..., d // 2:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, scale, settings):
  """[L, H, Dk] x [L, H, Dk] x [L, H, Dv] -> [L, H, Dv]; dense scores,
  ``query_block`` rows at a time."""
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
  c_kv = compressed[:, :s['kv_lora_rank']]
  if s['kv_norm']:
    c_kv = rms_norm(c_kv, p['kv_a_norm']['scale'], s['eps'])
  kv = (rounded(c_kv, dtype) @ cast(p['kv_b']['kernel'])).reshape(
      length, heads, nope + value)
  frequency = rotary_frequencies(s)
  q_pe = rounded(rope(q[..., nope:], frequency), dtype)
  k_pe = rounded(rope(compressed[:, None, s['kv_lora_rank']:], frequency),
                 dtype)
  if s['k_pe'] == 'shared':
    k_pe = jnp.broadcast_to(k_pe, (length, heads, rope_width))
  else:
    k_pe = jnp.stack([jnp.roll(k_pe[:, 0], 2 * head, axis=-1)
                      for head in range(heads)], axis=1)
  q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
  k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
  a = rounded(causal_attention(q, k, kv[..., nope:], attention_scale(s), s),
              dtype)
  return a.reshape(length, -1) @ cast(p['out']['kernel'])


def stream_maps(p, x, settings, dtype):
  """(pre [L, n], post [L, n], res [L, n, n]) of the state x [L, n, C]."""
  s = settings
  length, n, _ = x.shape
  flat = x.reshape(length, -1)
  normed = rounded(flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1,
                                            keepdims=True) + s['stream_eps']),
                   dtype)
  project = lambda name: (normed @ rounded(p[name], dtype)).astype(
      jnp.float32)
  pre = jax.nn.sigmoid(p['alpha_pre'] * project('phi_pre') + p['b_pre'])
  post = s['post_factor'] * jax.nn.sigmoid(
      p['alpha_post'] * project('phi_post') + p['b_post'])
  logits = jnp.clip(p['alpha_res'] * project('phi_res') + p['b_res'],
                    -s['clamp'], s['clamp']).reshape(length, n, n)
  if s['sinkhorn'] == 'row_softmax':
    return pre, post, jax.nn.softmax(logits, axis=-1)
  eps = s['stream_eps']

  def iteration(_, m):
    m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
    return m / (jnp.sum(m, axis=1, keepdims=True) + eps)

  return pre, post, jax.lax.fori_loop(0, s['sinkhorn_iters'], iteration,
                                      jnp.exp(logits))


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
  """One block on one sequence: x [L, n, C] f32."""
  s = settings
  pre, post, res = stream_maps(p['hc_attn'], x, s, dtype)
  h = jnp.einsum('lj,ljc->lc', pre, x)
  f = latent_attention(p['attn'], rounded(rms_norm(
      h, p['norm_attn']['scale'], s['eps']), dtype), s, dtype)
  x = jnp.einsum('lij,ljc->lic', res, x) + post[:, :, None] * rounded(
      f, dtype).astype(jnp.float32)[:, None, :]
  pre, post, res = stream_maps(p['hc_ff'], x, s, dtype)
  u = rms_norm(jnp.einsum('lj,ljc->lc', pre, x), p['norm_moe']['scale'],
               s['eps'])
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
  return jnp.einsum('lij,ljc->lic', res, x) + post[:, :, None] * rounded(
      y, dtype).astype(jnp.float32)[:, None, :]


def sequence_loss(params, tokens, settings, dtype=jnp.float32):
  """Sum over positions 0..L-2 of one sequence of the cross-entropy of the
  position's logits against the NEXT token, in float32."""
  s = settings
  length = tokens.shape[0]
  row = params['embedding'][tokens].astype(jnp.float32)
  x = jnp.broadcast_to(row[:, None, :], (length, s['streams'], row.shape[-1]))
  biases = iter(s.get('router_bias') or ())
  static = _frozen({k: v for k, v in s.items() if k != 'router_bias'})
  for index in range(len(s['window_layers'])):
    dense = index < s['num_dense_layers']
    bias = jnp.zeros((s['num_experts'],), jnp.float32)
    if not dense and s.get('router_bias'):
      bias = jnp.asarray(next(biases), jnp.float32)
    x = jax.checkpoint(layer, static_argnums=(3, 4, 5))(
        params['block{}'.format(index)], x, bias, dense, static, dtype)
  hidden = rounded(rms_norm(jnp.sum(x, axis=1),
                            params['norm_final']['scale'], s['eps']), dtype)
  head = rounded(params['head'], dtype)
  targets = jnp.roll(tokens, -1)
  counted = (jnp.arange(length) < length - 1).astype(jnp.float32)
  size = min(s['head_block'], length)
  total = jnp.float32(0)
  for start in range(0, length, size):
    total = total + jax.checkpoint(_rows_loss)(
        hidden[start:start + size], head, targets[start:start + size],
        counted[start:start + size])
  return total


def _rows_loss(hidden, head, targets, weights):
  logits = (hidden @ head).astype(jnp.float32)
  log_z = jax.nn.logsumexp(logits, axis=-1)
  picked = logits[jnp.arange(logits.shape[0]), targets]
  return jnp.sum(weights * (log_z - picked))


class _frozen(dict):
  """A dict ``jax.checkpoint`` can take as a static argument."""

  def __hash__(self):
    return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def loss(params, tokens, settings, dtype=jnp.float32):
  """The next-token loss of ``tokens`` [B, L]: mean over the B x (L - 1)
  counted positions, one sequence after another."""
  with jax.default_matmul_precision('highest'):
    total = sum(sequence_loss(params, tokens[b], settings, dtype)
                for b in range(tokens.shape[0]))
  return total / (tokens.shape[0] * (tokens.shape[1] - 1))
