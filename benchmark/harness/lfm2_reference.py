"""The plain reference of the LFM2-style hybrid backbone: ``jax.numpy`` only.

Float32 under ``jax.default_matmul_precision('highest')``; the gated short
convolution as three shifted products; dense causal softmax attention over
blocks of query rows; the router as a sigmoid, a selection by score + bias
and a renormalisation written out; the experts as a loop over the experts
held (``lax.scan``: one body, compiled once; each over every position,
weighted by the position's routing weight for it, 0 where it was not
chosen; pairs of absent experts are left out); the head as the embedding
transposed (tied), in blocks of rows; no kernels, no sorting of pairs, no
rematerialisation beyond ``jax.checkpoint``; the loss, and ``jax.grad`` of
it for gradients. It follows the equations of ``tensor2robot_tpu/research/
lfm2/README.md`` line by line and imports nothing of the program; it reads
the program's PARAMETER TREE (data, not code), so that ``jax.grad`` of it
has a leaf for every leaf of the program's.

The router's bias is STATE, not a parameter, and the benchmark's driver
hands a reference the parameter tree alone: ``settings['router_bias']``
gives it (a list, one [num_experts] row an expert layer, in layer order)
where a test has one; without the key it is zeros, the published initial
value and what the cell's first step starts from.

Departures from the published description (the README has their sources):
the chunk order B, C, X and the tap order are Hugging Face's
``Lfm2ShortConv``; embedding and head are tied; packed documents attend and
convolve across their boundaries; only the experts and vocabulary rows
``settings`` says are held are computed.

``settings`` is a plain dict: hidden_size, num_heads, num_kv_heads, head_dim,
dense_dim, expert_dim, num_experts, experts_held (first, count), top_k,
layer_types (one of 'conv' | 'full_attention' a layer held), num_dense_layers
(how many of them lead with a dense feed-forward), window_layers (one False
a layer: no layer has a window; the driver logs its length), rope_theta,
eps, vocab_rows, query_block and head_block (rows at a time, memory only),
and five that name the mathematics and have one right value each, so that a
test or a chip script can compute ANOTHER model and see the comparison
refuse it: ``taps`` ('causal'; 'reversed' puts the first tap on
the current token), ``c_gate`` (True; False leaves the output gate out),
``router`` ('sigmoid'; 'softmax' scores by a softmax over all experts),
``renormalise`` (True; False weighs by the raw scores), ``qk_norm`` (True).
``dtype`` below float32 gives the reference at a lower precision, which the
benchmark's tolerances have to refuse: bfloat16 computes in bfloat16; a
one-byte float (``jnp.float8_e4m3fn``) rounds every weight and activation
to it and multiplies in bfloat16.
"""

import jax
import jax.numpy as jnp


def rounded(x, dtype):
  """x at ``dtype``'s precision; one-byte floats are carried in bfloat16."""
  x = x.astype(dtype)
  return x.astype(jnp.bfloat16) if jnp.dtype(dtype).itemsize == 1 else x


def rms_norm(x, scale, eps):
  x = x.astype(jnp.float32)
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def shifted(z, back):
  """Row t of the result is row t - back of z [L, d]; zeros before row 0."""
  if back == 0:
    return z
  return jnp.concatenate([jnp.zeros_like(z[:back]), z[:-back]], axis=0)


def short_convolution(p, h, settings, dtype):
  """The gated short convolution on one sequence: h [L, hidden]."""
  d = h.shape[-1]
  cast = lambda w: rounded(w, dtype)
  bcx = h @ cast(p['in_proj']['kernel'])
  b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
  z = (b * x).astype(jnp.float32)
  taps = p['filter'].astype(jnp.float32)             # [d, 3]
  if settings['taps'] == 'reversed':
    taps = taps[:, ::-1]
  count = taps.shape[1]
  conv = sum(taps[:, tap] * shifted(z, count - 1 - tap)
             for tap in range(count))
  y = c.astype(jnp.float32) * conv if settings['c_gate'] else conv
  return rounded(y, dtype) @ cast(p['out_proj']['kernel'])


def rope(x, theta):
  """Rotary positions of [L, H, D] at positions 0..L-1: dimension i pairs
  with i + D/2."""
  length, _, d = x.shape
  half = d // 2
  frequency = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
  angle = jnp.arange(length, dtype=jnp.float32)[:, None] * frequency[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, settings):
  """[L, Hq, D] x [L, Hkv, D] -> [L, Hq, D]; query head n reads k/v head
  n // (Hq / Hkv). Dense scores, ``query_block`` rows at a time."""
  length, heads, d = q.shape
  group = heads // k.shape[1]
  block = min(settings['query_block'], length)
  while length % block:
    block -= 1
  columns = jnp.arange(length)[None, :]

  def rows(args):
    q_rows, first = args
    mask = columns <= (first + jnp.arange(block))[:, None]
    grouped = q_rows.reshape(block, heads // group, group, d)
    scores = jnp.einsum('qngd,knd->ngqk', grouped, k) / jnp.sqrt(
        jnp.float32(d))
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum('ngqk,knd->qngd', weights, v).reshape(block, heads, d)

  out = jax.lax.map(jax.checkpoint(rows),
                    (q.reshape(length // block, block, heads, d),
                     jnp.arange(0, length, block)))
  return out.reshape(length, heads, d)


def attention(p, h, settings, dtype):
  """Grouped-query attention on one sequence: h [L, hidden]."""
  s = settings
  length = h.shape[0]
  cast = lambda w: rounded(w, dtype)
  q = (h @ cast(p['q']['kernel'])).reshape(length, s['num_heads'],
                                           s['head_dim'])
  k = (h @ cast(p['k']['kernel'])).reshape(length, s['num_kv_heads'],
                                           s['head_dim'])
  v = (h @ cast(p['v']['kernel'])).reshape(length, s['num_kv_heads'],
                                           s['head_dim'])
  if s['qk_norm']:
    q = rounded(rms_norm(q, p['q_norm']['scale'], s['eps']), dtype)
    k = rounded(rms_norm(k, p['k_norm']['scale'], s['eps']), dtype)
  q = rounded(rope(q, s['rope_theta']), dtype)
  k = rounded(rope(k, s['rope_theta']), dtype)
  a = rounded(causal_attention(q, k, v, s), dtype)
  return a.reshape(length, -1) @ cast(p['out']['kernel'])


def routing_weights(router_logits, bias, settings):
  """[N, E]: every expert's score (a sigmoid of its own logit), the top_k
  largest of score + bias chosen, their scores over (their sum + 1e-6);
  zero elsewhere."""
  s = settings
  scores = (jax.nn.sigmoid(router_logits) if s['router'] == 'sigmoid'
            else jax.nn.softmax(router_logits, axis=-1))
  left = scores + bias
  chosen = jnp.zeros_like(scores)
  for _ in range(s['top_k']):
    pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), left.shape[-1],
                          dtype=scores.dtype)
    chosen = chosen + pick
    left = jnp.where(pick > 0, -jnp.inf, left)
  kept = scores * chosen
  if s['renormalise']:
    kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-6)
  return kept


def swiglu(u, w_gate, w_up, w_down, dtype):
  cast = lambda w: rounded(w, dtype)
  return (jax.nn.silu(u @ cast(w_gate)) * (u @ cast(w_up))) @ cast(w_down)


def experts(p, u, weights, settings, dtype):
  """Sum over the experts held of weight x expert(u): [N, hidden] f32. One
  loop body for every expert (a scan over the stacked weights)."""
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


def layer(p, x, bias, kind, dense, settings, dtype):
  """One block on one sequence: x [L, hidden]. ``kind`` is the mixer's
  ('conv' | 'full_attention'), ``dense`` whether the feed-forward is the
  dense one, ``bias`` [E] the router's (unused by a dense layer)."""
  s = settings
  h = rounded(rms_norm(x, p['norm_attn']['scale'], s['eps']), dtype)
  if kind == 'conv':
    x1 = x + short_convolution(p['conv'], h, s, dtype)
  else:
    x1 = x + attention(p['attn'], h, s, dtype)
  u = rms_norm(x1, p['norm_moe']['scale'], s['eps'])
  if dense:
    mlp = p['mlp']
    y = swiglu(rounded(u, dtype), mlp['w1']['kernel'], mlp['w3']['kernel'],
               mlp['w2']['kernel'], dtype)
  else:
    weights = routing_weights(u @ p['router']['kernel'], bias, s)
    y = experts(p['moe'], rounded(u, dtype), weights, s, dtype)
  return x1 + y.astype(x.dtype)


def sequence_loss(params, tokens, settings, dtype=jnp.float32):
  """Sum over positions 0..L-2 of one sequence of the cross-entropy of the
  position's logits against the NEXT token, in float32."""
  s = settings
  length = tokens.shape[0]
  x = rounded(params['embedding'][tokens], dtype)
  biases = iter(s.get('router_bias') or ())
  static = _frozen({k: v for k, v in s.items() if k != 'router_bias'})
  for index, kind in enumerate(s['layer_types']):
    dense = index < s['num_dense_layers']
    bias = jnp.zeros((s['num_experts'],), jnp.float32)
    if not dense and s.get('router_bias'):
      bias = jnp.asarray(next(biases), jnp.float32)
    x = jax.checkpoint(layer, static_argnums=(3, 4, 5, 6))(
        params['block{}'.format(index)], x, bias, kind, dense, static, dtype)
  hidden = rounded(rms_norm(x, params['norm_final']['scale'], s['eps']),
                   dtype)
  head = rounded(params['embedding'], dtype).T
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
