"""The plain reference of the SmallThinker-style backbone: ``jax.numpy`` only.

Float32 under ``jax.default_matmul_precision('highest')``, dense masked
attention over blocks of query rows, experts by a loop (``lax.scan``) over the
experts held with a 0/1 mask, no kernels, no sorting of pairs, no rematerialisation
beyond ``jax.checkpoint``; the loss, and ``jax.grad`` of it for gradients.
It follows the equations of ``tensor2robot_tpu/research/smallthinker/
README.md`` line by line and shares no function with the program's layers;
it reads the program's PARAMETER TREE (data, not code), so that ``jax.grad``
of it has a leaf for every leaf of the program's. The one copy lives here,
under ``benchmark/`` (the package's tests import it from here): the
benchmark's check travels with the benchmark's files and does not move with
the program unseen.

``settings`` is a plain dict: num_heads, num_kv_heads, head_dim, top_k,
experts_held (first, count), window, rope_theta, eps, window_layers,
rope_layers (one bool a layer), query_block and head_block (rows at a time,
memory only). ``dtype`` below float32 gives the reference at a lower
precision, which is what the benchmark's tolerances have to refuse: bfloat16
computes in bfloat16; a one-byte float (``jnp.float8_e4m3fn``) rounds every
weight and activation to it and multiplies in bfloat16.
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


def rotate_half(x, theta):
  """Rotary positions of [L, H, D]: dimension i pairs with i + D/2."""
  length, _, d = x.shape
  half = d // 2
  frequency = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
  angle = jnp.arange(length, dtype=jnp.float32)[:, None] * frequency[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window, query_block):
  """[L, Hq, D] x [L, Hkv, D] -> [L, Hq, D]; query head n reads k/v head
  n // (Hq / Hkv); row i sees columns j <= i and, with a window,
  i - j < window. Dense scores, ``query_block`` rows at a time."""
  length, heads, d = q.shape
  group = heads // k.shape[1]
  block = min(query_block, length)
  while length % block:
    block -= 1
  columns = jnp.arange(length)[None, :]

  def rows(args):
    q_rows, first = args
    i = first + jnp.arange(block)[:, None]
    allowed = columns <= i
    if window is not None:
      allowed = allowed & (i - columns < window)
    # [kv head, query head of its group, row, column]
    grouped = q_rows.reshape(block, heads // group, group, d)
    scores = jnp.einsum('qngd,knd->ngqk', grouped, k) / jnp.sqrt(
        jnp.float32(d))
    scores = jnp.where(allowed, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum('ngqk,knd->qngd', weights, v).reshape(block, heads, d)

  # Checkpointed: the backward pass forms a block's scores again instead of
  # keeping every block's [heads, block, L] scores.
  out = jax.lax.map(jax.checkpoint(rows),
                    (q.reshape(length // block, block, heads, d),
                     jnp.arange(0, length, block)))
  return out.reshape(length, heads, d)


def routing_weights(router_logits, top_k):
  """[T, E]: softmax over all experts, the top_k largest kept and
  renormalised, zero elsewhere. ``top_k`` rounds of take-the-largest."""
  probabilities = jax.nn.softmax(router_logits, axis=-1)
  left = probabilities
  chosen = jnp.zeros_like(probabilities)
  for _ in range(top_k):
    best = jnp.argmax(left, axis=-1)
    pick = jax.nn.one_hot(best, left.shape[-1], dtype=left.dtype)
    chosen = chosen + pick
    left = jnp.where(pick > 0, -1.0, left)
  kept = probabilities * chosen
  return kept / jnp.sum(kept, axis=-1, keepdims=True)


def block(p, x, settings, windowed, rotary, dtype):
  """One layer on one sequence: x [L, hidden] -> [L, hidden]."""
  s = settings
  length = x.shape[0]
  cast = lambda w: rounded(w, dtype)
  router_logits = x.astype(jnp.float32) @ p['router']['kernel']
  h = rounded(rms_norm(x, p['norm_attn']['scale'], s['eps']), dtype)
  q = (h @ cast(p['attn']['q']['kernel'])).reshape(
      length, s['num_heads'], s['head_dim'])
  k = (h @ cast(p['attn']['k']['kernel'])).reshape(
      length, s['num_kv_heads'], s['head_dim'])
  v = (h @ cast(p['attn']['v']['kernel'])).reshape(
      length, s['num_kv_heads'], s['head_dim'])
  if rotary:
    q = rounded(rotate_half(q, s['rope_theta']), dtype)
    k = rounded(rotate_half(k, s['rope_theta']), dtype)
  a = rounded(attention(q, k, v, s['window'] if windowed else None,
                        s['query_block']), dtype)
  x1 = x + a.reshape(length, -1) @ cast(p['attn']['out']['kernel'])
  u = rounded(rms_norm(x1, p['norm_moe']['scale'], s['eps']), dtype)
  weights = routing_weights(router_logits, s['top_k'])
  first, count = s['experts_held']

  def add_expert(y, expert):
    w_gate, w_up, w_down, weight = expert
    out = (jnp.maximum(u @ cast(w_gate), 0) * (u @ cast(w_up))) @ cast(w_down)
    return y + weight[:, None] * out, None

  # One held expert after another, each over EVERY token, weighted by the
  # token's routing weight for it (0 where the token did not choose it).
  y, _ = jax.lax.scan(
      jax.checkpoint(add_expert), jnp.zeros(x.shape, jnp.float32),
      (p['moe']['w_gate'], p['moe']['w_up'], p['moe']['w_down'],
       weights[:, first:first + count].T))
  return x1 + y.astype(x.dtype)


def sequence_loss(params, tokens, settings, dtype=jnp.float32):
  """Sum over positions 0..L-2 of one sequence of the next token's
  cross-entropy, in float32."""
  x = rounded(params['embedding'][tokens], dtype)
  for layer, (windowed, rotary) in enumerate(
      zip(settings['window_layers'], settings['rope_layers'])):
    x = jax.checkpoint(block, static_argnums=(2, 3, 4, 5))(
        params['block{}'.format(layer)], x, _frozen(settings), windowed,
        rotary, dtype)
  hidden = rms_norm(x, params['norm_final']['scale'], settings['eps'])
  hidden, targets = rounded(hidden[:-1], dtype), tokens[1:]
  head = rounded(params['head'], dtype)
  rows = hidden.shape[0]
  size = min(settings['head_block'], rows)
  total = jnp.float32(0)
  for start in range(0, rows, size):
    total = total + jax.checkpoint(_rows_loss)(
        hidden[start:start + size], head, targets[start:start + size])
  return total


def _rows_loss(hidden, head, targets):
  logits = (hidden @ head).astype(jnp.float32)
  log_z = jax.nn.logsumexp(logits, axis=-1)
  return jnp.sum(log_z - logits[jnp.arange(logits.shape[0]), targets])


class _frozen(dict):
  """A dict ``jax.checkpoint`` can take as a static argument."""

  def __hash__(self):
    return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def loss(params, tokens, settings, dtype=jnp.float32):
  """Mean next-token cross-entropy over positions 0..L-2 of every sequence
  of ``tokens`` [B, L], one sequence after another."""
  with jax.default_matmul_precision('highest'):
    total = sum(sequence_loss(params, tokens[b], settings, dtype)
                for b in range(tokens.shape[0]))
  return total / (tokens.shape[0] * (tokens.shape[1] - 1))
