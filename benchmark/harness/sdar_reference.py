"""The plain reference of the SDAR-style block-diffusion backbone: ``jax.numpy``
and ``jax.random`` only.

Float32 under ``jax.default_matmul_precision('highest')``, the [2L, 2L] mask
built from indices, dense masked softmax attention over blocks of query rows,
the experts as a plain loop over the experts held (each over every position,
weighted by the position's routing weight for it, 0 where it was not chosen;
pairs of absent experts are left out), no kernels, no sorting of pairs, no
rematerialisation beyond ``jax.checkpoint``; the loss, and ``jax.grad`` of it
for gradients. It follows the equations of ``tensor2robot_tpu/research/sdar/
README.md`` line by line and imports nothing of the program; it reads the
program's PARAMETER TREE (data, not code), so that ``jax.grad`` of it has a
leaf for every leaf of the program's.

The corruption is drawn here again, from ``settings`` and the row alone: the
trainer's seed, the step (0), the chain of ``jax.random`` calls that leads
from ``Trainer.train``'s base rng to the rng the model's ``loss_fn`` is given
(``first_step_rng``: the same few calls ``harness/reference.py::train_loss``
makes), and a checksum of the row's own ids folded in.

Departures from the published description (the README has their sources):
block length 4 and the noise schedule (t uniform on [eps, 1] per block) are
not in config.json; q/k norm is Qwen3's and has no key there; the mask id is
the last vocabulary row held; packed documents attend across their
boundaries; only the experts and vocabulary rows ``settings`` says are held
are computed.

``settings`` is a plain dict: hidden_size, num_heads, num_kv_heads, head_dim,
expert_dim, num_experts, experts_held (first, count), top_k, rope_theta, eps,
vocab_rows, window_layers (one False a layer: no layer has a window; its
length is the depth), block_length, noise_eps, mask_token_id, trainer_seed,
query_block and head_block (rows at a time, memory only), and five that name
the mathematics and have one right value each, so that a test or a chip
script can compute ANOTHER model and see the comparison refuse it: ``mask``
('block_diffusion'; 'causal' is plain causal over the 2L positions,
'clean_token_causal' lets a clean token see the clean tokens at or before
it and no later one of its block), ``qk_norm`` (True), ``gate`` ('silu';
'relu'), ``loss_weight`` ('1/t'; '1'), ``loss_shift`` (0; 1 compares
position i's logits with token i + 1). ``dtype`` below float32 gives the
reference at a lower precision, which the benchmark's tolerances have to
refuse: bfloat16 computes in bfloat16; a one-byte float
(``jnp.float8_e4m3fn``) rounds every weight and activation to it and
multiplies in bfloat16.
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


def first_step_rng(settings):
  """The rng the model's ``loss_fn`` is given in the trainer's first step."""
  base = jax.random.PRNGKey(settings['trainer_seed'] + 1)
  rng = jax.random.fold_in(base, settings.get('step', 0))
  _, step_rng = jax.random.split(rng)       # the other half: the preprocessor
  net_rng, _ = jax.random.split(step_rng)
  return net_rng


def corruption(tokens, settings):
  """(noised [L], t by block [L / B], masked [L] bool) of one clean row."""
  length, block = tokens.shape[0], settings['block_length']
  checksum = jnp.sum(tokens.astype(jnp.uint32) *
                     jnp.arange(1, length + 1, dtype=jnp.uint32),
                     dtype=jnp.uint32)
  level_key, token_key = jax.random.split(
      jax.random.fold_in(first_step_rng(settings), checksum))
  eps = settings['noise_eps']
  t = eps + (1.0 - eps) * jax.random.uniform(level_key, (length // block,),
                                             jnp.float32)
  masked = jax.random.uniform(token_key, (length,),
                              jnp.float32) < jnp.repeat(t, block)
  return jnp.where(masked, settings['mask_token_id'], tokens), t, masked


def allowed(rows, length, settings):
  """[len(rows), 2L] bool: which of the 2L positions [noised ; clean] each
  of the positions ``rows`` may attend to."""
  columns = jnp.arange(2 * length)[None, :]
  rows = rows[:, None]
  kind = settings['mask']
  if kind == 'causal':
    return columns <= rows
  block = settings['block_length']
  row_noised, column_noised = rows < length, columns < length
  row_at, column_at = rows % length, columns % length
  row_block, column_block = row_at // block, column_at // block
  clean_to_clean = (column_at <= row_at if kind == 'clean_token_causal'
                    else column_block <= row_block)
  return jnp.where(
      row_noised,
      jnp.where(column_noised, column_block == row_block,
                column_block < row_block),
      jnp.logical_and(jnp.logical_not(column_noised), clean_to_clean))


def rope(x, positions, theta):
  """Rotary positions of [N, H, D] at ``positions`` [N]: dimension i pairs
  with i + D/2."""
  d = x.shape[-1]
  half = d // 2
  frequency = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
  angle = positions.astype(jnp.float32)[:, None] * frequency[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, length, settings):
  """[2L, Hq, D] x [2L, Hkv, D] -> [2L, Hq, D] under the mask; query head n
  reads k/v head n // (Hq / Hkv). Dense scores, ``query_block`` rows at a
  time."""
  total, heads, d = q.shape
  group = heads // k.shape[1]
  block = min(settings['query_block'], total)
  while total % block:
    block -= 1

  def rows(args):
    q_rows, first = args
    mask = allowed(first + jnp.arange(block), length, settings)
    grouped = q_rows.reshape(block, heads // group, group, d)
    scores = jnp.einsum('qngd,knd->ngqk', grouped, k) / jnp.sqrt(
        jnp.float32(d))
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum('ngqk,knd->qngd', weights, v).reshape(block, heads, d)

  out = jax.lax.map(jax.checkpoint(rows),
                    (q.reshape(total // block, block, heads, d),
                     jnp.arange(0, total, block)))
  return out.reshape(total, heads, d)


def routing_weights(router_logits, top_k):
  """[N, E]: softmax over all experts, the top_k largest kept and
  renormalised to sum 1, zero elsewhere."""
  probabilities = jax.nn.softmax(router_logits, axis=-1)
  left = probabilities
  chosen = jnp.zeros_like(probabilities)
  for _ in range(top_k):
    pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), left.shape[-1],
                          dtype=left.dtype)
    chosen = chosen + pick
    left = jnp.where(pick > 0, -1.0, left)
  kept = probabilities * chosen
  return kept / jnp.sum(kept, axis=-1, keepdims=True)


def expert(u, weight, w_gate, w_up, w_down, gate, dtype):
  """One expert over every position, weighted: [N, hidden] f32."""
  cast = lambda w: rounded(w, dtype)
  activated = u @ cast(w_gate)
  activated = (jax.nn.silu(activated) if gate == 'silu'
               else jnp.maximum(activated, 0))
  out = (activated * (u @ cast(w_up))) @ cast(w_down)
  return weight[:, None] * out.astype(jnp.float32)


def layer(p, x, positions, length, settings, dtype):
  """One block on one sequence's 2L positions: x [2L, hidden]."""
  s = settings
  total = x.shape[0]
  cast = lambda w: rounded(w, dtype)
  h = rounded(rms_norm(x, p['norm_attn']['scale'], s['eps']), dtype)
  q = (h @ cast(p['attn']['q']['kernel'])).reshape(
      total, s['num_heads'], s['head_dim'])
  k = (h @ cast(p['attn']['k']['kernel'])).reshape(
      total, s['num_kv_heads'], s['head_dim'])
  v = (h @ cast(p['attn']['v']['kernel'])).reshape(
      total, s['num_kv_heads'], s['head_dim'])
  if s['qk_norm']:
    q = rounded(rms_norm(q, p['attn']['q_norm']['scale'], s['eps']), dtype)
    k = rounded(rms_norm(k, p['attn']['k_norm']['scale'], s['eps']), dtype)
  q = rounded(rope(q, positions, s['rope_theta']), dtype)
  k = rounded(rope(k, positions, s['rope_theta']), dtype)
  a = rounded(attention(q, k, v, length, s), dtype)
  x1 = x + a.reshape(total, -1) @ cast(p['attn']['out']['kernel'])
  u = rms_norm(x1, p['norm_moe']['scale'], s['eps'])
  weights = routing_weights(u @ p['router']['kernel'], s['top_k'])
  u = rounded(u, dtype)
  first, count = s['experts_held']
  y = jnp.zeros(x.shape, jnp.float32)
  for e in range(count):
    y = y + jax.checkpoint(expert, static_argnums=(5, 6))(
        u, weights[:, first + e], p['moe']['w_gate'][e], p['moe']['w_up'][e],
        p['moe']['w_down'][e], s['gate'], dtype)
  return x1 + y.astype(x.dtype)


def sequence_loss(params, tokens, settings, dtype=jnp.float32):
  """Sum over the masked positions of one sequence of the clean token's
  cross-entropy at the noised position, weighted by 1 / t, in float32."""
  length = tokens.shape[0]
  noised, t, masked = corruption(tokens, settings)
  x = rounded(params['embedding'][jnp.concatenate([noised, tokens])], dtype)
  positions = jnp.concatenate([jnp.arange(length), jnp.arange(length)])
  for index in range(len(settings['window_layers'])):
    x = jax.checkpoint(layer, static_argnums=(3, 4, 5))(
        params['block{}'.format(index)], x, positions, length,
        _frozen(settings), dtype)
  hidden = rms_norm(x[:length], params['norm_final']['scale'],
                    settings['eps'])
  hidden, head = rounded(hidden, dtype), rounded(params['head'], dtype)
  weights = masked.astype(jnp.float32)
  if settings['loss_weight'] == '1/t':
    weights = weights / jnp.repeat(t, settings['block_length'])
  targets = jnp.roll(tokens, -settings['loss_shift'])
  size = min(settings['head_block'], length)
  total = jnp.float32(0)
  for start in range(0, length, size):
    total = total + jax.checkpoint(_rows_loss)(
        hidden[start:start + size], head, targets[start:start + size],
        weights[start:start + size])
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
  """The block-diffusion loss of ``tokens`` [B, L]: (1 / L) x the weighted
  sum over each sequence's masked positions, mean over the sequences, one
  sequence after another."""
  with jax.default_matmul_precision('highest'):
    total = sum(sequence_loss(params, tokens[b], settings, dtype)
                for b in range(tokens.shape[0]))
  return total / (tokens.shape[0] * tokens.shape[1])
