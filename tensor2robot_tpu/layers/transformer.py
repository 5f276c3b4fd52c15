"""Transformer layers for sequence-to-action policies (RT-1-style).

The reference's temporal models stop at causal TCNs and dot-product
attention over tiny windows (SNAIL, /root/reference/layers/snail.py:78;
TEC, /root/reference/layers/tec.py:91). This module is the long-context
successor those layers never got: a causal transformer over per-frame
visual tokens whose attention backend scales from a single chip to a
sequence-sharded device mesh:

  * ``attention_mode='xla'``   — dense einsum attention (oracle; small L).
  * ``attention_mode='flash'`` — the Pallas blockwise kernel
    (parallel/flash_attention.py): O(L) memory, Pallas forward AND
    backward; what it measures in a cell of the benchmark is in
    PERF.md section 5.
  * ``attention_mode='ring'``  — ring attention over the mesh's sequence
    axis (parallel/ring_attention.py): O(L/N) per-device memory with k/v
    blocks rotating over ICI; trainable via its blockwise-recompute VJP.
  * ``attention_mode='auto'``  — dense below _FLASH_MIN_LENGTH, flash
    above (and on CPU backends, always dense — the kernel would run in
    the slow interpreter).

Causality is at TOKEN granularity: tokens are ordered frame-major, so a
frame's tokens attend to all earlier frames' tokens and to predecessors
within their own frame. This is slightly stricter than RT-1's frame-block
mask (which lets a frame's tokens also see later tokens of the same
frame) and equally leak-free; it lets all three backends share the plain
causal mask the kernels implement.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

# importlib: the parallel package re-exports the flash_attention FUNCTION
# under the same name as its module, which shadows plain module imports.
import importlib

flash_lib = importlib.import_module(
    'tensor2robot_tpu.parallel.flash_attention')
ring_lib = importlib.import_module(
    'tensor2robot_tpu.parallel.ring_attention')

from tensor2robot_tpu import runtime
from tensor2robot_tpu.parallel.sharding import constrain as _constrain

_FLASH_MIN_LENGTH = 2048


def scaled_dot_attention(q, k, v, causal: bool,
                         window: Optional[int] = None,
                         block_diffusion: Optional[Tuple[int, int]] = None,
                         scale: Optional[float] = None) -> jnp.ndarray:
  """Dense [B, L, H, D] attention in f32 accumulation (the oracle path).

  k/v with fewer heads than q are grouped-query heads (query head n reads
  head n // group); ``window`` keeps columns j with 0 <= i - j < window;
  ``block_diffusion`` = (length, block), not causal, is the mask of
  ``flash_attention``'s argument of that name over 2 x length positions.
  v may be narrower or wider than q and k; ``scale`` defaults to 1 /
  sqrt of their width."""
  if scale is None:
    scale = 1.0 / np.sqrt(q.shape[-1])
  group = q.shape[2] // k.shape[2]
  if group > 1:
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
  scores = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale
  if causal:
    l_q, l_k = q.shape[1], k.shape[1]
    mask = jnp.tril(jnp.ones((l_q, l_k), bool), k=l_k - l_q)
    if window is not None:
      mask = jnp.logical_and(
          mask, jnp.triu(jnp.ones((l_q, l_k), bool), k=l_k - l_q - window + 1))
    scores = jnp.where(mask, scores, -jnp.inf)
  if block_diffusion is not None:
    scores = jnp.where(flash_lib.block_diffusion_mask(*block_diffusion),
                       scores, -jnp.inf)
  probs = jax.nn.softmax(scores, axis=-1)
  return jnp.einsum('bhqk,bkhd->bqhd', probs, v.astype(jnp.float32)
                    ).astype(q.dtype)


def resolve_attention_mode(mode: str, seq_length: int) -> str:
  """'auto' -> 'flash'/'xla' by backend and length; other modes pass through.

  Lengths with poor block divisibility fall back to dense rather than
  running the kernel with tiny blocks (the kernel itself steps its blocks
  down to dividing sizes, so explicit 'flash' always works — 'auto' just
  avoids the slow small-block regime).
  """
  if mode != 'auto':
    return mode
  return 'flash' if (runtime.on_tpu() and seq_length >= _FLASH_MIN_LENGTH
                     and seq_length % 128 == 0) else 'xla'


def run_attention(q, k, v, *, mode: str, causal: bool,
                  mesh=None, seq_axis: str = 'data',
                  window: Optional[int] = None,
                  block_diffusion: Optional[Tuple[int, int]] = None,
                  scale: Optional[float] = None) -> jnp.ndarray:
  """Dispatches [B, L, H, D] self-attention to the selected backend.

  Grouped-query heads (k/v with fewer heads), ``window``, the
  ``block_diffusion`` mask, a value width unlike the key width and a
  ``scale`` of the caller's are the dense and flash backends'; the ring
  backend has none of them."""
  mode = resolve_attention_mode(mode, q.shape[1])
  if mode == 'xla':
    return scaled_dot_attention(q, k, v, causal, window, block_diffusion,
                                scale)
  if mode == 'flash':
    return flash_lib.flash_attention(q, k, v, causal=causal, scale=scale,
                                     window=window,
                                     block_diffusion=block_diffusion)
  if mode == 'ring':
    if mesh is None:
      raise ValueError("attention_mode='ring' requires a mesh.")
    if window is not None or k.shape[2] != q.shape[2] or \
        block_diffusion is not None or scale is not None or \
        v.shape[-1] != q.shape[-1]:
      raise ValueError("attention_mode='ring' has no window, no "
                       'grouped-query heads, no block-diffusion mask, no '
                       'scale of its own and one head width.')
    return ring_lib.ring_self_attention(q, k, v, mesh, seq_axis=seq_axis,
                                        causal=causal)
  raise ValueError('Unknown attention mode: {!r}'.format(mode))


class MultiHeadAttention(nn.Module):
  """Self-attention with pluggable backend (see module docstring).

  ``tp_axis``: Megatron-style tensor parallelism. The qkv projection is
  laid out HEAD-MAJOR (columns grouped [H, 3, Dh]) so sharding its output
  dim over ``tp_axis`` (parallel/sharding.py TP_RULES_TRANSFORMER) splits
  whole heads per device; attention then computes only local heads, and
  the out projection's input-dim sharding leaves a partial sum that XLA
  closes with one psum over the axis. With ``attention_mode='flash'`` the
  Pallas kernel is wrapped in a shard_map over ``tp_axis`` — attention is
  head-independent, so each device runs the kernel on its resident heads
  (a pallas_call is opaque to GSPMD and would otherwise be all-gathered).
  """

  num_heads: int
  head_dim: int
  attention_mode: str = 'auto'
  causal: bool = True
  mesh: Optional[object] = None  # jax.sharding.Mesh for 'ring'/tp
  seq_axis: str = 'data'
  tp_axis: Optional[str] = None
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
    from jax.sharding import PartitionSpec as P

    b, l, _ = x.shape
    features = self.num_heads * self.head_dim
    if self.tp_axis and self.mesh is not None:
      if self.tp_axis not in self.mesh.shape:
        # Mirror MoEMlp's ep_axis check: a missing axis would otherwise
        # skip the divisibility check here and surface later as a cryptic
        # with_sharding_constraint error.
        raise ValueError(
            'tp_axis {!r} is not an axis of the mesh (axes: {}); build the '
            'mesh with a model axis (parallel.create_mesh).'.format(
                self.tp_axis, tuple(self.mesh.axis_names)))
      tp_size = int(self.mesh.shape[self.tp_axis])
      if self.num_heads % tp_size:
        # Catch at trace time: the param rule would otherwise shard the
        # flat qkv column dim mid-head (parallel/sharding.py matches on
        # divisibility of H*3*Dh, which it cannot decompose into heads).
        raise ValueError(
            'tensor parallelism needs num_heads ({}) divisible by the '
            '{!r} axis size ({}).'.format(self.num_heads, self.tp_axis,
                                          tp_size))
    # Head-major qkv columns: [d, H*3*Dh] (NOT q|k|v-major) — see class
    # docstring; single-chip numerics only permute init columns. NOTE:
    # checkpoints saved before round 4's head-major change load
    # shape-compatibly but are scrambled — re-train (none shipped).
    qkv = nn.Dense(3 * features, dtype=self.dtype, name='qkv')(x)
    qkv = qkv.reshape(b, l, self.num_heads, 3, self.head_dim)
    if self.tp_axis:
      qkv = _constrain(qkv, self.mesh, P(None, None, self.tp_axis, None, None))
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    # Resolve 'auto' BEFORE the tp/flash routing below — otherwise
    # run_attention would resolve it internally and the opaque
    # pallas_call would be all-gathered over the model axis.
    mode = resolve_attention_mode(self.attention_mode, l)
    if self.tp_axis and mode == 'ring':
      # Only the flash path is shard_mapped over tp; the ring path's
      # seq-axis shard_map would force the head-sharded q/k/v to be
      # all-gathered over the model axis, silently negating tensor
      # parallelism for attention. Reject like the pipeline path does.
      raise ValueError(
          "tp_axis cannot combine with attention_mode='ring': the ring "
          'shard_map replicates over the model axis, all-gathering the '
          "head-sharded q/k/v. Use 'flash' (head-resident shard_map) or "
          "'xla' with tensor parallelism, or drop tp_axis for ring.")
    if self.tp_axis and mode == 'flash':
      out = _flash_sharded_heads(q, k, v, causal=self.causal, mesh=self.mesh,
                                 tp_axis=self.tp_axis)
    else:
      out = run_attention(q, k, v, mode=mode, causal=self.causal,
                          mesh=self.mesh, seq_axis=self.seq_axis)
    if self.tp_axis:
      out = _constrain(out, self.mesh, P(None, None, self.tp_axis, None))
    out = out.reshape(b, l, features)
    out = nn.Dense(x.shape[-1], dtype=self.dtype, name='out')(out)
    if self.tp_axis:
      out = _constrain(out, self.mesh, P(None, None, None))
    return out


def _flash_sharded_heads(q, k, v, *, causal: bool, mesh, tp_axis: str):
  """Flash attention with heads resident per tp shard via shard_map.

  The batch dim is also sharded over the mesh's data axis when the batch
  divides it — without that, a data x model mesh would all-gather q/k/v
  over 'data' and run the kernel on the full global batch per device.
  """
  from functools import partial

  from jax.experimental.shard_map import shard_map
  from jax.sharding import PartitionSpec as P

  from tensor2robot_tpu.parallel.mesh import DATA_AXIS

  data_size = int(mesh.shape.get(DATA_AXIS, 1))
  batch_axis = (DATA_AXIS
                if data_size > 1 and q.shape[0] % data_size == 0 else None)
  spec = P(batch_axis, None, tp_axis, None)
  fn = shard_map(
      partial(flash_lib.flash_attention, causal=causal),
      mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
      check_rep=False)
  return fn(q, k, v)


class TransformerBlock(nn.Module):
  """Pre-LN block: LN -> MHA -> +res, LN -> MLP(gelu) -> +res."""

  num_heads: int
  head_dim: int
  mlp_dim: int
  attention_mode: str = 'auto'
  causal: bool = True
  mesh: Optional[object] = None
  seq_axis: str = 'data'
  tp_axis: Optional[str] = None
  moe_experts: int = 0           # > 0: MoE MLP instead of the dense MLP
  moe_top_k: int = 2
  moe_capacity_factor: float = 1.25
  ep_axis: Optional[str] = None  # expert-parallel mesh axis for the MoE
  dropout_rate: float = 0.0
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, train: bool = False):
    """Returns (x, aux_loss) — aux is the MoE load-balance term (0 when
    the block uses the dense MLP), threaded explicitly rather than via a
    mutable flax collection so it reaches the loss through the pure
    functional path the train step differentiates."""
    from jax.sharding import PartitionSpec as P

    # LayerNorm in f32: bf16 variance over long sequences loses precision.
    h = nn.LayerNorm(dtype=jnp.float32, name='ln_attn')(x).astype(self.dtype)
    h = MultiHeadAttention(
        num_heads=self.num_heads, head_dim=self.head_dim,
        attention_mode=self.attention_mode, causal=self.causal,
        mesh=self.mesh, seq_axis=self.seq_axis, tp_axis=self.tp_axis,
        dtype=self.dtype, name='attn')(h)
    if self.dropout_rate:
      h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    h = nn.LayerNorm(dtype=jnp.float32, name='ln_mlp')(x).astype(self.dtype)
    if self.moe_experts:
      from tensor2robot_tpu.layers.moe import MoEMlp

      h, aux = MoEMlp(
          num_experts=self.moe_experts, expert_dim=self.mlp_dim,
          top_k=self.moe_top_k, capacity_factor=self.moe_capacity_factor,
          mesh=self.mesh, ep_axis=self.ep_axis,
          dtype=self.dtype, name='moe')(h)
    else:
      h = nn.Dense(self.mlp_dim, dtype=self.dtype, name='mlp_in')(h)
      if self.tp_axis:
        # Hidden activations shard over tp ([B, L, mlp/|model| each);
        # mlp_out's input-dim sharding then yields the closing psum.
        h = _constrain(h, self.mesh, P(None, None, self.tp_axis))
      h = nn.gelu(h)
      h = nn.Dense(x.shape[-1], dtype=self.dtype, name='mlp_out')(h)
      if self.tp_axis:
        h = _constrain(h, self.mesh, P(None, None, None))
    if self.dropout_rate:
      h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
    return x + h, aux


class RMSNorm(nn.Module):
  """x / sqrt(mean(x^2) + eps) * scale, in f32 whatever comes in."""

  eps: float = 1e-6

  @nn.compact
  def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
    scale = self.param('scale', nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def rotary_positions(x: jnp.ndarray, theta: float,
                     positions: Optional[jnp.ndarray] = None,
                     inverse: Optional[np.ndarray] = None) -> jnp.ndarray:
  """Rotary position embedding of [B, L, H, D], rotate-half pairing
  (dimension i with i + D/2), in f32. ``positions`` [L] are the position
  ids of the rows (None: the index in the sequence). ``inverse`` [D/2]: the
  frequencies (None: theta^(-2i/D); ``yarn_frequencies`` gives YaRN's)."""
  d = x.shape[-1]
  if inverse is None:
    inverse = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  else:
    inverse = jnp.asarray(inverse, jnp.float32)
  if positions is None:
    positions = jnp.arange(x.shape[1])
  angle = positions.astype(jnp.float32)[:, None] * inverse[None]
  cos, sin = (jnp.concatenate([f(angle), f(angle)], axis=-1)[None, :, None]
              for f in (jnp.cos, jnp.sin))
  x = x.astype(jnp.float32)
  first, second = x[..., :d // 2], x[..., d // 2:]
  return x * cos + jnp.concatenate([-second, first], axis=-1) * sin


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
  """YaRN's rotary frequencies [dim/2] (Peng et al. 2023, arXiv:2309.00071,
  as DeepSeek-V2/V3 compute them): f_i = theta^(-2i/dim) kept where a
  dimension turns more than ``beta_fast`` times over the ``original``
  context, divided by ``factor`` where it turns fewer than ``beta_slow``
  times, and a linear ramp between over the dimensions
  low = floor(c(beta_fast)) .. high = ceil(c(beta_slow)), c(r) = dim
  ln(original / (2 pi r)) / (2 ln theta)."""
  frequency = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
  turns = lambda rotations: dim * np.log(original / (rotations * 2 * np.pi)) / (
      2 * np.log(theta))
  low = max(int(np.floor(turns(beta_fast))), 0)
  high = min(int(np.ceil(turns(beta_slow))), dim - 1)
  ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
  return (frequency / factor * ramp + frequency * (1 - ramp)).astype(
      np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
  """YaRN's attention temperature term: 0.1 mscale ln(factor) + 1."""
  return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def blocked_cross_entropy(hidden, head, targets, weights, block_tokens: int,
                          dtype) -> jnp.ndarray:
  """Sum over all tokens of ``weights`` x the cross-entropy of the token's
  logits (``hidden`` @ ``head``) against its target, in f32.

  hidden [B, L, d], head [d, V], targets and weights [B, L]. The logits are
  formed ``block_tokens`` tokens at a time: a block's [block, V] f32 logits
  live only inside its own step of the loop. Under differentiation that
  step forms them ONCE and gives the loss and both gradients
  (``_head_loss``); ``targets`` and ``weights`` get no cotangent."""
  b, l, d = hidden.shape
  n = b * l
  block = max(c for c in range(1, min(block_tokens, n) + 1) if n % c == 0)
  # Cast once, outside the loop: the rows' gradients are then stacked at
  # this width, not in float32.
  head, hidden = head.astype(dtype), hidden.astype(dtype)
  with jax.named_scope('head_loss'):
    return _head_loss(hidden.reshape(n // block, block, d), head,
                      targets.reshape(n // block, block),
                      weights.astype(jnp.float32).reshape(n // block, block))


def _block_logits(rows, head, target, weight):
  """A block's f32 logits and its weighted cross-entropy sum."""
  logits = jnp.dot(rows, head, preferred_element_type=jnp.float32)
  picked = jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0]
  lse = jax.nn.logsumexp(logits, axis=-1)
  return logits, lse, jnp.sum(weight * (lse - picked))


@jax.custom_vjp
def _head_loss(rows, head, targets, weights):
  """Sum of the blocks' losses; rows [blocks, block, d], head [d, V].
  Undifferentiated (eval, predict): one product over the vocabulary a
  block."""
  return jnp.sum(jax.lax.map(
      lambda args: _block_logits(args[0], head, *args[1:])[2],
      (rows, targets, weights)))


def _head_loss_fwd(rows, head, targets, weights):
  """One pass over the blocks that forms each block's logits once and
  gives its loss, its rows' gradient dh (stacked) and its share of the
  head's gradient dW (carried at the head's width): three products over
  the vocabulary a block, where the loss's forward and its transpose took
  four."""
  from tensor2robot_tpu.observability import get_registry

  get_registry().gauge('head_loss/vocab_products').set(3.0)
  vocab = jax.lax.broadcasted_iota(jnp.int32, (1, head.shape[1]), 1)

  def step(d_head, args):
    block_rows, target, weight = args
    logits, lse, loss = _block_logits(block_rows, head, target, weight)
    # w (softmax - onehot), the onehot a comparison, in the compute dtype.
    p = jnp.exp(logits - lse[:, None])
    dlogits = (weight[:, None] * jnp.where(vocab == target[:, None], p - 1.0,
                                           p)).astype(head.dtype)
    d_rows = jnp.dot(dlogits, head.T, preferred_element_type=jnp.float32)
    d_head = d_head + jnp.dot(block_rows.T, dlogits,
                              preferred_element_type=jnp.float32)
    return d_head.astype(head.dtype), (loss, d_rows.astype(rows.dtype))

  d_head, (losses, d_rows) = jax.lax.scan(
      step, jnp.zeros_like(head), (rows, targets, weights))
  return jnp.sum(losses), (d_rows, d_head)


def _head_loss_bwd(residuals, g):
  d_rows, d_head = residuals
  return ((d_rows * g).astype(d_rows.dtype), (d_head * g).astype(d_head.dtype),
          None, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


class GroupedQueryAttention(nn.Module):
  """Self-attention with ``num_heads`` query heads over ``num_kv_heads``
  key/value heads (query head n reads k/v head n // group), separate
  bias-free q/k/v/out projections, optionally a sliding ``window`` and
  rotary positions (``rope_theta``; None: the layer carries no positions
  at all), at the ``positions`` [L] the caller gives (None: the index in
  the sequence). ``qk_norm``: an RMS norm over the head dimension of q and
  of k, one learned scale each, before the rotation. The mask is causal,
  or with ``block_diffusion`` = (length, block) that of block-diffusion
  training over [noised ; clean] (``flash_attention`` has the rules).
  Backends as ``run_attention``: the Pallas kernels index the shared k/v
  heads, nothing is repeated in HBM."""

  num_heads: int
  num_kv_heads: int
  head_dim: int
  window: Optional[int] = None
  rope_theta: Optional[float] = None
  qk_norm: bool = False
  eps: float = 1e-6             # of the q/k norms
  block_diffusion: Optional[Tuple[int, int]] = None
  attention_mode: str = 'auto'
  out_init_std: float = 0.02    # of `out`, which writes into the residual
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray,
               positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    b, l, d = x.shape
    init = nn.initializers.normal(0.02)

    def project(name, heads):
      out = nn.Dense(heads * self.head_dim, use_bias=False, dtype=self.dtype,
                     kernel_init=init, name=name)(x)
      return out.reshape(b, l, heads, self.head_dim)

    q = project('q', self.num_heads)
    k = project('k', self.num_kv_heads)
    v = project('v', self.num_kv_heads)
    if self.qk_norm:
      q = RMSNorm(self.eps, name='q_norm')(q).astype(self.dtype)
      k = RMSNorm(self.eps, name='k_norm')(k).astype(self.dtype)
    if self.rope_theta is not None:
      q, k = (rotary_positions(t, self.rope_theta, positions).astype(
          self.dtype) for t in (q, k))
    with jax.named_scope('attention'):
      out = run_attention(q, k, v, mode=self.attention_mode,
                          causal=self.block_diffusion is None,
                          window=self.window,
                          block_diffusion=self.block_diffusion)
    return nn.Dense(d, use_bias=False, dtype=self.dtype,
                    kernel_init=nn.initializers.normal(self.out_init_std),
                    name='out')(out.reshape(b, l, -1))


class LatentAttention(nn.Module):
  """Multi-head latent attention (DeepSeek-V2/V3): queries and keys/values
  through low-rank bottlenecks, a rotary key SHARED by all heads, and values
  narrower or wider than the keys. On the input h [B, L, d]:

    c_q = rmsnorm(h W_qa);  q = c_q W_qb = [q_nope ; q_pe] a head
    [c_kv ; k_pe] = h W_kva;  [k_nope ; v] = rmsnorm(c_kv) W_kvb a head
    q_h = [q_nope ; R(q_pe)],  k_h = [k_nope ; R(k_pe)]  (k_pe one for all)
    o_h = softmax(scale q_h k_h^T + causal) v_h;  out = concat_h(o_h) W_o

  R is the rotary embedding (rotate-half pairing) at YaRN's frequencies
  when ``rope_scaling`` = (factor, original context, beta_fast, beta_slow,
  mscale, mscale_all_dim) is given, plain ones at ``rope_theta``
  otherwise; scale is yarn_mscale(factor, mscale_all_dim)^2 / sqrt(nope +
  rope width), and cos and sin are multiplied by yarn_mscale(factor,
  mscale) / yarn_mscale(factor, mscale_all_dim). No bias anywhere; the
  parameters keep DeepSeek's names (q_a, q_a_norm, q_b, kv_a, kv_a_norm,
  kv_b, out). The attention is ``run_attention``'s, the flash kernels at
  key width nope + rope and value width ``v_head_dim``."""

  num_heads: int
  q_lora_rank: int
  kv_lora_rank: int
  qk_nope_head_dim: int
  qk_rope_head_dim: int
  v_head_dim: int
  rope_theta: float
  rope_scaling: Optional[Tuple[float, ...]] = None
  eps: float = 1e-6
  attention_mode: str = 'auto'
  out_init_std: float = 0.02    # of `out`, which writes into the residual
  dtype: jnp.dtype = jnp.float32

  def rotary(self):
    """(frequencies [rope width / 2] or None, the factor on cos and sin,
    the attention's scale)."""
    width = self.qk_nope_head_dim + self.qk_rope_head_dim
    if self.rope_scaling is None:
      return None, 1.0, 1.0 / float(np.sqrt(width))
    factor, original, fast, slow, mscale, mscale_all = self.rope_scaling
    inverse = yarn_frequencies(self.qk_rope_head_dim, self.rope_theta,
                               factor, int(original), fast, slow)
    temperature = yarn_mscale(factor, mscale_all)
    return (inverse, yarn_mscale(factor, mscale) / temperature,
            temperature ** 2 / float(np.sqrt(width)))

  @nn.compact
  def __call__(self, x: jnp.ndarray,
               positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    b, l, d = x.shape
    heads, nope, rope = (self.num_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim)
    dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                              kernel_init=nn.initializers.normal(0.02))
    c_q = RMSNorm(self.eps, name='q_a_norm')(
        dense(self.q_lora_rank, name='q_a')(x)).astype(self.dtype)
    q = dense(heads * (nope + rope), name='q_b')(c_q).reshape(
        b, l, heads, nope + rope)
    compressed = dense(self.kv_lora_rank + rope, name='kv_a')(x)
    c_kv = RMSNorm(self.eps, name='kv_a_norm')(
        compressed[..., :self.kv_lora_rank]).astype(self.dtype)
    kv = dense(heads * (nope + self.v_head_dim), name='kv_b')(c_kv).reshape(
        b, l, heads, nope + self.v_head_dim)
    inverse, on_cos_sin, scale = self.rotary()

    def rotated(t):
      t = rotary_positions(t, self.rope_theta, positions, inverse)
      return (t * on_cos_sin if on_cos_sin != 1.0 else t).astype(self.dtype)

    q_pe = rotated(q[..., nope:])
    k_pe = rotated(compressed[..., self.kv_lora_rank:].reshape(b, l, 1, rope))
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, l, heads, rope))],
        axis=-1)
    with jax.named_scope('attention'):
      out = run_attention(q, k, kv[..., nope:], mode=self.attention_mode,
                          causal=True, scale=scale)
    return nn.Dense(d, use_bias=False, dtype=self.dtype,
                    kernel_init=nn.initializers.normal(self.out_init_std),
                    name='out')(out.reshape(b, l, -1))


class ShortConvolution(nn.Module):
  """A gated short convolution, the token mixer that is not attention:

    [B, C, X] = h W_in;  z = B * X;  c_t = sum_i w[:, i] z_{t-2+i};
    y = (C * c) W_out

  ``in_proj`` d -> 3 d (the three chunks in that order), a depthwise causal
  filter of three taps a channel (``filter`` [d, 3], the last tap on the
  current token, zeros before a sequence's first token, nothing across
  batch rows), ``out_proj`` d -> d; no bias, no positions. The core between
  the two projections is ``parallel/short_conv.py``: one Pallas kernel pair
  on the TPU, the same mathematics in ``jax.numpy`` elsewhere."""

  out_init_std: float = 0.02    # of `out_proj`, which writes into the stream
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
    from tensor2robot_tpu.parallel import short_conv as short_conv_lib

    d = h.shape[-1]
    taps = short_conv_lib.TAPS
    bound = 1.0 / taps ** 0.5      # torch.nn.Conv1d's default for a fan-in of 3
    taps_init = lambda key, shape, dtype: jax.random.uniform(
        key, shape, dtype, -bound, bound)
    weights = self.param('filter', taps_init, (d, taps), jnp.float32)
    bcx = nn.Dense(3 * d, use_bias=False, dtype=self.dtype,
                   kernel_init=nn.initializers.normal(0.02),
                   name='in_proj')(h)
    with jax.named_scope('short_conv'):
      y = short_conv_lib.short_conv(bcx, weights)
    return nn.Dense(d, use_bias=False, dtype=self.dtype,
                    kernel_init=nn.initializers.normal(self.out_init_std),
                    name='out_proj')(y)


class GatedMLP(nn.Module):
  """One dense SwiGLU: ``(silu(u W1) * (u W3)) W2``, no bias."""

  width: int
  down_init_std: float = 0.02   # of `w2`, which writes into the stream
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
    dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
    init = nn.initializers.normal(0.02)
    hidden = nn.silu(dense(self.width, kernel_init=init, name='w1')(u)) * \
        dense(self.width, kernel_init=init, name='w3')(u)
    return dense(u.shape[-1],
                 kernel_init=nn.initializers.normal(self.down_init_std),
                 name='w2')(hidden)


class StreamMaps(nn.Module):
  """The maps of one sublayer over ``n`` residual streams
  (``parallel/hyper_connections.py`` has the equations): phi_pre, phi_post
  [n C, n] and phi_res [n C, n^2] (normal(0.02)), the gains alpha_pre,
  alpha_post, alpha_res (ones) and the biases b_pre, b_post [n], b_res
  [n^2] (normal(1): seeded draws that make the maps differ by stream, so
  that a fault in a map shows). ``__call__(state [rows, n C] f32)`` -> (h [rows, C] f32, maps,
  the state to hand to ``hc_post``); the projection runs at ``dtype``."""

  n: int
  iters: int = 20
  eps: float = 1e-6
  clamp: float = 30.0
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, state: jnp.ndarray):
    from tensor2robot_tpu.parallel import hyper_connections as hc_lib

    n, width = self.n, state.shape[-1]
    init = nn.initializers.normal(0.02)
    bias_init = nn.initializers.normal(1.0)
    phi = jnp.concatenate([
        self.param('phi_pre', init, (width, n), jnp.float32),
        self.param('phi_post', init, (width, n), jnp.float32),
        self.param('phi_res', init, (width, n * n), jnp.float32)], axis=1)
    alpha = jnp.stack([self.param(name, nn.initializers.ones, (), jnp.float32)
                       for name in ('alpha_pre', 'alpha_post', 'alpha_res')])
    bias = jnp.concatenate([
        self.param('b_pre', bias_init, (n,), jnp.float32),
        self.param('b_post', bias_init, (n,), jnp.float32),
        self.param('b_res', bias_init, (n * n,), jnp.float32)])
    with jax.named_scope('hc_pre'):
      return hc_lib.hc_pre(state, phi.astype(self.dtype), alpha, bias, n=n,
                           iters=self.iters, eps=self.eps, clamp=self.clamp)


def _gauge_kept_stream_bytes(value: int):
  from tensor2robot_tpu.observability import get_registry

  get_registry().gauge('hc/kept_bytes_per_token').set(float(value))


class MoEBlock(nn.Module):
  """Pre-norm block of a token mixer and a feed-forward:

    x1 = x + mixer(rmsnorm(x));  u = rmsnorm(x1);  out = x1 + ff(u)

  Both are FIELDS. ``mixer``: ``'attention'`` (grouped-query attention:
  window, rotary positions, q/k norm, the block-diffusion mask),
  ``'short_conv'`` (``ShortConvolution``; it takes no positions) or
  ``'latent_attention'`` (``LatentAttention``: the ``q_lora_rank`` ..
  ``v_head_dim`` fields and ``rope_scaling``; ``num_heads`` its heads).
  ``feed_forward``: ``'experts'``, the dropless routed experts, which are
  told which experts they hold (layers/moe.py::DroplessMoE) and how their
  gate is activated, or ``'dense'``, one SwiGLU of width ``dense_dim``.
  ``shared_expert_dim``: with experts, one more SwiGLU of that width that
  every token goes through, unweighted, added to the routed experts' sum.

  With experts, the router's logits r (f32) read what ``router_reads``
  says: ``'input'``, the block's INPUT before the mixer, r = x W_r (the
  router-first block), or ``'normed'``, the normed post-mixer stream,
  r = u W_r (the usual place); and ``router`` says what is made of them:
  ``'softmax'`` (the ``top_k`` largest logits, softmax over just those) or
  ``'sigmoid_bias'`` (``route_sigmoid_bias``: the largest sigmoid + bias are
  chosen, the sigmoids alone weigh, times ``routed_scaling``). The bias
  [num_experts] f32 is no parameter: it lives in the collection
  ``router_state`` and, where that collection is mutable (training), leaves
  the call moved by ``balanced_bias`` on this call's own counts at
  ``router_bias_rate``; under ``nn.remat`` the backward pass computes the
  block again from the bias it was GIVEN and its update is dropped, so a
  step applies the rule once.

  ``hc_streams`` n > 0: the block carries n residual streams, x [B, L, n
  d] float32 in and out (manifold-constrained hyper-connections,
  ``parallel/hyper_connections.py``): each of the two sublayers reads h from
  its own ``StreamMaps`` (``hc_attn``, ``hc_ff``), computes f from
  rmsnorm(h) as above, and the streams become res X + post f; the router
  reads ``'normed'``. Where the stream kernels run, they name h, the maps, f
  and the state between the sublayers (``hc_lib.BACKWARD_READS``) for a
  checkpoint policy to keep. When the block is traced it sets the gauge
  ``hc/kept_bytes_per_token``: the bytes a token of those arrays
  (``hc_lib.kept_bytes_per_token``), 0 for a block that names none.

  Returns (out, the feed-forward's stats): the expert layer's, with
  ``chosen_load_max_over_mean`` (the most chosen of ALL experts over the
  mean: what the bias balances) and ``router_bias_abs_mean`` (of the bias
  as the call leaves it; 0 under a softmax router); {} for a dense one;
  with streams also ``res_stochastic_error``, the largest |row or column
  sum - 1| of either sublayer's res over the tokens.

  The norms keep the names of the first block of this class (``norm_attn``
  before the mixer, ``norm_moe`` before the feed-forward) whatever the
  fields say, so that parameter trees written before the fields read on."""

  num_heads: int
  num_kv_heads: int
  head_dim: int
  num_experts: int
  experts_held: tuple
  expert_dim: int
  top_k: int
  window: Optional[int] = None
  rope_theta: Optional[float] = None
  eps: float = 1e-6
  mixer: str = 'attention'
  feed_forward: str = 'experts'
  dense_dim: Optional[int] = None
  router_reads: str = 'input'
  router: str = 'softmax'
  router_bias_rate: float = 1e-3
  routed_scaling: float = 1.0
  shared_expert_dim: Optional[int] = None
  qk_norm: bool = False
  block_diffusion: Optional[Tuple[int, int]] = None
  q_lora_rank: Optional[int] = None
  kv_lora_rank: Optional[int] = None
  qk_nope_head_dim: Optional[int] = None
  qk_rope_head_dim: Optional[int] = None
  v_head_dim: Optional[int] = None
  rope_scaling: Optional[Tuple[float, ...]] = None
  hc_streams: int = 0
  hc_iters: int = 20
  hc_eps: float = 1e-6
  hc_clamp: float = 30.0
  gate_activation: str = 'relu'
  attention_mode: str = 'auto'
  moe_block_rows: int = 256
  residual_init_std: float = 0.02  # of the two matrices that write into x
  dtype: jnp.dtype = jnp.float32

  def _route(self, router_logits):
    """((expert index, weight), the router's two stats)."""
    from tensor2robot_tpu.layers import moe as moe_lib

    k = min(self.top_k, self.num_experts)
    with jax.named_scope('moe_route'):
      if self.router == 'softmax':
        bias = None
        routing = moe_lib.route_top_k(router_logits, k)
      else:
        bias = self.variable('router_state', 'bias', jnp.zeros,
                             (self.num_experts,), jnp.float32)
        routing = moe_lib.route_sigmoid_bias(router_logits, bias.value, k,
                                             self.routed_scaling)
      counts = moe_lib.expert_counts(routing[0], self.num_experts)
      if (bias is not None and self.is_mutable_collection('router_state')
          and not self.is_initializing()):
        bias.value = moe_lib.balanced_bias(bias.value, counts,
                                           self.router_bias_rate)
      return routing, {
          'chosen_load_max_over_mean':
              counts.max() / jnp.maximum(counts.mean(), 1.0),
          'router_bias_abs_mean': jnp.float32(0) if bias is None else
                                  jnp.mean(jnp.abs(bias.value))}

  def _mix(self, h, positions):
    """The mixer's output on the normed h [B, L, d]."""
    if self.mixer == 'attention':
      return GroupedQueryAttention(
          num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
          head_dim=self.head_dim, window=self.window,
          rope_theta=self.rope_theta, qk_norm=self.qk_norm, eps=self.eps,
          block_diffusion=self.block_diffusion,
          attention_mode=self.attention_mode,
          out_init_std=self.residual_init_std, dtype=self.dtype,
          name='attn')(h, positions)
    if self.mixer == 'latent_attention':
      return LatentAttention(
          num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
          kv_lora_rank=self.kv_lora_rank,
          qk_nope_head_dim=self.qk_nope_head_dim,
          qk_rope_head_dim=self.qk_rope_head_dim,
          v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
          rope_scaling=self.rope_scaling, eps=self.eps,
          attention_mode=self.attention_mode,
          out_init_std=self.residual_init_std, dtype=self.dtype,
          name='attn')(h, positions)
    return ShortConvolution(out_init_std=self.residual_init_std,
                            dtype=self.dtype, name='conv')(h)

  def _feed(self, u, router_logits):
    """(the feed-forward's output [B, L, d], its stats) on the normed u
    (f32); ``router_logits`` [B, L, E] where the router read the input."""
    from tensor2robot_tpu.layers.moe import DroplessMoE

    b, l, d = u.shape
    if self.feed_forward == 'dense':
      return GatedMLP(self.dense_dim, down_init_std=self.residual_init_std,
                      dtype=self.dtype, name='mlp')(u.astype(self.dtype)), {}
    if router_logits is None:
      router_logits = self._router()(u)
    routing, router_stats = self._route(router_logits.reshape(b * l, -1))
    y, stats = DroplessMoE(
        num_experts=self.num_experts, experts_held=tuple(self.experts_held),
        expert_dim=self.expert_dim, gate_activation=self.gate_activation,
        block_rows=self.moe_block_rows,
        down_init_std=self.residual_init_std, dtype=self.dtype, name='moe')(
            u.astype(self.dtype).reshape(b * l, d), routing)
    y = y.reshape(b, l, d)
    if self.shared_expert_dim:
      y = y + GatedMLP(self.shared_expert_dim,
                       down_init_std=self.residual_init_std, dtype=self.dtype,
                       name='shared_expert')(u.astype(self.dtype))
    return y, dict(stats, **router_stats)

  def _router(self):
    return nn.Dense(
        self.num_experts, use_bias=False, dtype=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
        kernel_init=nn.initializers.normal(0.02), name='router')

  @nn.compact
  def __call__(self, x: jnp.ndarray,
               positions: Optional[jnp.ndarray] = None):
    for field, allowed in (
        ('router_reads', ('input', 'normed')),
        ('mixer', ('attention', 'short_conv', 'latent_attention')),
        ('feed_forward', ('experts', 'dense')),
        ('router', ('softmax', 'sigmoid_bias'))):
      if getattr(self, field) not in allowed:
        raise ValueError('{} {!r} is none of {}.'.format(
            field, getattr(self, field), ', '.join(map(repr, allowed))))
    if self.hc_streams:
      return self._streams(x, positions)
    _gauge_kept_stream_bytes(0)
    router_logits = None
    if self.feed_forward == 'experts' and self.router_reads == 'input':
      router_logits = self._router()(x.astype(jnp.float32))
    h = RMSNorm(self.eps, name='norm_attn')(x).astype(self.dtype)
    x = x + self._mix(h, positions)
    u = RMSNorm(self.eps, name='norm_moe')(x)
    y, stats = self._feed(u, router_logits)
    return x + y.astype(x.dtype), stats

  def _streams(self, x, positions):
    """The block over ``hc_streams`` residual streams (class docstring)."""
    from tensor2robot_tpu.parallel import hyper_connections as hc_lib

    if self.router_reads != 'normed':
      raise ValueError('a block of several streams routes on the normed '
                       "input of its feed-forward: router_reads 'normed'.")
    n = self.hc_streams
    b, l, width = x.shape
    d = width // n
    _gauge_kept_stream_bytes(hc_lib.kept_bytes_per_token(
        b * l, n, d, jnp.dtype(self.dtype).itemsize))
    maps_of = lambda name: StreamMaps(
        n, self.hc_iters, self.hc_eps, self.hc_clamp, dtype=self.dtype,
        name=name)
    state = x.astype(jnp.float32).reshape(b * l, width)
    h, maps_attn, state = maps_of('hc_attn')(state)
    f = self._mix(RMSNorm(self.eps, name='norm_attn')(
        h.reshape(b, l, d)).astype(self.dtype), positions)
    with jax.named_scope('hc_post'):
      state = hc_lib.hc_post(state, f.reshape(b * l, d).astype(self.dtype),
                             maps_attn, n=n)
    h, maps_ff, state = maps_of('hc_ff')(state)
    y, stats = self._feed(RMSNorm(self.eps, name='norm_moe')(
        h.reshape(b, l, d)), None)
    with jax.named_scope('hc_post'):
      state = hc_lib.hc_post(state, y.reshape(b * l, d).astype(self.dtype),
                             maps_ff, n=n)
    error = jax.lax.stop_gradient(jnp.maximum(
        hc_lib.res_stochastic_error(maps_attn, n),
        hc_lib.res_stochastic_error(maps_ff, n)))
    return state.reshape(b, l, width), dict(stats, res_stochastic_error=error)


class TokenLearner(nn.Module):
  """Learns K attention maps that pool N spatial tokens to K tokens.

  RT-1's TokenLearner: per output token k, a weight map over the input
  tokens (softmax-normalized), applied as a weighted sum. Cuts the
  transformer's L from T*N to T*K (8x here) at negligible accuracy cost.
  """

  num_tokens: int
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
    # tokens: [B, N, D] -> [B, K, D]
    x = nn.LayerNorm(dtype=jnp.float32, name='ln')(tokens).astype(self.dtype)
    maps = nn.Dense(self.num_tokens * 2, dtype=self.dtype, name='map_in')(x)
    maps = nn.gelu(maps)
    maps = nn.Dense(self.num_tokens, dtype=self.dtype, name='map_out')(maps)
    maps = jax.nn.softmax(maps.astype(jnp.float32), axis=1)  # over N
    return jnp.einsum('bnk,bnd->bkd', maps,
                      tokens.astype(jnp.float32)).astype(tokens.dtype)


class ImageTokenizer(nn.Module):
  """Conv stem turning a [B, H, W, 3] frame into [B, K, D] visual tokens.

  Four stride-2 convs (H/16 x W/16 spatial map), then TokenLearner down to
  ``num_tokens``. The reference's per-frame encoders (vision_layers
  BuildImagesToFeaturesModel) collapse each frame to ONE vector; tokens
  preserve spatial structure for the sequence model.
  """

  num_tokens: int = 8
  embed_dim: int = 512
  widths: tuple = (32, 64, 128, 256)
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, images: jnp.ndarray, train: bool = False) -> jnp.ndarray:
    x = images.astype(self.dtype)
    for i, width in enumerate(self.widths):
      x = nn.Conv(width, (3, 3), strides=(2, 2), dtype=self.dtype,
                  name='conv{}'.format(i))(x)
      x = nn.LayerNorm(dtype=jnp.float32,
                       name='ln{}'.format(i))(x).astype(self.dtype)
      x = nn.gelu(x)
    b = x.shape[0]
    x = x.reshape(b, -1, x.shape[-1])                    # [B, hw, C]
    x = nn.Dense(self.embed_dim, dtype=self.dtype, name='embed')(x)
    if self.num_tokens and self.num_tokens > x.shape[1]:
      raise ValueError(
          'num_tokens={} exceeds the conv stem\'s {} spatial tokens for '
          'this input size; lower num_tokens or raise the resolution.'
          .format(self.num_tokens, x.shape[1]))
    if self.num_tokens and self.num_tokens < x.shape[1]:
      x = TokenLearner(num_tokens=self.num_tokens, dtype=self.dtype,
                       name='token_learner')(x)
    # num_tokens == spatial tokens: pass-through (TokenLearner would be a
    # square resampling; small test configs rely on the identity).
    return x


class CausalTransformer(nn.Module):
  """Token sequence model: learned positions + N causal blocks + final LN.

  ``pipe_axis``: pipeline parallelism (parallel/pipeline.py). The blocks
  become ONE stacked param tree (``pipe_blocks``, leading dims
  ``[S, k]`` = [stage, block-within-stage], stage dim sharded over the
  pipe axis by PP_RULES_TRANSFORMER) and run as a GPipe pipeline with
  ``pipeline_microbatches`` microbatches; positions and the final LN stay
  outside the pipeline (replicated, cheap). Each stage runs
  ``num_layers / |pipe|`` consecutive blocks (virtual stages), so layer
  count only needs to be divisible by — not equal to — the stage count.
  Pipelined constraints (asserted at trace time): divisibility, no
  dropout, and no MoE/tp/ring inside the pipeline. NOTE: round 4's
  virtual-stage change moved pipe_blocks leaves from [L, ...] to
  [S, k, ...]; pipelined checkpoints saved before it need a one-off
  reshape (k == 1 splits the leading dim) — none are shipped in-tree.
  """

  num_layers: int
  num_heads: int
  head_dim: int
  mlp_dim: int
  max_length: int
  attention_mode: str = 'auto'
  mesh: Optional[object] = None
  seq_axis: str = 'data'
  tp_axis: Optional[str] = None
  moe_experts: int = 0
  moe_top_k: int = 2
  moe_capacity_factor: float = 1.25
  ep_axis: Optional[str] = None
  pipe_axis: Optional[str] = None
  pipeline_microbatches: int = 2
  pipeline_remat: bool = False
  dropout_rate: float = 0.0
  dtype: jnp.dtype = jnp.float32

  def _block(self, name: Optional[str] = None) -> 'TransformerBlock':
    return TransformerBlock(
        num_heads=self.num_heads, head_dim=self.head_dim,
        mlp_dim=self.mlp_dim, attention_mode=self.attention_mode,
        causal=True, mesh=self.mesh, seq_axis=self.seq_axis,
        tp_axis=self.tp_axis, moe_experts=self.moe_experts,
        moe_top_k=self.moe_top_k,
        moe_capacity_factor=self.moe_capacity_factor, ep_axis=self.ep_axis,
        dropout_rate=self.dropout_rate, dtype=self.dtype, name=name)

  @nn.compact
  def __call__(self, tokens: jnp.ndarray, train: bool = False):
    """Returns (encoded, aux_loss) — summed MoE load-balance loss over
    blocks, 0.0 for a dense (non-MoE) stack."""
    b, l, d = tokens.shape
    if l > self.max_length:
      raise ValueError('Sequence length {} exceeds max_length {}.'.format(
          l, self.max_length))
    pos = self.param('pos_embedding', nn.initializers.normal(0.02),
                     (self.max_length, d), jnp.float32)
    x = tokens + pos[None, :l].astype(tokens.dtype)
    aux_total = jnp.zeros((), jnp.float32)
    if self.pipe_axis:
      x = self._pipelined_blocks(x)
    else:
      for i in range(self.num_layers):
        x, aux = self._block(name='block{}'.format(i))(x, train=train)
        aux_total = aux_total + aux
    return nn.LayerNorm(dtype=jnp.float32, name='ln_final')(x), aux_total

  def _pipelined_blocks(self, x: jnp.ndarray) -> jnp.ndarray:
    from tensor2robot_tpu.parallel import pipeline as pipeline_lib

    if self.mesh is None:
      raise ValueError('pipe_axis requires a mesh.')
    stages = int(self.mesh.shape.get(self.pipe_axis, 0))
    if stages < 1 or self.num_layers % stages:
      raise ValueError(
          'pipelined transformer needs num_layers ({}) divisible by the '
          '{!r} axis size ({}); each stage runs num_layers/|pipe| blocks.'
          .format(self.num_layers, self.pipe_axis, stages))
    blocks_per_stage = self.num_layers // stages
    if self.dropout_rate or self.moe_experts:
      raise ValueError('pipelined blocks do not support dropout or MoE '
                       '(rngs/aux are not threaded through the pipeline).')
    if self.tp_axis or self.attention_mode == 'ring':
      # Both run their own sharding machinery (with_sharding_constraint /
      # a nested shard_map) inside pipeline_apply's shard_map body, where
      # every mesh axis is already manual — fail clearly instead of deep
      # inside JAX tracing.
      raise ValueError('pipelined blocks cannot combine with tp_axis or '
                       "attention_mode='ring' (nested sharding inside the "
                       'pipeline shard_map); plain xla/flash attention '
                       'works.')
    b, l, d = x.shape
    block = self._block()

    def init_stacked(rng):
      # Leading dims [S, k]: stage-major so leaf i on the stage axis holds
      # stage i's k consecutive blocks (layer order = stage*k + j).
      rngs = jax.random.split(rng, stages * blocks_per_stage)
      rngs = rngs.reshape((stages, blocks_per_stage) + rngs.shape[1:])
      return jax.vmap(jax.vmap(
          lambda r: block.init(r, jnp.zeros((1, l, d), x.dtype))['params']
      ))(rngs)

    stacked = self.param('pipe_blocks', init_stacked)

    def stage_fn(params, act):
      # params leaves: [k, ...] — apply the stage's k blocks in order.
      for j in range(blocks_per_stage):
        act, _ = block.apply(
            {'params': jax.tree.map(lambda p: p[j], params)}, act)
      return act

    mb = pipeline_lib.microbatch(x, self.pipeline_microbatches)
    out = pipeline_lib.pipeline_apply(stage_fn, stacked, mb, self.mesh,
                                      axis=self.pipe_axis,
                                      remat=self.pipeline_remat)
    return pipeline_lib.unmicrobatch(out)
