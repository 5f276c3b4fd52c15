"""Manifold-constrained hyper-connections (mHC) as Pallas kernels.

A token carries ``n`` residual streams in place of one (Zhu et al.,
arXiv:2409.19606; the doubly stochastic form: Xie et al., arXiv:2512.24880).
Each sublayer F of a block reads a mix of the streams and writes its output
back through a second map, and the streams are mixed among themselves by a
matrix that Sinkhorn-Knopp makes doubly stochastic. Per token, X [n, C]:

    x'   = X / rms(vec X)                                  (no learned scale)
    z    = x' phi                                          phi [n C, n (n + 2)]
    pre  = sigmoid(a_pre z_pre + b_pre)                    [n]
    post = 2 sigmoid(a_post z_post + b_post)               [n]
    res  = SK(exp(clip(a_res z_res + b_res, -clamp, clamp)))   [n, n]
    h    = sum_j pre_j X_j            -> the sublayer computes f = F(h)
    X'_i = sum_j res_ij X_j + post_i f

SK runs ``iters`` iterations, each dividing the matrix by its row sums and
then by its column sums, ``eps`` added to every sum. The state X is float32
and lies in HBM as ``[rows, n C]``: stream j is the lanes j C .. (j + 1) C of
a row, so a stream is a lane-aligned slice wherever C is a multiple of 128.

The work is MEMORY-bound: a sublayer reads and writes n x C float32 a token
and does a few operations an element. So each pass moves every element once:

  ``hc_pre_fwd``   reads X, forms x', z (one product on the MXU against
                   phi held whole in VMEM), the maps and h: writes h [rows,
                   C] float32 and the maps [rows, 32] (pre, post, res in
                   lanes 0 .. n (n + 2) - 1)
  ``hc_post_fwd``  reads X, f and the maps, writes X'
  ``hc_post_bwd``  reads X, f, dX' and the maps, writes dX (the part through
                   res), df and d(post, res)
  ``hc_pre_bwd``   reads X, dh, that dX and d(post, res); forms the maps
                   again and takes them back through SK, the sigmoids and
                   the norm; writes the whole dX (in place of the part it
                   read) and accumulates d phi and the sums the gains' and
                   biases' gradients are made of over the grid, in resident
                   float32 blocks

A tile is a block of ROWS OF TOKENS at the full width n C: the norm is over
the whole row and every map is a token's own, so a tile of whole rows needs
nothing from any other tile, and the streams of a token are lane slices of
the same block. Rows per tile are the most (a power of two, at most 256)
that divide the rows and keep one tile's blocks under ``_TILE_BYTES``.

Inside a tile the maps are computed with the tokens along the LANES: the
projection is formed transposed, z^T = phi^T x'^T [32, tile] (the NT form of
q k^T), so each of the 24 pre-activations is one row of the tile's tokens
and the 20 iterations of SK on n x n a token are a few hundred vector
operations a tile on rows of that block (VPU work). The maps go to HBM and
to the mixes transposed back, a token a row, where each map is a column
broadcast over a stream's C lanes. The transposes want tiles of 128 rows
or more on the TPU.

``hc_pre`` hands the state on to ``hc_post`` as a third result: so the post
kernel's dX reaches the pre kernel's backward as that result's cotangent,
and the kernel adds its own part in place; no separate pass sums the two.

``hc_pre_reference`` and ``hc_post_reference`` are the same mathematics in
plain ``jax.numpy``: the path off the TPU and the oracle of the tests. All
four kernels are ``jax.jit`` functions (one copy a program) carrying their
names into the instruction, which is how a trace finds them. When a kernel
is traced it sets the gauge ``hc/bytes_per_token/<kernel>``: the bytes it
moves a token (operands read once, results written once).

Named arrays. The forward rules of the two custom VJPs name, with
``jax.ad_checkpoint.checkpoint_name``, what a sublayer's backward reads
beside the state it was handed: h and the maps (HC_H, HC_MAPS: the norm and
projections of the sublayer take their gradients from h, the post kernel's
backward reads the maps), the sublayer's output f as the post kernel reads
it (HC_F) and the post kernel's result X' (HC_STATE: the next sublayer's
state). BACKWARD_READS holds the four. As for the flash kernels' names, a
name is the identity unless a ``jax.checkpoint`` policy asks for it; one
that does keeps the arrays, and its backward pass runs neither forward
kernel again nor the products that made f. The plain formulation names
nothing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu import runtime

MAP_ROWS = 32            # pre, post and res maps of n <= 4, padded to sublanes
_TILE_ROWS = (256, 128, 64, 32, 16, 8)
_TILE_BYTES = 24 * 1024 * 1024
_VMEM_LIMIT = 96 * 1024 * 1024
KERNELS = ('hc_pre_fwd', 'hc_post_fwd', 'hc_post_bwd', 'hc_pre_bwd')

# Names of what a sublayer's backward reads (module docstring).
HC_H = 'hc_h'
HC_MAPS = 'hc_maps'
HC_F = 'hc_f'
HC_STATE = 'hc_state'
BACKWARD_READS = (HC_H, HC_MAPS, HC_F, HC_STATE)


def map_count(n: int) -> int:
  """Pre-activations a token: n for pre, n for post, n x n for res."""
  return n * (n + 2)


# -- the maps, on lists of same-shaped arrays --------------------------------
#
# Every map is elementwise across tokens: the kernels compute them on a list
# of [1, tile] rows (tokens along the lanes) and take them back by
# ``_maps_backward``; the plain formulation below computes the same on
# arrays and is differentiated by JAX.


def _sigmoid(x):
  return 1.0 / (1.0 + jnp.exp(-x))


def _groups(n: int):
  """Flat indices of the rows, then of the columns, of an n x n matrix."""
  rows = [[i * n + j for j in range(n)] for i in range(n)]
  return rows, [list(column) for column in zip(*rows)]


def _sinkhorn(m, n: int, iters: int, eps: float):
  """SK on a flat list of n x n entries: ``iters`` times, rows then columns.
  Returns (result, [(entries after each half-step, 1 / sums)])."""
  saved = []
  for _ in range(iters):
    for groups in _groups(n):
      m = list(m)
      inverses = []
      for group in groups:
        inverse = 1.0 / (sum(m[k] for k in group) + eps)
        inverses.append(inverse)
        for k in group:
          m[k] = m[k] * inverse
      saved.append((m, inverses))
  return m, saved


def stream_maps(z, alpha, bias, n: int, iters: int, eps: float,
                clamp: float):
  """(pre, post, res, saved): the maps of the pre-activations ``z`` (a list
  of n (n + 2) arrays), ``alpha`` (a_pre, a_post, a_res) and ``bias`` (one a
  pre-activation); ``saved`` is what ``_maps_backward`` reads."""
  count = map_count(n)
  pre = [_sigmoid(alpha[0] * z[j] + bias[j]) for j in range(n)]
  post = [2.0 * _sigmoid(alpha[1] * z[n + j] + bias[n + j]) for j in range(n)]
  logits = [alpha[2] * z[k] + bias[k] for k in range(2 * n, count)]
  clipped = [jnp.clip(w, -clamp, clamp) for w in logits]
  start = [jnp.exp(w) for w in clipped]
  res, steps = _sinkhorn(start, n, iters, eps)
  return pre, post, res, (logits, start, steps)


def _maps_backward(d_pre, d_post, d_res, pre, post, saved, n: int,
                   clamp: float):
  """The gradient of the n (n + 2) pre-activations a_g z_k + b_k (the
  arguments of the sigmoids and of the clip) from that of the maps."""
  logits, start, steps = saved
  d_m = list(d_res)
  for (out, inverses), groups in zip(
      reversed(steps), [_groups(n)[1], _groups(n)[0]] * (len(steps) // 2)):
    # Y = X / (sum X + eps) in a group: dX = (dY - sum_g dY Y) / (sum + eps).
    d_in = list(d_m)
    for group, inverse in zip(groups, inverses):
      dot = sum(d_m[k] * out[k] for k in group)
      for k in group:
        d_in[k] = (d_m[k] - dot) * inverse
    d_m = d_in
  d_u = [d_pre[j] * pre[j] * (1.0 - pre[j]) for j in range(n)]
  d_u += [d_post[j] * post[j] * (1.0 - 0.5 * post[j]) for j in range(n)]
  d_u += [jnp.where(jnp.abs(w) < clamp, d * e, 0.0)
          for d, e, w in zip(d_m, start, logits)]
  return d_u


# -- the plain formulation ---------------------------------------------------


def _rms(x, eps: float):
  return jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def hc_pre_reference(x, phi, alpha, bias, *, n: int, iters: int, eps: float,
                     clamp: float):
  """(h [rows, C] f32, maps [rows, 32] f32) of the state ``x`` [rows, n C]
  f32; ``phi`` [n C, n (n + 2)] at the dtype the projection runs in. The
  maps as arrays, SK a ``fori_loop`` (one body to trace and differentiate,
  where the kernels unroll it)."""
  c = x.shape[-1] // n
  x = x.astype(jnp.float32)
  normed = (x * _rms(x, eps)).astype(phi.dtype)
  z = jnp.dot(normed, phi, preferred_element_type=jnp.float32)
  pre = _sigmoid(alpha[0] * z[:, :n] + bias[:n])
  post = 2.0 * _sigmoid(alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
  logits = jnp.clip(alpha[2] * z[:, 2 * n:] + bias[2 * n:], -clamp, clamp)

  def iteration(_, m):
    m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
    return m / (jnp.sum(m, axis=1, keepdims=True) + eps)

  res = jax.lax.fori_loop(0, iters, iteration,
                          jnp.exp(logits).reshape(-1, n, n))
  h = jnp.einsum('rj,rjc->rc', pre, x.reshape(-1, n, c))
  maps = jnp.concatenate([pre, post, res.reshape(-1, n * n)], axis=1)
  return h, jnp.pad(maps, ((0, 0), (0, MAP_ROWS - maps.shape[1])))


def hc_post_reference(x, f, maps, *, n: int):
  """X' [rows, n C] f32 of the state ``x``, the sublayer's output ``f``
  [rows, C] and ``maps`` (``hc_pre_reference``'s)."""
  c = f.shape[-1]
  x = x.astype(jnp.float32)
  f = f.astype(jnp.float32)
  streams = [maps[:, n + i:n + i + 1] * f + sum(
      maps[:, 2 * n + i * n + j:2 * n + i * n + j + 1] *
      x[:, j * c:(j + 1) * c] for j in range(n)) for i in range(n)]
  return jnp.concatenate(streams, axis=-1)


# -- the kernels -------------------------------------------------------------


def _scalars(s_ref, n: int):
  """(alpha, bias) from the SMEM vector [bias (n (n + 2)), alpha (3)]."""
  count = map_count(n)
  return [s_ref[count + g] for g in range(3)], [s_ref[k] for k in range(count)]


def _projected(x_ref, phi_ref, eps: float):
  """(x [tile, n C] f32, 1 / rms [tile, 1], x' at phi's dtype, z^T [32,
  tile] f32)."""
  x = x_ref[...].astype(jnp.float32)
  inverse_rms = _rms(x, eps)
  normed = (x * inverse_rms).astype(phi_ref.dtype)
  z_t = jax.lax.dot_general(phi_ref[...], normed, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
  return x, inverse_rms, normed, z_t


def _as_block(rows, tile: int):
  """A list of [1, tile] rows -> [32, tile], zeros below."""
  pad = [jnp.zeros((1, tile), jnp.float32)] * (MAP_ROWS - len(rows))
  return jnp.concatenate(list(rows) + pad, axis=0)


def _column(block, k: int):
  """Column k of a [tile, 32] block, [tile, 1]."""
  return block[:, k:k + 1]


def _pre_fwd_kernel(s_ref, x_ref, phi_ref, h_ref, maps_ref, *, n, c, iters,
                    eps, clamp):
  tile = x_ref.shape[0]
  x, _, _, z_t = _projected(x_ref, phi_ref, eps)
  alpha, bias = _scalars(s_ref, n)
  pre, post, res, _ = stream_maps(
      [z_t[k:k + 1, :] for k in range(map_count(n))], alpha, bias, n, iters,
      eps, clamp)
  maps = _as_block(pre + post + res, tile).T                  # [tile, 32]
  maps_ref[...] = maps
  h_ref[...] = sum(_column(maps, j) * x[:, j * c:(j + 1) * c]
                   for j in range(n)).astype(h_ref.dtype)


def _post_fwd_kernel(maps_ref, x_ref, f_ref, out_ref, *, n, c):
  maps = maps_ref[...]
  f = f_ref[...].astype(jnp.float32)
  for i in range(n):
    stream = _column(maps, n + i) * f
    for j in range(n):
      stream = stream + _column(maps, 2 * n + i * n + j) * x_ref[
          :, j * c:(j + 1) * c]
    out_ref[:, i * c:(i + 1) * c] = stream


def _post_bwd_kernel(maps_ref, x_ref, f_ref, g_ref, dx_ref, df_ref,
                     dmaps_ref, *, n, c):
  maps = maps_ref[...]
  f = f_ref[...].astype(jnp.float32)
  lane = jax.lax.broadcasted_iota(jnp.int32, maps.shape, 1)
  d_maps = jnp.zeros(maps.shape, jnp.float32)
  d_f = None
  for i in range(n):
    g = g_ref[:, i * c:(i + 1) * c]
    part = _column(maps, n + i) * g
    d_f = part if d_f is None else d_f + part
    d_maps = jnp.where(lane == n + i, jnp.sum(g * f, axis=1, keepdims=True),
                       d_maps)
    for j in range(n):
      d_maps = jnp.where(
          lane == 2 * n + i * n + j,
          jnp.sum(g * x_ref[:, j * c:(j + 1) * c], axis=1, keepdims=True),
          d_maps)
  for j in range(n):
    dx_ref[:, j * c:(j + 1) * c] = sum(
        _column(maps, 2 * n + i * n + j) * g_ref[:, i * c:(i + 1) * c]
        for i in range(n))
  df_ref[...] = d_f.astype(df_ref.dtype)
  dmaps_ref[...] = d_maps


def _pre_bwd_kernel(s_ref, x_ref, phi_ref, dh_ref, dxp_ref, dmaps_ref,
                    dx_ref, dphi_ref, du_ref, *, n, c, iters, eps, clamp):
  tile = x_ref.shape[0]
  count = map_count(n)
  x, inverse_rms, normed, z_t = _projected(x_ref, phi_ref, eps)
  alpha, bias = _scalars(s_ref, n)
  z = [z_t[k:k + 1, :] for k in range(count)]
  pre, post, _, saved = stream_maps(z, alpha, bias, n, iters, eps, clamp)
  dh = dh_ref[...].astype(jnp.float32)
  lane = jax.lax.broadcasted_iota(jnp.int32, (tile, MAP_ROWS), 1)
  # d pre_j = <dh, X_j>; d post and d res are the post kernel's.
  d_maps = jnp.where(lane < n, 0.0, dmaps_ref[...])
  for j in range(n):
    d_maps = jnp.where(
        lane == j, jnp.sum(dh * x[:, j * c:(j + 1) * c], axis=1,
                           keepdims=True), d_maps)
  d_rows = d_maps.T                                            # [32, tile]
  d_u = _maps_backward(
      [d_rows[k:k + 1, :] for k in range(n)],
      [d_rows[k:k + 1, :] for k in range(n, 2 * n)],
      [d_rows[k:k + 1, :] for k in range(2 * n, count)], pre, post, saved, n,
      clamp)
  groups = [0] * n + [1] * n + [2] * (n * n)
  d_z = _as_block([alpha[g] * d for g, d in zip(groups, d_u)], tile)

  @pl.when(pl.program_id(0) == 0)
  def _():
    dphi_ref[...] = jnp.zeros_like(dphi_ref)
    du_ref[...] = jnp.zeros_like(du_ref)

  # The scalars' gradients, summed over the grid: d bias_k = sum d u_k, d
  # alpha_g = sum over its k of d u_k z_k (tokens along the lanes).
  du_ref[...] += jnp.concatenate(
      [_as_block(d_u, tile), _as_block([d * w for d, w in zip(d_u, z)],
                                       tile)], axis=0)
  d_z = d_z.astype(phi_ref.dtype)
  dphi_ref[...] += jax.lax.dot_general(
      d_z, normed, (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32)
  d_normed = jax.lax.dot_general(d_z, phi_ref[...], (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
  scaled = x * inverse_rms
  centred = jnp.mean(d_normed * scaled, axis=1, keepdims=True)
  dx = dxp_ref[...] + inverse_rms * (d_normed - scaled * centred)
  pre_columns = _as_block(pre, tile).T
  for j in range(n):
    dx_ref[:, j * c:(j + 1) * c] = (dx[:, j * c:(j + 1) * c] +
                                    _column(pre_columns, j) * dh)


def _tile_rows(rows: int, bytes_per_row: int) -> int:
  for tile in _TILE_ROWS:
    if rows % tile == 0 and tile * bytes_per_row <= _TILE_BYTES:
      return tile
  raise ValueError('no tile of {} divides {} rows'.format(_TILE_ROWS, rows))


def supported(rows: int, c: int) -> bool:
  """Shapes the kernels take: streams of whole 128-lane columns, rows of
  whole 128-row tiles (a tile's maps are transposed in VMEM)."""
  return c % 128 == 0 and rows % 128 == 0


def call_bytes(kernel: str, n: int, c: int, f_itemsize: int = 2) -> int:
  """Bytes ``kernel`` moves a token: every operand read once and every
  result written once (phi and its gradient, which move once a call, and
  the scalars' sums are not counted). The state and h are float32, f and df
  ``f_itemsize`` bytes, the maps and their gradient 32 float32."""
  state, h, f, maps = 4 * n * c, 4 * c, f_itemsize * c, 4 * MAP_ROWS
  return {
      'hc_pre_fwd': state + h + maps,
      'hc_post_fwd': maps + state + f + state,
      'hc_post_bwd': maps + state + f + state + state + f + maps,
      'hc_pre_bwd': state + h + state + maps + state,
  }[kernel]


def kept_bytes_per_token(rows: int, n: int, c: int, f_itemsize: int = 2,
                         mode: str = 'auto') -> int:
  """Bytes a token that a checkpoint keeping BACKWARD_READS holds for a
  block of two sublayers over ``rows`` tokens, beside the block's input: h,
  the maps and f of each sublayer and the state between them (the second
  sublayer's X' is the block's output, which the next block keeps as its
  input anyway). 0 where ``mode`` picks the plain formulation, which names
  nothing."""
  if not _use_kernels(mode, rows, c):
    return 0
  return 2 * (4 * c + 4 * MAP_ROWS + f_itemsize * c) + 4 * n * c


def _gauge(kernel: str, n: int, c: int, f_itemsize: int = 2):
  from tensor2robot_tpu.observability import get_registry

  get_registry().gauge('hc/bytes_per_token/' + kernel).set(
      float(call_bytes(kernel, n, c, f_itemsize)))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret,
          accumulate=False, **kwargs):
  if interpret is None:
    interpret = not runtime.on_tpu()
  return pl.pallas_call(
      kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
      out_shape=out_shape,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('arbitrary' if accumulate else 'parallel',),
          vmem_limit_bytes=_VMEM_LIMIT),
      interpret=interpret, name=name, **kwargs)


def _packed(alpha, bias):
  """The SMEM vector [bias, alpha] (``_scalars``)."""
  return jnp.concatenate([jnp.reshape(bias, (-1,)), jnp.reshape(alpha, (-1,))
                          ]).astype(jnp.float32)


def _phi_rows(phi):
  """phi [n C, count] -> phi^T [32, n C] at phi's dtype, zeros below."""
  return jnp.pad(phi.T, ((0, MAP_ROWS - phi.shape[1]), (0, 0)))


_STATIC = ('n', 'iters', 'eps', 'clamp', 'interpret')
_ROW = lambda i: (i, 0)
_WHOLE = lambda i: (0, 0)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hc_pre_fwd(x, phi, alpha, bias, *, n, iters, eps, clamp,
               interpret: Optional[bool] = None):
  """The pre kernel: (h [rows, C] f32, maps [rows, 32] f32)."""
  rows, width = x.shape
  c = width // n
  _gauge('hc_pre_fwd', n, c)
  tile = _tile_rows(rows, call_bytes('hc_pre_fwd', n, c))
  return _call(
      functools.partial(_pre_fwd_kernel, n=n, c=c, iters=iters, eps=eps,
                        clamp=clamp), 'hc_pre_fwd', (rows // tile,),
      [pl.BlockSpec(memory_space=pltpu.SMEM),
       pl.BlockSpec((tile, width), _ROW),
       pl.BlockSpec((MAP_ROWS, width), _WHOLE)],
      [pl.BlockSpec((tile, c), _ROW), pl.BlockSpec((tile, MAP_ROWS), _ROW)],
      [jax.ShapeDtypeStruct((rows, c), jnp.float32),
       jax.ShapeDtypeStruct((rows, MAP_ROWS), jnp.float32)], interpret,
  )(_packed(alpha, bias), x, _phi_rows(phi))


@functools.partial(jax.jit, static_argnames=('n', 'interpret'))
def hc_post_fwd(x, f, maps, *, n, interpret: Optional[bool] = None):
  """The post kernel: X' [rows, n C] f32."""
  rows, width = x.shape
  c = width // n
  _gauge('hc_post_fwd', n, c, f.dtype.itemsize)
  tile = _tile_rows(rows, call_bytes('hc_post_fwd', n, c, f.dtype.itemsize))
  return _call(
      functools.partial(_post_fwd_kernel, n=n, c=c), 'hc_post_fwd',
      (rows // tile,),
      [pl.BlockSpec((tile, MAP_ROWS), _ROW),
       pl.BlockSpec((tile, width), _ROW), pl.BlockSpec((tile, c), _ROW)],
      pl.BlockSpec((tile, width), _ROW),
      jax.ShapeDtypeStruct((rows, width), jnp.float32), interpret,
  )(maps, x, f)


@functools.partial(jax.jit, static_argnames=('n', 'interpret'))
def hc_post_bwd(x, f, maps, g, *, n, interpret: Optional[bool] = None):
  """The post kernel's backward: (dX through res [rows, n C] f32, df at f's
  dtype, d maps [rows, 32] f32 in the post and res lanes)."""
  rows, width = x.shape
  c = width // n
  _gauge('hc_post_bwd', n, c, f.dtype.itemsize)
  tile = _tile_rows(rows, call_bytes('hc_post_bwd', n, c, f.dtype.itemsize))
  return _call(
      functools.partial(_post_bwd_kernel, n=n, c=c), 'hc_post_bwd',
      (rows // tile,),
      [pl.BlockSpec((tile, MAP_ROWS), _ROW),
       pl.BlockSpec((tile, width), _ROW), pl.BlockSpec((tile, c), _ROW),
       pl.BlockSpec((tile, width), _ROW)],
      [pl.BlockSpec((tile, width), _ROW), pl.BlockSpec((tile, c), _ROW),
       pl.BlockSpec((tile, MAP_ROWS), _ROW)],
      [jax.ShapeDtypeStruct((rows, width), jnp.float32),
       jax.ShapeDtypeStruct((rows, c), f.dtype),
       jax.ShapeDtypeStruct((rows, MAP_ROWS), jnp.float32)], interpret,
  )(maps, x, f, g)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hc_pre_bwd(x, phi, alpha, bias, dh, dx_part, d_maps, *, n, iters, eps,
               clamp, interpret: Optional[bool] = None):
  """The pre kernel's backward: (dX [rows, n C] f32, d phi [n C, count]
  f32, d alpha [3], d bias [count]). ``d_maps`` holds d post and d res (the
  post kernel's; its pre lanes are not read); ``dx_part`` is dX through the
  post kernel, and its buffer becomes dX."""
  rows, width = x.shape
  c = width // n
  count = map_count(n)
  _gauge('hc_pre_bwd', n, c)
  tile = _tile_rows(rows, call_bytes('hc_pre_bwd', n, c))
  dx, dphi_t, sums = _call(
      functools.partial(_pre_bwd_kernel, n=n, c=c, iters=iters, eps=eps,
                        clamp=clamp), 'hc_pre_bwd', (rows // tile,),
      [pl.BlockSpec(memory_space=pltpu.SMEM),
       pl.BlockSpec((tile, width), _ROW),
       pl.BlockSpec((MAP_ROWS, width), _WHOLE),
       pl.BlockSpec((tile, c), _ROW), pl.BlockSpec((tile, width), _ROW),
       pl.BlockSpec((tile, MAP_ROWS), _ROW)],
      [pl.BlockSpec((tile, width), _ROW),
       pl.BlockSpec((MAP_ROWS, width), _WHOLE),
       pl.BlockSpec((2 * MAP_ROWS, tile), _WHOLE)],
      [jax.ShapeDtypeStruct((rows, width), jnp.float32),
       jax.ShapeDtypeStruct((MAP_ROWS, width), jnp.float32),
       jax.ShapeDtypeStruct((2 * MAP_ROWS, tile), jnp.float32)], interpret,
      accumulate=True, input_output_aliases={4: 0},
  )(_packed(alpha, bias), x, _phi_rows(phi), dh, dx_part, d_maps)
  sums = jnp.sum(sums, axis=1)
  d_alpha = jnp.stack([jnp.sum(sums[MAP_ROWS + lo:MAP_ROWS + hi])
                       for lo, hi in ((0, n), (n, 2 * n), (2 * n, count))])
  return dx, dphi_t[:count].T, d_alpha, sums[:count]


# -- differentiable entry points ---------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _pre_kernels(x, phi, alpha, bias, n, iters, eps, clamp, interpret):
  h, maps = hc_pre_fwd(x, phi, alpha, bias, n=n, iters=iters, eps=eps,
                       clamp=clamp, interpret=interpret)
  return h, maps, x


def _pre_kernels_fwd(x, phi, alpha, bias, n, iters, eps, clamp, interpret):
  h, maps = hc_pre_fwd(x, phi, alpha, bias, n=n, iters=iters, eps=eps,
                       clamp=clamp, interpret=interpret)
  # The state leaves as the array that came in, so a checkpoint that keeps
  # h and the maps has nothing of this call left to run again.
  return ((checkpoint_name(h, HC_H), checkpoint_name(maps, HC_MAPS), x),
          (x, phi, alpha, bias))


def _pre_kernels_bwd(n, iters, eps, clamp, interpret, residuals, cotangents):
  x, phi, alpha, bias = residuals
  dh, d_maps, dx_part = cotangents
  dx, d_phi, d_alpha, d_bias = hc_pre_bwd(
      x, phi, alpha, bias, dh, dx_part, d_maps, n=n, iters=iters, eps=eps,
      clamp=clamp, interpret=interpret)
  return (dx, d_phi.astype(phi.dtype),
          d_alpha.reshape(alpha.shape).astype(alpha.dtype),
          d_bias.reshape(bias.shape).astype(bias.dtype))


_pre_kernels.defvjp(_pre_kernels_fwd, _pre_kernels_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _post_kernels(x, f, maps, n, interpret):
  return hc_post_fwd(x, f, maps, n=n, interpret=interpret)


def _post_kernels_fwd(x, f, maps, n, interpret):
  f = checkpoint_name(f, HC_F)
  out = checkpoint_name(hc_post_fwd(x, f, maps, n=n, interpret=interpret),
                        HC_STATE)
  return out, (x, f, maps)


def _post_kernels_bwd(n, interpret, residuals, g):
  x, f, maps = residuals
  return hc_post_bwd(x, f, maps, g, n=n, interpret=interpret)


_post_kernels.defvjp(_post_kernels_fwd, _post_kernels_bwd)


def _use_kernels(mode: str, rows: int, c: int) -> bool:
  if mode not in ('auto', 'pallas', 'xla'):
    raise ValueError('mode {!r} is none of auto, pallas, xla.'.format(mode))
  takes = supported(rows, c)
  if mode == 'pallas' and not takes:
    raise ValueError('the hyper-connection kernels want C a multiple of 128 '
                     'and rows a multiple of 128; got {} rows of C {}.'.format(
                         rows, c))
  return mode == 'pallas' or (mode == 'auto' and takes and runtime.on_tpu())


def hc_pre(x, phi, alpha, bias, *, n: int, iters: int, eps: float,
           clamp: float, mode: str = 'auto',
           interpret: Optional[bool] = None):
  """(h [rows, C] f32, maps [rows, 32] f32, the state to hand to
  ``hc_post``) of the state ``x`` [rows, n C] f32 (module docstring),
  differentiable in x, phi, alpha and bias. ``mode``: ``'pallas'`` the
  kernels (on the interpreter off the TPU unless ``interpret`` says
  otherwise), ``'xla'`` the plain formulation, ``'auto'`` the kernels on the
  TPU for shapes they take and the plain formulation elsewhere."""
  if _use_kernels(mode, x.shape[0], x.shape[1] // n):
    return _pre_kernels(x, phi, alpha, bias, n, iters, eps, clamp, interpret)
  h, maps = hc_pre_reference(x, phi, alpha, bias, n=n, iters=iters, eps=eps,
                             clamp=clamp)
  return h, maps, x


def hc_post(x, f, maps, *, n: int, mode: str = 'auto',
            interpret: Optional[bool] = None):
  """X' [rows, n C] f32 (module docstring) of the state ``hc_pre`` handed
  on, the sublayer's output ``f`` [rows, C] and the maps; differentiable in
  all three."""
  if _use_kernels(mode, x.shape[0], x.shape[1] // n):
    return _post_kernels(x, f, maps, n, interpret)
  return hc_post_reference(x, f, maps, n=n)


def res_stochastic_error(maps, n: int):
  """The largest |row or column sum - 1| of res over the tokens of
  ``maps`` [rows, 32]."""
  res = maps[:, 2 * n:map_count(n)].reshape(-1, n, n)
  return jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),
                     jnp.max(jnp.abs(jnp.sum(res, axis=2) - 1.0)))
