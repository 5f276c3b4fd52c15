"""The core of a gated short convolution as one Pallas kernel pair.

A gated short convolution (the token mixer of the hybrid convolution /
attention models: 18 of LFM2's 24 layers) projects the stream to three
chunks ``[B, C, X]``, multiplies two of them, runs a depthwise CAUSAL filter
of a few taps over the product and gates the result with the third:

    z_t = B_t * X_t
    c_t = w[:, 0] * z_{t-2} + w[:, 1] * z_{t-1} + w[:, 2] * z_t
    y_t = C_t * c_t

with zeros before a sequence's first token and nothing crossing from one
sequence of the batch to the next. ``short_conv`` is that core: the
projection ``bcx`` [batch, L, 3 d] in (the three chunks side by side, in that
order), ``y`` [batch, L, d] out, the filter ``w`` [d, 3] with its LAST tap
on the current token (``torch.nn.Conv1d``'s order under left padding). The
two projections around it are the layer's (``layers/transformer.py::
ShortConvolution``).

It is memory-bound: 4 d elements a token move forward (3 read, 1 written)
and 7 d backward (the projection and dy read; dB, dC, dX written), against
about 6 and 20 operations an element. So the pair's job is to move every
element ONCE: ``short_conv_fwd`` reads a tile of ``bcx`` whole, forms z, the
shifted sums and the gate in float32 in VMEM and writes ``y``;
``short_conv_bwd`` reads the same tile and ``dy``, forms z and c again
(cheaper than keeping either), and writes the gradient of the whole
projection ``[dB, dC, dX]`` as ONE array, so that the projection's own
backward product reads it as it stands, and the filter's gradient [d, 3],
summed over the grid in a float32 block that stays resident.

A tile is ``block_rows`` tokens of one sequence at the full width. The taps
reach two tokens back, so a tile also reads the ``HALO`` rows before it (a
16-row block: the tile of a bfloat16 array; zeroed for a sequence's first
tile), and the backward pass, whose dz_t needs dc_{t+1} and dc_{t+2}, the
16 rows behind it (zeroed for the last). Shifts by one and two rows are
sublane rotations (``pltpu.roll``) of the tile with its halo attached, read
back at an aligned offset: no unaligned slice, no branch on the row.

``short_conv_reference`` is the same mathematics in plain ``jax.numpy``
(three shifted products): the oracle of the tests and the path off the TPU
and for shapes the kernels do not take (d not a multiple of 128, L not a
multiple of 16). Both kernels are ``jax.jit`` functions, so a program holds
one copy of each however many layers call them (PR 31's lesson), and each
carries its name into the instruction, which is how the benchmark's trace
finds them (``short_conv_fwd``, ``short_conv_bwd``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu import runtime

TAPS = 3
HALO = 16          # rows of the block that carries a tile's neighbours
_BLOCK_ROWS = (256, 128, 64, 32, 16)
_VMEM_LIMIT = 64 * 1024 * 1024


def short_conv_reference(bcx: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
  """[batch, L, 3 d], [d, 3] -> [batch, L, d]: the gated filter as three
  shifted products, in float32, returned at ``bcx``'s dtype."""
  d = w.shape[0]
  b, c, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
             for i in range(3))
  z = b * x
  w = w.astype(jnp.float32)
  conv = w[:, TAPS - 1] * z
  for back in range(1, TAPS):
    shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :z.shape[1]]
    conv = conv + w[:, TAPS - 1 - back] * shifted
  return (c * conv).astype(bcx.dtype)


def supported(length: int, d: int) -> bool:
  """Shapes the kernels take."""
  return length % HALO == 0 and d % 128 == 0


def _block_rows(length: int) -> int:
  return next(rows for rows in _BLOCK_ROWS if length % rows == 0)


def _shifted_back(tile, halo, back: int):
  """Rows t - back of ``tile`` [rows, d], its first rows from the end of
  ``halo`` [HALO, d]."""
  joined = jnp.concatenate([halo, tile], axis=0)
  return pltpu.roll(joined, back, 0)[HALO:]


def _shifted_ahead(tile, halo, ahead: int):
  """Rows t + ahead of ``tile``, its last rows from the start of ``halo``."""
  joined = jnp.concatenate([tile, halo], axis=0)
  return pltpu.roll(joined, joined.shape[0] - ahead, 0)[:tile.shape[0]]


def _gated_product(b_ref, x_ref, keep):
  """z = B * X of a block in float32; zeros where ``keep`` is false."""
  z = b_ref[0].astype(jnp.float32) * x_ref[0].astype(jnp.float32)
  return jnp.where(keep, z, 0.0)


def _filtered(z, z_before, w_ref):
  """(c, [z_{t-2}, z_{t-1}, z_t]) of a tile."""
  shifts = [_shifted_back(z, z_before, TAPS - 1 - tap)
            for tap in range(TAPS - 1)] + [z]
  conv = sum(w_ref[tap:tap + 1, :] * shifts[tap] for tap in range(TAPS))
  return conv, shifts


def _fwd_kernel(bcx_ref, b_before_ref, x_before_ref, w_ref, y_ref, *, d: int):
  i = pl.program_id(1)
  z = (bcx_ref[0, :, :d].astype(jnp.float32) *
       bcx_ref[0, :, 2 * d:].astype(jnp.float32))
  conv, _ = _filtered(z, _gated_product(b_before_ref, x_before_ref, i > 0),
                      w_ref)
  y_ref[0] = (bcx_ref[0, :, d:2 * d].astype(jnp.float32) * conv).astype(
      y_ref.dtype)


def _bwd_kernel(bcx_ref, b_before_ref, x_before_ref, c_after_ref, dy_ref,
                dy_after_ref, w_ref, dbcx_ref, dw_ref, *, d: int):
  n, i = pl.program_id(0), pl.program_id(1)
  last = pl.num_programs(1) - 1
  b = bcx_ref[0, :, :d].astype(jnp.float32)
  c = bcx_ref[0, :, d:2 * d].astype(jnp.float32)
  x = bcx_ref[0, :, 2 * d:].astype(jnp.float32)
  dy = dy_ref[0].astype(jnp.float32)
  conv, shifts = _filtered(
      b * x, _gated_product(b_before_ref, x_before_ref, i > 0), w_ref)
  d_conv = dy * c
  d_conv_after = _gated_product(dy_after_ref, c_after_ref, i < last)
  dz = w_ref[TAPS - 1:TAPS, :] * d_conv
  for ahead in range(1, TAPS):
    tap = TAPS - 1 - ahead
    dz = dz + w_ref[tap:tap + 1, :] * _shifted_ahead(d_conv, d_conv_after,
                                                      ahead)
  dbcx_ref[0, :, :d] = (dz * x).astype(dbcx_ref.dtype)
  dbcx_ref[0, :, d:2 * d] = (dy * conv).astype(dbcx_ref.dtype)
  dbcx_ref[0, :, 2 * d:] = (dz * b).astype(dbcx_ref.dtype)

  @pl.when(jnp.logical_and(n == 0, i == 0))
  def _():
    dw_ref[...] = jnp.zeros_like(dw_ref)

  for tap in range(TAPS):
    dw_ref[tap:tap + 1, :] += jnp.sum(d_conv * shifts[tap], axis=0,
                                      keepdims=True)


def _halo_specs(block_rows: int, d: int, chunks, ahead: bool, length: int):
  """Block specs of the HALO rows before (or behind) tile i, one for each
  of ``chunks`` (which d-wide column block of the array)."""
  per_tile = block_rows // HALO
  final = length // HALO - 1

  def index(chunk):
    if ahead:
      return lambda n, i: (n, jnp.minimum((i + 1) * per_tile, final), chunk)
    return lambda n, i: (n, jnp.maximum(i * per_tile - 1, 0), chunk)

  return [pl.BlockSpec((1, HALO, d), index(chunk)) for chunk in chunks]


def _filter_rows(w):
  """[d, 3] -> [8, d] float32: a tap a row (lanes along the channels)."""
  return jnp.pad(w.astype(jnp.float32).T, ((0, 8 - TAPS), (0, 0)))


@functools.partial(jax.jit, static_argnames=('interpret',))
def short_conv_fwd(bcx, w, interpret: Optional[bool] = None):
  """``short_conv``'s forward kernel: [batch, L, 3 d], [d, 3] -> [batch, L,
  d] at ``bcx``'s dtype."""
  if interpret is None:
    interpret = not runtime.on_tpu()
  batch, length, width = bcx.shape
  d = width // 3
  rows = _block_rows(length)
  tile = lambda n, i: (n, i, 0)
  return pl.pallas_call(
      functools.partial(_fwd_kernel, d=d),
      grid=(batch, length // rows),
      in_specs=[pl.BlockSpec((1, rows, width), tile)] + _halo_specs(
          rows, d, (0, 2), False, length) + [
              pl.BlockSpec((8, d), lambda n, i: (0, 0))],
      out_specs=pl.BlockSpec((1, rows, d), tile),
      out_shape=jax.ShapeDtypeStruct((batch, length, d), bcx.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'parallel'),
          vmem_limit_bytes=_VMEM_LIMIT),
      interpret=interpret,
      name='short_conv_fwd',
  )(bcx, bcx, bcx, _filter_rows(w))


@functools.partial(jax.jit, static_argnames=('interpret',))
def short_conv_bwd(bcx, w, dy, interpret: Optional[bool] = None):
  """``short_conv``'s backward kernel: (d_bcx [batch, L, 3 d] at ``bcx``'s
  dtype: dB, dC, dX side by side; d_w [d, 3] float32)."""
  if interpret is None:
    interpret = not runtime.on_tpu()
  batch, length, width = bcx.shape
  d = width // 3
  rows = _block_rows(length)
  tile = lambda n, i: (n, i, 0)
  resident = pl.BlockSpec((8, d), lambda n, i: (0, 0))
  d_bcx, d_w = pl.pallas_call(
      functools.partial(_bwd_kernel, d=d),
      grid=(batch, length // rows),
      in_specs=[pl.BlockSpec((1, rows, width), tile)] + _halo_specs(
          rows, d, (0, 2), False, length) + _halo_specs(
              rows, d, (1,), True, length) + [
                  pl.BlockSpec((1, rows, d), tile)] + _halo_specs(
                      rows, d, (0,), True, length) + [resident],
      out_specs=[pl.BlockSpec((1, rows, width), tile), resident],
      out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                 jax.ShapeDtypeStruct((8, d), jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('arbitrary', 'arbitrary'),
          vmem_limit_bytes=_VMEM_LIMIT),
      interpret=interpret,
      name='short_conv_bwd',
  )(bcx, bcx, bcx, bcx, dy, dy, _filter_rows(w))
  return d_bcx, d_w[:TAPS].T


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _short_conv_kernels(bcx, w, interpret):
  return short_conv_fwd(bcx, w, interpret=interpret)


def _kernels_fwd(bcx, w, interpret):
  return short_conv_fwd(bcx, w, interpret=interpret), (bcx, w)


def _kernels_bwd(interpret, residuals, dy):
  bcx, w = residuals
  d_bcx, d_w = short_conv_bwd(bcx, w, dy.astype(bcx.dtype),
                              interpret=interpret)
  return d_bcx, d_w.astype(w.dtype)


_short_conv_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def short_conv(bcx: jnp.ndarray, w: jnp.ndarray, mode: str = 'auto',
               interpret: Optional[bool] = None) -> jnp.ndarray:
  """y [batch, L, d] of the projection ``bcx`` [batch, L, 3 d] = [B, C, X]
  and the filter ``w`` [d, 3] (module docstring), differentiable in both.

  ``mode``: ``'pallas'`` the kernel pair (on the interpreter off the TPU
  unless ``interpret`` says otherwise), ``'xla'`` the plain ``jax.numpy``
  formulation, ``'auto'`` the kernels on the TPU for shapes they take and
  the plain formulation elsewhere."""
  if w.shape != (bcx.shape[-1] // 3, TAPS) or bcx.shape[-1] % 3:
    raise ValueError('short_conv wants bcx [.., 3 d] and w [d, {}]; got {} '
                     'and {}.'.format(TAPS, bcx.shape, w.shape))
  if mode not in ('auto', 'pallas', 'xla'):
    raise ValueError('mode {!r} is none of auto, pallas, xla.'.format(mode))
  takes = supported(bcx.shape[1], w.shape[0])
  if mode == 'pallas' and not takes:
    raise ValueError('the short_conv kernels want L a multiple of {} and d '
                     'a multiple of 128; got {}.'.format(HALO, bcx.shape))
  if mode == 'xla' or (mode == 'auto' and not (takes and runtime.on_tpu())):
    return short_conv_reference(bcx, w)
  return _short_conv_kernels(bcx, w, interpret)
