"""Pallas grouped matrix products over row tiles that each belong to one group.

The dropless expert layer (layers/moe.py) lays the (token, expert) pairs it
holds out in rows, expert after expert, each expert's rows padded up to a
whole number of ``block_m``-row tiles. Every tile then belongs to exactly
one expert, and the three products of an expert MLP and of its backward pass
become dense tile products whose weight block is chosen per tile:

  grouped_matmul      out[tile i]  = lhs[tile i] @ rhs[group(i)]      (or rhs^T)
  grouped_matmul_dw   out[g]       = sum over tiles i of g: lhs[tile i]^T @ dout[tile i]

``tile_group`` ([tiles] int32) and ``num_tiles`` (how many leading tiles are
in use; the layer's buffer is sized for the worst routing) ride in as scalar
prefetch. Tiles past ``num_tiles`` are skipped, and their block indices are
clamped to the last tile in use so that they fetch nothing either: their
output rows are left as they were (uninitialised), and nothing may read
them. The grid runs the rows innermost, so a weight block stays resident
over the consecutive tiles of its expert and every weight is read once.

``grouped_matmul`` is differentiable (custom VJP): d lhs is the same kernel
against the transposed weights, d rhs is ``grouped_matmul_dw``, with the
groups that own no tile zeroed outside the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu import runtime


def _dividing(size: int, candidates) -> int:
  """The first candidate that divides ``size``; ``size`` itself if none
  does (a block equal to the whole dimension is always legal)."""
  for candidate in candidates:
    if candidate <= size and size % candidate == 0:
      return candidate
  return size


def _tile_in_use(i, num_tiles_ref):
  """Tile ``i``, or the last tile in use for the tiles past it, so that a
  skipped step names the block already resident and fetches nothing."""
  return jnp.minimum(i, jnp.maximum(num_tiles_ref[0] - 1, 0))


def _matmul_kernel(tile_group_ref, num_tiles_ref, lhs_ref, rhs_ref, out_ref,
                   *, transpose_rhs: bool):
  del tile_group_ref

  @pl.when(pl.program_id(1) < num_tiles_ref[0])
  def _():
    contract = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], contract,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _grouped_matmul_call(lhs, rhs, tile_group, num_tiles, *, block_m: int,
                         transpose_rhs: bool, interpret: bool):
  m, k = lhs.shape
  n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
  block_n = _dividing(n, (512, 384, 256, 128))

  def rhs_index(j, i, tile_group_ref, num_tiles_ref):
    group = tile_group_ref[_tile_in_use(i, num_tiles_ref)]
    return (group, j, 0) if transpose_rhs else (group, 0, j)

  rhs_block = (1, block_n, k) if transpose_rhs else (1, k, block_n)
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(n // block_n, m // block_m),
      in_specs=[
          pl.BlockSpec((block_m, k), lambda j, i, tg, nt: (_tile_in_use(i, nt), 0)),
          pl.BlockSpec(rhs_block, rhs_index),
      ],
      out_specs=pl.BlockSpec((block_m, block_n),
                             lambda j, i, tg, nt: (_tile_in_use(i, nt), j)),
  )
  return pl.pallas_call(
      functools.partial(_matmul_kernel, transpose_rhs=transpose_rhs),
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
      interpret=interpret,
      name='moe_grouped_matmul_nt' if transpose_rhs else 'moe_grouped_matmul',
  )(tile_group, num_tiles, lhs, rhs)


def _dw_kernel(tile_group_ref, num_tiles_ref, lhs_ref, dout_ref, out_ref,
               acc_ref):
  i = pl.program_id(2)
  last_tile = pl.num_programs(2) - 1
  num_tiles = num_tiles_ref[0]
  group = tile_group_ref[i]
  opens = jnp.logical_or(i == 0,
                         tile_group_ref[jnp.maximum(i - 1, 0)] != group)
  closes = jnp.logical_or(
      i == num_tiles - 1,
      tile_group_ref[jnp.minimum(i + 1, last_tile)] != group)

  @pl.when(i < num_tiles)
  def _():
    @pl.when(opens)
    def _():
      acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(closes)
    def _():
      out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def grouped_matmul_dw(lhs, dout, tile_group, num_tiles, num_groups: int, *,
                      block_m: int, interpret: Optional[bool] = None):
  """[G, K, N]: for each group the sum over its tiles of lhs^T @ dout.

  A group that owns none of the first ``num_tiles`` tiles is zero."""
  if interpret is None:
    interpret = not runtime.on_tpu()
  m, k = lhs.shape
  n = dout.shape[1]
  block_k = _dividing(k, (1280, 1024, 768, 512, 256, 128))
  block_n = _dividing(n, (512, 384, 256, 128))

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(k // block_k, n // block_n, m // block_m),
      in_specs=[
          pl.BlockSpec((block_m, block_k),
                       lambda a, b, i, tg, nt: (_tile_in_use(i, nt), a)),
          pl.BlockSpec((block_m, block_n),
                       lambda a, b, i, tg, nt: (_tile_in_use(i, nt), b)),
      ],
      out_specs=pl.BlockSpec(
          (1, block_k, block_n),
          lambda a, b, i, tg, nt: (tg[_tile_in_use(i, nt)], a, b)),
      scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
  )
  out = pl.pallas_call(
      _dw_kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((num_groups, k, n), lhs.dtype),
      interpret=interpret,
      name='moe_grouped_matmul_dw',
  )(tile_group, num_tiles, lhs, dout)
  in_use = jnp.arange(tile_group.shape[0]) < num_tiles[0]
  owns_a_tile = jnp.zeros((num_groups,), bool).at[tile_group].max(in_use)
  return jnp.where(owns_a_tile[:, None, None], out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped_matmul(lhs, rhs, tile_group, num_tiles, block_m, interpret):
  return _grouped_matmul_call(lhs, rhs, tile_group, num_tiles,
                              block_m=block_m, transpose_rhs=False,
                              interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, tile_group, num_tiles, block_m, interpret):
  out = _grouped_matmul(lhs, rhs, tile_group, num_tiles, block_m, interpret)
  return out, (lhs, rhs, tile_group, num_tiles)


def _grouped_matmul_bwd(block_m, interpret, residuals, dout):
  lhs, rhs, tile_group, num_tiles = residuals
  dlhs = _grouped_matmul_call(dout, rhs, tile_group, num_tiles,
                              block_m=block_m, transpose_rhs=True,
                              interpret=interpret)
  drhs = grouped_matmul_dw(lhs, dout, tile_group, num_tiles, rhs.shape[0],
                           block_m=block_m, interpret=interpret)
  return dlhs, drhs.astype(rhs.dtype), None, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(lhs, rhs, tile_group, num_tiles, *, block_m: int,
                   interpret: Optional[bool] = None):
  """[M, N]: ``lhs[tile i] @ rhs[tile_group[i]]`` for the first
  ``num_tiles[0]`` tiles of ``block_m`` rows; later rows are NOT written.

  lhs [M, K] with M a multiple of ``block_m``; rhs [G, K, N]; tile_group
  [M / block_m] int32, the tiles of one group consecutive; num_tiles [1]
  int32. Differentiable in lhs and rhs."""
  if interpret is None:
    interpret = not runtime.on_tpu()
  if lhs.shape[0] % block_m:
    raise ValueError('{} rows are no whole number of {}-row tiles.'.format(
        lhs.shape[0], block_m))
  return _grouped_matmul(lhs, rhs, tile_group, num_tiles, block_m, interpret)
