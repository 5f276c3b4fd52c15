"""Pallas kernels of the dropless expert layer: grouped matrix products over
row tiles that each belong to one group, and the two movements of rows
between token order and expert order that feed and drain them.

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

The rows get into that buffer and out of it by two more kernels, which walk
the part of the buffer that is in use and no more (second half of this file):

  moe_take_rows   buffer[row of pair (t, j)] = table[t] (x weight[t, j])
  moe_sum_rows    out[t] = sum over t's pairs of (weight[t, j] x) buffer[row of pair]
                  (or, given d_out: out[t, j] = buffer[row of pair] . d_out[t])

THE ZERO-PADDING INVARIANT. Inside a tile in use, a row that is no pair's
(the padding behind an expert's last row) holds ZEROS in the buffer
``moe_take_rows`` fills, so it stays zero through the products (no bias) and
``grouped_matmul_dw``, which sums whole tiles, adds 0 x 0 for it.
``moe_sum_rows`` relies on nothing: a row its tokens do not own is zeroed
after the fetch, whatever it holds. Rows past the tiles in use are never
written and never read.
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


# -- moving rows between token order and expert order -------------------------
#
# The layer's buffer is sized for the worst routing; the two kernels below
# walk only the part of it that is in use. Both run over tiles of
# ``token_block`` tokens. An expert's rows are in token order and a token has
# at most one pair an expert, so the rows one tile of tokens owns in one
# expert are one contiguous run of the buffer; ``layers/moe.py::group_pairs``
# lists, for every tile of tokens, the ``chunk_rows``-row chunks of the buffer
# those runs touch (``tile_chunks``, ``tile_num_chunks``). A kernel moves
# whole chunks by DMA (Mosaic moves no single row of a tiled array) and
# places rows by an exact 0/1 selection on the MXU.

_GROUP_CHUNKS = 16  # chunks moved and multiplied together


def token_block(tokens: int) -> int:
  """Tokens a tile of the row-moving kernels; divides ``tokens``."""
  return _dividing(tokens, (128, 64, 32, 16, 8))


def chunk_rows(block_rows: int) -> int:
  """Rows a chunk: the unit the row-moving kernels move; divides a tile."""
  return _dividing(block_rows, (16, 8))


def max_tile_chunks(tokens: int, top_k: int, held: int, block_rows: int) -> int:
  """Chunks one tile of tokens can touch under ANY routing (a run of n rows
  touches at most (n - 1) // chunk + 2), in whole groups."""
  chunks = (token_block(tokens) * min(top_k, held) // chunk_rows(block_rows)
            + 2 * held)
  return -(-chunks // _GROUP_CHUNKS) * _GROUP_CHUNKS


def _pieces(x, other_dtype, terms: int):
  """float32 ``x`` in pieces that a product with an operand of ``other_dtype``
  keeps whole. Against bfloat16: ``terms`` bfloat16 arrays that add up to x
  to 8 x ``terms`` bits (three hold every bit, a 0/1 array needs one), each
  product then exact and summed in float32. Against float32: x itself, to
  meet it at the highest precision."""
  if other_dtype == jnp.float32:
    return [x]
  pieces = []
  for _ in range(terms):
    pieces.append(x.astype(jnp.bfloat16))
    x = x - pieces[-1].astype(jnp.float32)
  return pieces


def _dot(lhs, rhs, contract):
  both_f32 = lhs.dtype == rhs.dtype == jnp.float32
  return jax.lax.dot_general(
      lhs, rhs, (contract, ((), ())),
      precision=jax.lax.Precision.HIGHEST if both_f32 else None,
      preferred_element_type=jnp.float32)


def _chunks_of_group(group, num_chunks):
  """How many of the ``_GROUP_CHUNKS`` slots of a tile's ``group`` hold one
  of its ``num_chunks`` chunks (none for the group before the first)."""
  return jnp.where(
      group >= 0,
      jnp.clip(num_chunks - group * _GROUP_CHUNKS, 0, _GROUP_CHUNKS), 0)


def _select(chunks_ref, first, group, num_chunks, pair_row_ref, weight_ref,
            row_ref, *, chunk: int):
  """(select [group rows, bt] f32, hits): ``select[m, t]`` is token t's
  weight (1 with no ``weight_ref``) where row m of the group's chunks is a
  pair of token t, else 0; ``hits[j]`` says where it is the token's j-th."""
  def write_rows(s, carry):
    index = group * _GROUP_CHUNKS + s
    # A slot with no chunk gets rows below zero: no pair's.
    start = jnp.where(index < num_chunks, chunks_ref[first + index] * chunk,
                      -1 - chunk)
    row_ref[pl.ds(pl.multiple_of(s * chunk, chunk), chunk), :] = (
        start + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0))
    return carry

  jax.lax.fori_loop(0, _GROUP_CHUNKS, write_rows, 0)
  row = row_ref[...]
  hits = [row == pair_row_ref[j:j + 1, :]
          for j in range(pair_row_ref.shape[0])]
  select = jnp.zeros((row.shape[0], pair_row_ref.shape[1]), jnp.float32)
  for j, hit in enumerate(hits):
    select = jnp.where(hit, 1.0 if weight_ref is None else
                       weight_ref[j:j + 1, :], select)
  return select, hits


def _sum_rows_kernel(chunks_ref, num_chunks_ref, *refs, chunk: int,
                     max_chunks: int, weighted: bool, dot: bool):
  refs = list(refs)
  pair_row_ref = refs.pop(0)                       # [k, bt] int32
  weight_ref = refs.pop(0) if weighted else None   # [k, bt] f32
  d_out_ref = refs.pop(0) if dot else None         # [bt, d] f32
  rows_ref, out_ref, slab_ref, sem_ref, acc_ref, row_ref = refs
  tile = pl.program_id(0)
  num_chunks = num_chunks_ref[tile]
  first = tile * max_chunks

  def fetch(group, act):
    """``act(copy)`` for the group's chunks: buffer chunk -> its slot."""
    def one(s, carry):
      start = chunks_ref[first + group * _GROUP_CHUNKS + s] * chunk
      act(pltpu.make_async_copy(
          rows_ref.at[pl.ds(pl.multiple_of(start, chunk), chunk)],
          slab_ref.at[group % 2, pl.ds(pl.multiple_of(s * chunk, chunk),
                                       chunk)],
          sem_ref.at[group % 2]))
      return carry

    jax.lax.fori_loop(0, _chunks_of_group(group, num_chunks), one, 0)

  def multiply(group):
    select, hits = _select(chunks_ref, first, group, num_chunks, pair_row_ref,
                           weight_ref, row_ref, chunk=chunk)
    # A row no token of this tile owns (padding, a neighbour's, a slot left
    # over) may hold anything: zeros, so that 0 x it is 0.
    hit_any = functools.reduce(jnp.logical_or, hits)
    owned = jnp.max(jnp.where(hit_any, 1.0, 0.0), axis=1, keepdims=True) > 0
    slab = slab_ref[group % 2]
    slab = jnp.where(owned, slab, jnp.zeros_like(slab))
    if not dot:
      acc_ref[...] += sum(
          _dot(piece, slab, ((0,), (0,)))
          for piece in _pieces(select, slab.dtype, 3 if weighted else 1))
      return
    # [group rows, bt]: every slab row against every token's d_out.
    products = sum(_dot(slab, piece, ((1,), (1,))) for piece in d_out_pieces)
    for j, hit in enumerate(hits):
      acc_ref[j:j + 1, :] += jnp.sum(jnp.where(hit, products, 0.0), axis=0,
                                     keepdims=True)

  acc_ref[...] = jnp.zeros_like(acc_ref)
  groups = (num_chunks + _GROUP_CHUNKS - 1) // _GROUP_CHUNKS
  fetch(0, lambda copy: copy.start())
  if dot:
    d_out_pieces = _pieces(d_out_ref[...], slab_ref.dtype, 3)

  def step(group, carry):
    fetch(group + 1, lambda copy: copy.start())
    fetch(group, lambda copy: copy.wait())
    multiply(group)
    return carry

  jax.lax.fori_loop(0, groups, step, 0)
  out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# Both under ``jit``: a model's layers call them with the same shapes, and a
# program then holds one copy of a kernel, traced and lowered once, in place
# of one a call (24 calls a step of four layers; a copy costs a quarter of a
# second of set-up on the chip's host).
@functools.partial(jax.jit, static_argnames=('out_dtype', 'interpret'))
def moe_sum_rows(rows, weight, layout, *, d_out=None, out_dtype=jnp.float32,
                 interpret: Optional[bool] = None):
  """Sums each token's rows out of the experts' buffer, fetching only rows
  in use.

  rows [M, d]; weight [T, k] float32 or None (1); ``layout`` is
  ``group_pairs``'s, whose ``pair_row`` [T, k] is the row of each pair (M
  where it has none). Returns [T, d] ``out_dtype``: token t's sum over its
  pairs of weight x row, in float32. With ``d_out`` [T, d] float32 given,
  returns [T, k] float32 instead: each pair's row dotted with its token's
  ``d_out`` (0 where the pair has no row); ``weight`` is then None.

  Rows that the tokens of a tile do not own are fetched with their chunk and
  never enter a sum, whatever they hold."""
  if interpret is None:
    interpret = not runtime.on_tpu()
  pair_row = layout['pair_row']
  tokens, top_k = pair_row.shape
  d = rows.shape[1]
  bt = token_block(tokens)
  chunk = chunk_rows(rows.shape[0] // layout['tile_group'].shape[0])
  tiles = tokens // bt
  max_chunks = layout['tile_chunks'].shape[0] // tiles
  weighted, dot = weight is not None, d_out is not None
  per_token = pl.BlockSpec((top_k, bt), lambda i, *_: (0, i))
  operands, in_specs = [pair_row.T], [per_token]
  if weighted:
    operands.append(weight.astype(jnp.float32).T)
    in_specs.append(per_token)
  if dot:
    operands.append(d_out.astype(jnp.float32))
    in_specs.append(pl.BlockSpec((bt, d), lambda i, *_: (i, 0)))
  operands.append(rows)
  in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
  out_block = (top_k, bt) if dot else (bt, d)
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(tiles,),
      in_specs=in_specs,
      out_specs=pl.BlockSpec(
          out_block, (lambda i, *_: (0, i)) if dot else (lambda i, *_: (i, 0))),
      scratch_shapes=[
          pltpu.VMEM((2, _GROUP_CHUNKS * chunk, d), rows.dtype),
          pltpu.SemaphoreType.DMA((2,)),
          pltpu.VMEM(out_block, jnp.float32),
          pltpu.VMEM((_GROUP_CHUNKS * chunk, 1), jnp.int32),
      ],
  )
  out = pl.pallas_call(
      functools.partial(_sum_rows_kernel, chunk=chunk, max_chunks=max_chunks,
                        weighted=weighted, dot=dot),
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct(
          (top_k, tokens) if dot else (tokens, d),
          jnp.float32 if dot else out_dtype),
      interpret=interpret,
      name='moe_sum_rows',
  )(layout['tile_chunks'], layout['tile_num_chunks'], *operands)
  return out.T if dot else out


def _take_rows_kernel(chunks_ref, num_chunks_ref, tile_group_ref,
                      pad_chunks_ref, *refs, chunk: int, block_rows: int,
                      max_chunks: int, weighted: bool):
  refs = list(refs)
  pair_row_ref = refs.pop(0)                       # [k, bt] int32
  weight_ref = refs.pop(0) if weighted else None   # [k, bt] f32
  (table_ref, out_ref, placed_ref, stage_ref, open_ref, open_chunk_ref,
   sem_ref, row_ref) = refs
  tile = pl.program_id(0)
  num_chunks = num_chunks_ref[tile]
  first = tile * max_chunks

  @pl.when(tile == 0)
  def _():
    def none_open(expert, carry):
      open_chunk_ref[expert] = -1
      return carry

    jax.lax.fori_loop(0, open_chunk_ref.shape[0], none_open, 0)

  def write(group, s, this):
    """The copy of slot ``s`` of the group's staging buffer to chunk ``this``."""
    return pltpu.make_async_copy(
        stage_ref.at[group % 2, pl.ds(pl.multiple_of(s * chunk, chunk), chunk)],
        out_ref.at[pl.ds(pl.multiple_of(this * chunk, chunk), chunk)],
        sem_ref.at[group % 2])

  def for_chunks(group, act):
    def one(s, carry):
      act(s, chunks_ref[first + group * _GROUP_CHUNKS + s])
      return carry

    jax.lax.fori_loop(0, _chunks_of_group(group, num_chunks), one, 0)

  def place(s, this, group):
    expert = tile_group_ref[this * chunk // block_rows]
    rows = placed_ref[pl.ds(pl.multiple_of(s * chunk, chunk), chunk), :]
    # The chunk an earlier tile of tokens began: its rows come along.
    began = jnp.where(open_chunk_ref[expert] == this,
                      open_ref[expert].astype(jnp.float32), 0.0)
    rows = (rows + began).astype(stage_ref.dtype)
    stage_ref[group % 2, pl.ds(pl.multiple_of(s * chunk, chunk), chunk), :] = (
        rows)
    open_ref[expert] = rows
    open_chunk_ref[expert] = this
    write(group, s, this).start()

  def step(group, carry):
    select, _ = _select(chunks_ref, first, group, num_chunks, pair_row_ref,
                        weight_ref, row_ref, chunk=chunk)
    # Row m of the group: its token's row of the table (x its weight), or
    # zeros where no token of this tile owns it.
    table = table_ref[...]
    placed_ref[...] = sum(
        _dot(piece, table, ((1,), (0,)))
        for piece in _pieces(select, table.dtype, 3 if weighted else 1))
    for_chunks(group, functools.partial(place, group=group))
    # The group before is on its way out while this one was being placed.
    for_chunks(group - 1, lambda s, this: write(group - 1, s, this).wait())
    return carry

  groups = (num_chunks + _GROUP_CHUNKS - 1) // _GROUP_CHUNKS
  jax.lax.fori_loop(0, groups, step, 0)
  # Drained before the next tile of tokens starts: it may write a chunk this
  # one wrote, with more of its rows in place.
  for_chunks(groups - 1, lambda s, this: write(groups - 1, s, this).wait())

  # Last, the chunks of padding behind each expert's rows: zeros.
  @pl.when(tile == pl.num_programs(0) - 1)
  def _():
    stage_ref[0, :chunk, :] = jnp.zeros((chunk, stage_ref.shape[2]),
                                        stage_ref.dtype)

    def for_padding(act):
      def one(i, carry):
        @pl.when(pad_chunks_ref[i] >= 0)
        def _():
          act(write(0, 0, pad_chunks_ref[i]))

        return carry

      jax.lax.fori_loop(0, pad_chunks_ref.shape[0], one, 0)

    for_padding(lambda copy: copy.start())
    for_padding(lambda copy: copy.wait())


@functools.partial(jax.jit, static_argnames=('block_rows', 'interpret'))
def moe_take_rows(table, weight, layout, *, block_rows: int,
                  interpret: Optional[bool] = None):
  """[M, d]: the experts' buffer filled from a table of tokens [T, d],
  writing only the tiles in use.

  Row ``pair_row[t, j]`` (where that is under M) becomes ``table[t]``,
  times ``weight[t, j]`` (float32 [T, k]; None: 1); a row of padding inside a
  tile in use becomes ZEROS (``grouped_matmul_dw`` sums whole tiles); tiles
  past the last in use are NOT written. ``layout`` is ``group_pairs``'s: each
  tile of tokens places its rows into the chunks of its runs (an exact 0/1
  selection on the MXU) and writes those chunks; a chunk that two tiles of
  tokens share is written by both, the later with the earlier's rows kept."""
  if interpret is None:
    interpret = not runtime.on_tpu()
  pair_row = layout['pair_row']
  tokens, top_k = pair_row.shape
  d = table.shape[1]
  rows = layout['tile_group'].shape[0] * block_rows
  bt = token_block(tokens)
  chunk = chunk_rows(block_rows)
  tiles = tokens // bt
  held = layout['counts'].shape[0]
  weighted = weight is not None
  per_token = pl.BlockSpec((top_k, bt), lambda i, *_: (0, i))
  operands, in_specs = [pair_row.T], [per_token]
  if weighted:
    operands.append(weight.astype(jnp.float32).T)
    in_specs.append(per_token)
  operands.append(table)
  in_specs.append(pl.BlockSpec((bt, d), lambda i, *_: (i, 0)))
  group_rows = _GROUP_CHUNKS * chunk
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=4,
      grid=(tiles,),
      in_specs=in_specs,
      out_specs=pl.BlockSpec(memory_space=pl.ANY),
      scratch_shapes=[
          pltpu.VMEM((group_rows, d), jnp.float32),
          pltpu.VMEM((2, group_rows, d), table.dtype),
          pltpu.VMEM((held, chunk, d), table.dtype),
          pltpu.SMEM((held,), jnp.int32),
          pltpu.SemaphoreType.DMA((2,)),
          pltpu.VMEM((group_rows, 1), jnp.int32),
      ],
  )
  return pl.pallas_call(
      functools.partial(
          _take_rows_kernel, chunk=chunk, block_rows=block_rows,
          max_chunks=layout['tile_chunks'].shape[0] // tiles,
          weighted=weighted),
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((rows, d), table.dtype),
      interpret=interpret,
      name='moe_take_rows',
  )(layout['tile_chunks'], layout['tile_num_chunks'], layout['tile_group'],
    layout['pad_chunks'], *operands)
