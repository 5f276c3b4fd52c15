"""Pallas flash attention: blockwise online-softmax attention in VMEM.

The long-context compute kernel (SURVEY §7 Pallas candidates; the ring
layer in parallel/ring_attention.py handles the multi-device dimension).
XLA's attention materializes the full [B, H, L, L] score tensor in HBM —
O(L^2) memory and two full HBM round-trips over it. This kernel tiles
q into [block_q, D] VMEM blocks and streams k/v through in [block_k, D]
blocks, keeping the running (max, sum, accumulator) of the numerically
stable online softmax (Milakov & Gimelshein 2018; Dao et al. 2022,
FlashAttention) in VMEM scratch that persists across the innermost grid
dimension:

  grid = (batch*heads, steps)
         # one step a TILE THE MASK KEEPS, and no other: the mask is static
         # (causal, causal with a window, block diffusion, or none), so the
         # host lists the needed (q block, k block) pairs once a call
         # (tile_table) and hands the list to the kernel as scalar-prefetch
         # tables; the index maps and the body read their block indices
         # from it. q OUTER, k ASCENDING within a q block: the q block, its
         # output and its accumulators stay put over the q block's steps
         # (fetched and written back once), k/v blocks stream past.
  s    = q_block @ k_block^T * scale           # MXU, f32 accumulation
  m'   = max(m, rowmax(s));  p = exp(s - m')   # VPU
  l    = l * exp(m - m') + rowsum(p)
  acc  = acc * exp(m - m') + p @ v_block       # MXU
  at the q block's last step (a flag of the table): out = acc / l

A tile outside the mask is not a grid step at all: it is not launched,
its blocks are not fetched and no output block is written back for it. (A
rectangular grid with a branch on the tile's predicate inside pays all
three for every tile it skips; what that cost the two token cells is in
PERF.md's findings.) An unmasked call lists the whole rectangle.

A step knows from its flags (``tile_table``) what the mask keeps of its
tile. Where a q block holds two strips of STRIP_ROWS rows or more, a tile
the mask keeps WHOLE forms no mask and selects no score, and a tile on the
mask's EDGE (kept in part) runs strip by strip: each strip's products run
over its SPAN, the STRIP_ROWS-wide column sub-blocks from the first that
holds one of its kept pairs to the last (``strip_spans``: a prefix of the
tile's columns on the causal diagonal, a suffix on a window's lower edge,
the strip's own columns on block diffusion's own-block diagonal), with the
mask applied inside the span; the online-softmax state of a strip is its
rows of the q block's scratch. The host lists each strip's (first, count)
sub-blocks in one more scalar-prefetch table and the kernels branch over
the span lengths that occur (a slice's size is static). Blocks under two
strips, the two-kernel backward below and the ring's carry kernel compute
every tile of a masked call whole, masked.

Memory: per-device O(L*D) activations only — no score tensor ever reaches
HBM, forward OR backward: the backward is the same kernel family instead of
an XLA scan (_flash_bwd_pallas), over the same list of needed tiles. It is
ONE Pallas kernel of five products a tile: on the forward's grid (q block
resident, k/v blocks streaming) a step forms s = q k^T and dp = do v^T
once, from them p and ds, and adds all three of dv += p^T do, dk += ds^T q
and dq += ds k. dq accumulates in a [block_q, D] float32 scratch and leaves
at its q block's last step; dk and dv accumulate in float32 over the WHOLE
k/v head ([l_k, D] scratch each, a step adds at its k block's rows) and
leave once a k/v head, after the last query head that reads it (grouped
heads are consecutive on the head axis, which therefore runs in order).
That working set grows with the length (16 bytes x l_k x D with bf16
operands: 16 MB at 8,192 keys of 128, 64 MB at 32,768), so a call whose
k/v head would take more than FUSED_BWD_RESIDENT_BYTES runs the
FlashAttention-2 pair instead (dk/dv with the k/v block resident and q
streaming, dq with the q block resident: s, dp, the exponential and the
mask formed twice, seven products; its working set does not grow): one
algorithm, its layout chosen from the call's shapes alone. The fused
kernel is launched under the name ``flash_attention_bwd_dq`` (the kernel
whose grid it runs): the benchmark's readers sum the attention time by the
names of the pair, and a name of its own would drop out of them. Numerics
match the XLA oracle to f32 rounding (tests/test_flash_attention.py), on
both paths, which form every sum in the same order; what the
kernels measure in a cell of the benchmark is in PERF.md (section 5:
time a step by kernel, the work executed beside the work needed, and
``attention_roofline``). This is the single-device
long-context path; ring_attention.py handles the cross-device dimension
with its own shard-level blockwise accumulation (flash_attention_carry:
its offsets are traced values, so its grid stays the rectangle with a
branch inside).

Named residuals. The forward rule of the custom VJP (_flash_fwd) names
what the backward kernels read with ``jax.ad_checkpoint.checkpoint_name``:
q, k and v as the kernels see them ([BH, L, D], after the caller's
positions and the layout change), ``out`` and the per-row log-sum-exp
(FLASH_Q .. FLASH_LSE; BACKWARD_READS holds all five). A name is the
identity unless a ``jax.checkpoint`` around the caller has a policy that
asks for it (``save_only_these_names``): that checkpoint then keeps the
array instead of running the forward kernel a second time in its backward
pass. Who saves them: research/smallthinker's block checkpoint, all five.
Every other caller (MultiHeadAttention / TransformerBlock,
parallel/pipeline.py's policy-less checkpoint, direct calls) names no
policy and compiles to what it compiled to without the names. The names
must be given INSIDE the forward rule: under differentiation JAX traces
that rule in place of the primal function, so a name in the primal
function is never seen by a policy.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu import runtime

NEG_INF = -1e30

# Names of the custom VJP's residuals (see the module docstring): what the
# backward reads. A checkpoint policy that saves them spares its
# backward pass the forward kernel (out, lse) and the caller's projections,
# positions and layout change (q, k, v).
FLASH_Q = 'flash_q'
FLASH_K = 'flash_k'
FLASH_V = 'flash_v'
FLASH_OUT = 'flash_out'
FLASH_LSE = 'flash_lse'
BACKWARD_READS = (FLASH_Q, FLASH_K, FLASH_V, FLASH_OUT, FLASH_LSE)


def _dividing_block_or_raise(requested: int, l: int) -> int:
  """Largest block <= requested that divides L (power-of-two ladder).

  Raises for lengths nothing on the ladder divides (L % 8 != 0) instead
  of silently returning L itself — a full-length "block" bypasses the
  VMEM sizing the caps encode and surfaces later as an opaque Mosaic
  scoped-vmem error. Callers pad the sequence instead.
  """
  for candidate in (requested, 512, 256, 128, 64, 32, 16, 8):
    if (candidate % 8 == 0 and candidate <= l and l % candidate == 0
        and candidate <= requested):
      # candidate % 8: requested itself heads the ladder, and for L <=
      # requested that first candidate is L — an 8-misaligned L must fall
      # through to the raise, not return itself as a full-length "block".
      return candidate
  raise ValueError(
      'No flash-attention block size <= {} divides sequence length {}; '
      'pad the sequence to a multiple of 8.'.format(requested, l))


def _in_band(q_pos, k_pos, window: Optional[int]):
  """The causal mask, and with ``window`` the band ``q_pos - k_pos < window``
  (a row sees itself and the ``window - 1`` positions before it)."""
  mask = q_pos >= k_pos
  if window is not None:
    mask = jnp.logical_and(mask, q_pos - k_pos < window)
  return mask


def _block_index(position, block: int):
  """``position // block`` for non-negative positions (a shift where
  ``block`` is a power of two: the kernels run it on every tile's row and
  column of positions)."""
  if block & (block - 1) == 0:
    return position >> (block.bit_length() - 1)
  return position // block


def _in_block_diffusion(q_pos, k_pos, length: int, block: int):
  """The block-diffusion mask over the 2 x ``length`` positions [noised ;
  clean] (``flash_attention``'s docstring has the four rules), for int32
  positions that broadcast against each other. Every key gets a code, its
  block for a clean key and ``blocks`` + its block for a noised one; a
  query then sees the clean codes below a threshold (its block, or its
  block + 1 for a clean query) and one noised code (its own block's, none
  for a clean query): two comparisons an element, on codes that cost a row
  and a column of a tile."""
  blocks = length // block
  q_noised = q_pos < length
  q_block = _block_index(jnp.where(q_noised, q_pos, q_pos - length), block)
  below = jnp.where(q_noised, q_block, q_block + 1)
  own = jnp.where(q_noised, q_block + blocks, -1)
  k_noised = k_pos < length
  k_code = _block_index(jnp.where(k_noised, k_pos, k_pos - length),
                        block) + jnp.where(k_noised, blocks, 0)
  return (k_code < below) | (k_code == own)


def _tile_mask(q_base, k_base, block_q: int, block_k: int,
               window: Optional[int], diffusion):
  """[block_q, block_k] bool of a tile, or of a strip's span in it, whose
  first row and column sit at ``q_base`` and ``k_base``: the causal band,
  or with ``diffusion`` = (length, block) the block-diffusion mask."""
  if diffusion is not None:
    q_pos = q_base + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = k_base + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return _in_block_diffusion(q_pos, k_pos, *diffusion)
  q_pos = q_base + jax.lax.broadcasted_iota(
      jnp.int32, (block_q, block_k), 0)
  k_pos = k_base + jax.lax.broadcasted_iota(
      jnp.int32, (block_q, block_k), 1)
  return _in_band(q_pos, k_pos, window)


def block_diffusion_mask(length: int, block: int):
  """[2 length, 2 length] bool, row = query: the mask the kernels apply
  with ``block_diffusion=(length, block)``, for the dense backend and the
  tests."""
  position = jnp.arange(2 * length, dtype=jnp.int32)
  return _in_block_diffusion(position[:, None], position[None, :], length,
                             block)


def mask_pairs(l_q: int, l_k: int, causal: bool, window: Optional[int],
               diffusion) -> int:
  """Pairs (i, j) the mask keeps, by formula."""
  if diffusion is not None:
    length, block = diffusion
    return length * length + length * block
  if not causal:
    return l_q * l_k
  shift = l_k - l_q             # row i sees columns j <= i + shift
  rows = np.arange(l_q, dtype=np.int64) + shift
  first = np.zeros_like(rows) if window is None else np.maximum(
      rows - window + 1, 0)
  return int(np.sum(np.clip(np.minimum(rows, l_k - 1) - first + 1, 0, None)))


def _row_intervals(l_q: int, l_k: int, causal: bool, window: Optional[int],
                   diffusion):
  """The columns each row keeps, as disjoint inclusive intervals: a list of
  (first, last) int64 [l_q] pairs, empty where first > last. The band is one
  interval a row (rows and columns counted from their first position, as
  ``_tile_mask`` counts them); block diffusion two, the row's own noised
  block and the clean columns below its threshold."""
  rows = np.arange(l_q, dtype=np.int64)
  if diffusion is not None:
    length, block = diffusion
    noised = rows < length
    start = np.where(noised, rows, rows - length) // block * block
    own = (np.where(noised, start, 0), np.where(noised, start + block - 1, -1))
    clean = (np.full(l_q, length),
             length - 1 + np.where(noised, start, start + block))
    return [own, clean]
  if not causal:
    return [(np.zeros(l_q, np.int64), np.full(l_q, l_k - 1))]
  first = np.zeros(l_q, np.int64) if window is None else np.maximum(
      rows - window + 1, 0)
  return [(first, np.minimum(rows, l_k - 1))]


@functools.lru_cache(maxsize=64)
def _kept_pairs(l_q: int, l_k: int, rows: int, cols: int, causal: bool,
                window: Optional[int], diffusion):
  """int64 [l_q // rows, l_k // cols], on the host: the pairs the mask keeps
  in each ``rows`` x ``cols`` rectangle of the [l_q, l_k] scores. Read-only:
  every call with these arguments shares it (tables are built each time a
  call is traced)."""
  start = np.arange(0, l_k, cols)
  per_row = sum(
      np.clip(np.minimum(last[:, None], start + cols - 1) -
              np.maximum(first[:, None], start) + 1, 0, None)
      for first, last in _row_intervals(l_q, l_k, causal, window, diffusion))
  kept = per_row.reshape(l_q // rows, rows, -1).sum(axis=1)
  kept.flags.writeable = False
  return kept


def _tile_kinds(n_q: int, n_k: int, block_q: int, block_k: int,
                causal: bool, window: Optional[int], diffusion):
  """[n_q, n_k] bool twice, on the host: the tiles that hold a pair of the
  mask, and the tiles it keeps whole (every tile, both, of an unmasked
  call)."""
  if not causal and diffusion is None:
    return np.ones((n_q, n_k), bool), np.ones((n_q, n_k), bool)
  kept = _kept_pairs(n_q * block_q, n_k * block_k, block_q, block_k, causal,
                     window, diffusion)
  return kept > 0, kept == block_q * block_k


def tiles_computed(n_q: int, n_k: int, block_q: int, block_k: int,
                   causal: bool, window: Optional[int], diffusion) -> int:
  """Tiles of the [n_q, n_k] rectangle that the mask keeps: the tiles the
  kernels compute (an edge tile in part, ``strip_spans``), and but for an
  empty block (``tile_table``) the grid steps they launch."""
  return int(np.sum(_tile_kinds(n_q, n_k, block_q, block_k, causal, window,
                                diffusion)[0]))


# Flags of a grid step (``tile_table``): its accumulator starts here, is
# written out here; its tile is one the mask keeps only in part.
FIRST, LAST, EDGE = 1, 2, 4

# The rows of one strip of an edge tile, and the width of the column
# sub-blocks its span is made of (``strip_spans``); chosen on the chip
# against 128 (PERF.md, section 6).
STRIP_ROWS = 256


def tile_table(n_q: int, n_k: int, block_q: int, block_k: int, causal: bool,
               window: Optional[int], diffusion, *, k_resident: bool = False,
               group: int = 1):
  """The grid of a kernel: int32 [4, steps], the rows (head, q block,
  k block, flags), one column a grid step, every step a tile the mask keeps.

  An ACCUMULATOR is what stays in VMEM while blocks stream past it: a q
  block in the forward and the dq kernel (its k blocks follow one another
  in ASCENDING order; ``head`` is 0), a k/v block in the dk/dv kernel
  (``k_resident``: the ``group`` query heads that read it follow one
  another, each with its q blocks ascending, which is the order the
  rectangular sweep had, so every sum is formed in that order). Steps of
  one accumulator are consecutive: the resident blocks and the output are
  named by every one of them, so they are fetched and written back once.
  ``flags`` has FIRST on an accumulator's first step, LAST on its last and
  EDGE on a step whose tile the mask keeps only in part (an unmasked call
  has no EDGE step): where the blocks hold strips the kernels mask an edge
  tile and no other (``_each_part``).

  An accumulator that the mask leaves NO tile (a k/v block past the last
  query under ``causal`` with ``l_k > l_q``; never a self-attention row,
  which sees itself) still gets one step, FIRST | LAST | EDGE, on its
  first candidate tile: the in-tile mask keeps nothing of it, so the step
  computes the defined output (zeros for dk/dv and ``out``) at the price
  of one tile, with no branch of its own in the kernel."""
  needed, whole = _tile_kinds(n_q, n_k, block_q, block_k, causal, window,
                              diffusion)
  if k_resident:
    needed, whole = needed.T, whole.T
  needed, whole = np.tile(needed, (1, group)), np.tile(whole, (1, group))
  needed[~needed.any(axis=1), 0] = True
  resident, streamed = np.nonzero(needed)   # row-major: both ascending
  first = np.append(True, resident[1:] != resident[:-1])
  flags = (FIRST * first + LAST * np.append(first[1:], True) +
           EDGE * ~whole[resident, streamed])
  if k_resident:
    rows = (streamed // n_q, streamed % n_q, resident, flags)
  else:
    rows = (np.zeros_like(resident), resident, streamed, flags)
  return np.stack(rows).astype(np.int32)


def strip_spans(table, l_q: int, l_k: int, block_q: int, block_k: int,
                causal: bool, window: Optional[int], diffusion):
  """int32 [steps, strips, 2], on the host: how each step of a q-resident
  ``table`` runs an edge tile. Where a q block holds two strips of
  STRIP_ROWS rows or more (and STRIP_ROWS divides both blocks), an EDGE
  step runs strip by strip, each over its SPAN: (first, count) sub-blocks
  of STRIP_ROWS columns of the tile, from the first that holds a pair of
  the strip's rows to the last; (0, 0) for a strip that holds none, and
  for every strip of an interior step. Elsewhere, and where no step is an
  edge step, there are no strips (shape [steps, 0, 2]) and an edge tile is
  computed whole."""
  size = STRIP_ROWS
  if (block_q < 2 * size or block_q % size or block_k % size or
      not np.any(table[3] & EDGE)):
    return np.zeros((table.shape[1], 0, 2), np.int32)
  strips, subs = block_q // size, block_k // size
  held = (_kept_pairs(l_q, l_k, size, size, causal, window, diffusion) > 0
         ).reshape(l_q // block_q, strips, l_k // block_k, subs)
  held = held.transpose(0, 2, 1, 3)[table[1], table[2]]  # [steps, strips, subs]
  held &= (table[3] & EDGE != 0)[:, None, None]
  first = np.argmax(held, axis=-1)
  count = np.where(held.any(axis=-1),
                   subs - np.argmax(held[..., ::-1], axis=-1) - first, 0)
  return np.stack([first * (count > 0), count], axis=-1).astype(np.int32)


def _stripped(table, spans):
  """bool [steps]: the steps of ``table`` that run as strips."""
  return (table[3] & EDGE != 0) & (spans.shape[1] > 0)


def pairs_computed(table, spans, block_q: int, block_k: int) -> int:
  """Pairs the kernel of a q-resident ``table`` computes a query head: a
  whole tile's at every step but those run as strips (``strip_spans``),
  which compute their spans' sub-blocks."""
  stripped = _stripped(table, spans)
  size = block_q // max(spans.shape[1], 1)
  return int(np.count_nonzero(~stripped) * block_q * block_k +
             size * size * spans[stripped, :, 1].sum())


def _strip_plan(table, l_q: int, l_k: int, block_q: int, block_k: int,
                causal: bool, window: Optional[int], diffusion, *,
                suffix: str, bh: int, whole: bool = False, **gauges):
  """``strip_spans`` of a q-resident kernel's table (none with ``whole``),
  flat for its scalar prefetch (one 0 where there are no strips), and the
  span lengths its strips take; sets the kernel's gauges (``_set_gauges``)
  when the call is masked."""
  spans = np.zeros((table.shape[1], 0, 2), np.int32) if whole else \
      strip_spans(table, l_q, l_k, block_q, block_k, causal, window,
                  diffusion)
  if causal or diffusion is not None:
    _set_gauges(suffix, bh, mask_pairs(l_q, l_k, causal, window, diffusion),
                pairs_computed(table, spans, block_q, block_k),
                edge_steps=int(np.count_nonzero(_stripped(table, spans))),
                **gauges)
  flat = spans.reshape(-1) if spans.size else np.zeros(1, np.int32)
  return flat, _span_lengths(spans)


def _span_lengths(spans):
  """The span lengths each strip takes somewhere in ``spans`` (static: the
  kernels hold one branch for each)."""
  return tuple(tuple(sorted(set(spans[:, strip, 1].tolist()) - {0}))
               for strip in range(spans.shape[1]))


def _set_gauges(suffix: str, bh: int, needed: int, computed: int, *,
                steps: int, edge_steps: int,
                backward_kernels: Optional[int] = None):
  """Host side, when a masked call is traced, all heads of the call: the
  pairs the mask keeps (``needed``) and the pairs a kernel computes
  (``computed``: interior tiles whole, an edge tile's strips over their
  spans, or the whole edge tile where it holds no strips), the grid steps
  it launches and of them those run as strips (``steps``, ``edge_steps``,
  a query head); from the backward also the kernels it launches (1 fused,
  2 not)."""
  from tensor2robot_tpu.observability import get_registry

  registry = get_registry()
  if backward_kernels is not None:
    registry.gauge('attention/backward_kernels').set(float(backward_kernels))
  registry.gauge('attention/mask_pairs_needed').set(float(bh * needed))
  registry.gauge('attention/mask_pairs_computed' + suffix).set(
      float(bh * computed))
  registry.gauge('attention/grid_steps' + suffix).set(float(bh * steps))
  registry.gauge('attention/edge_steps' + suffix).set(float(bh * edge_steps))


def _rows_at(start, size: int):
  """``size`` rows (or lanes) from ``start``: a static slice, or a dynamic
  one at a traced start (a strip's span, ``_each_part``)."""
  if isinstance(start, int):
    return slice(start, start + size)
  return pl.ds(start, size)


def _each_part(t, flags_ref, spans_ref, part, *, masked: bool, block_q: int,
               block_k: int, strips):
  """Runs ``part(row, rows, col, cols, masked)`` over what step ``t``'s tile
  needs: the rows [row, row + rows) of the q block against the columns
  [col, col + cols) of the k block. Where the blocks hold strips
  (``strips``: the span lengths each strip takes, one branch each, since a
  slice's size must be static), an interior tile (no EDGE flag) runs whole
  with no mask and an edge tile strip by strip, each of block_q /
  len(strips) rows (STRIP_ROWS, as ``strip_spans`` cut them) over its span
  of sub-blocks as wide (read from ``spans_ref``), masked. Blocks
  under two strips run every tile whole, masked where the call is, with no
  branch: skipping the mask there cost more than it saved (PERF.md,
  section 6)."""
  if not (masked and strips):
    part(0, block_q, 0, block_k, masked)
    return
  edge = flags_ref[t] & EDGE != 0
  pl.when(jnp.logical_not(edge))(
      functools.partial(part, 0, block_q, 0, block_k, False))

  size = block_q // len(strips)

  @pl.when(edge)
  def _strips():
    for strip, lengths in enumerate(strips):
      at = (t * len(strips) + strip) * 2
      first, count = spans_ref[at], spans_ref[at + 1]
      col = pl.multiple_of(first * size, size)
      for length in lengths:
        pl.when(count == length)(functools.partial(
            part, strip * size, size, col, length * size, True))


def _block_update(q, k, v, acc_ref, m_ref, l_ref, rows, *, scale: float,
                  mask=None):
  """The shared online-softmax update both kernels run: scores the q rows
  against the k/v rows given, and folds them into the (acc, m, l) scratch
  accumulators' ``rows``. ``mask`` (bool, the scores' shape) is given on a
  tile the mask keeps in part; None forms no mask and selects no score.

  m/l scratch is [bq, 128] with the per-row scalar broadcast UNIFORMLY
  across all 128 lanes: jax 0.9's Mosaic rejects sub-slicing width-1
  VMEM memrefs ("slice shape along dimension 1 must be aligned to
  tiling (128)"), so the scalars are read back with a lane-reduce and
  stored with a broadcast instead of living in [bq, 1] refs.
  """
  q = q.astype(jnp.float32)                              # [rows, D]
  k = k.astype(jnp.float32)                              # [cols, D]
  v = v.astype(jnp.float32)
  s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32) * scale
  if mask is not None:
    s = jnp.where(mask, s, NEG_INF)

  m_prev = jnp.max(m_ref[rows], axis=-1, keepdims=True)  # [rows, 1]
  l_prev = jnp.max(l_ref[rows], axis=-1, keepdims=True)
  m_block = jnp.max(s, axis=-1, keepdims=True)
  m_new = jnp.maximum(m_prev, m_block)
  safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
  p = jnp.exp(s - safe_m)
  # A no-op where nothing is masked, kept on every tile: without it XLA's
  # CPU backend (the interpreter's) forms the exponential in two fusions
  # whose last bits differ, and interior tiles would not be the parent's.
  p = jnp.where(s <= NEG_INF / 2, 0.0, p)
  correction = jnp.exp(m_prev - safe_m)
  correction = jnp.where(m_prev <= NEG_INF / 2, 0.0, correction)
  l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
  lanes = (l_new.shape[0], l_ref.shape[1])
  l_ref[rows] = jnp.broadcast_to(l_new, lanes)
  m_ref[rows] = jnp.broadcast_to(m_new, lanes)
  acc_ref[rows] = acc_ref[rows] * correction + jax.lax.dot_general(
      p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _flash_kernel(q_blocks_ref, k_blocks_ref, flags_ref, spans_ref, q_ref,
                  k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  window: Optional[int] = None, diffusion=None, strips=()):
  """One step of the grid (bh, steps of ``tile_table``): one tile the mask
  keeps, folded into its q block's accumulators (``_each_part``: whole,
  or an edge tile's strips). The q block, its output and its log-sum-exp
  are named by every step of the q block, so they move once; k and v
  blocks stream past in ascending order."""
  t = pl.program_id(1)
  flags = flags_ref[t]

  @pl.when(flags & FIRST != 0)
  def _init():
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

  q_base, k_base = q_blocks_ref[t] * block_q, k_blocks_ref[t] * block_k

  def part(row, rows, col, cols, masked):
    q_rows, k_rows = _rows_at(row, rows), _rows_at(col, cols)
    mask = _tile_mask(q_base + row, k_base + col, rows, cols, window,
                      diffusion) if masked else None
    _block_update(q_ref[0, q_rows], k_ref[0, k_rows], v_ref[0, k_rows],
                  acc_ref, m_ref, l_ref, q_rows, scale=scale, mask=mask)

  _each_part(t, flags_ref, spans_ref, part,
             masked=causal or diffusion is not None, block_q=block_q,
             block_k=block_k, strips=strips)

  @pl.when(flags & LAST != 0)
  def _finalize():
    l_col = jnp.max(l_ref[...], axis=-1, keepdims=True)        # [bq, 1]
    m_col = jnp.max(m_ref[...], axis=-1, keepdims=True)
    l_final = jnp.maximum(l_col, 1e-20)
    o_ref[0] = (acc_ref[...] / l_final).astype(o_ref.dtype)
    # Log-sum-exp per row, saved for the backward pass (FlashAttention).
    # Broadcast over the 8 padding sublanes (see _flash_fwd_call's lse).
    row = (m_col + jnp.log(l_final))[:, 0]
    lse_ref[0] = jnp.broadcast_to(row[None, :], (8, block_q))


def _kv_head(group: int):
  """Index of the key/value head that query head ``b`` (of the flattened
  batch x query-head axis) reads: ``b // group`` with grouped-query heads,
  ``b`` itself with equal head counts."""
  return (lambda b: b) if group == 1 else (lambda b: b // group)


def _flash_bhld(q, k, v, *, scale: float, causal: bool, block_q: int,
                block_k: int, interpret: bool, window: Optional[int] = None,
                diffusion=None):
  """[BH, L, D] flash attention: the grid's tables on the host
  (``tile_table``; ``_strip_plan``, which sets the gauges of a masked
  call), the kernel by ``_flash_fwd_call``."""
  bh, l_q, _ = q.shape
  l_k = k.shape[1]
  table = tile_table(l_q // block_q, l_k // block_k, block_q, block_k,
                     causal, window, diffusion)
  spans, strips = _strip_plan(table, l_q, l_k, block_q, block_k, causal,
                              window, diffusion, suffix='', bh=bh,
                              steps=table.shape[1])
  return _flash_fwd_call(*table[1:], spans, q, k, v, scale=scale,
                         causal=causal, block_q=block_q, block_k=block_k,
                         interpret=interpret, window=window,
                         diffusion=diffusion, strips=strips)


# What fixes a kernel's body beside the shapes: a launcher is jitted on it,
# so the calls of a program that agree (a model's layers) share one trace
# and one lowering of the kernel.
_KERNEL_STATICS = ('scale', 'causal', 'block_q', 'block_k', 'interpret',
                   'window', 'diffusion', 'strips')


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_fwd_call(q_blocks, k_blocks, flags, spans, q, k, v, *, scale,
                    causal, block_q, block_k, interpret, window, diffusion,
                    strips):
  """The forward kernel over its tables (``_flash_bhld``).

  k/v may hold fewer heads than q ([BH/group, L, D], grouped-query
  attention): query head ``b`` then reads k/v head ``b // group`` through
  the block index maps, so no k/v head is repeated in HBM.

  The log-sum-exp output is materialized as [BH, 8, L] — Mosaic requires
  output blocks whose second-minor dim is divisible by 8 (or equals the
  array dim), so the per-row LSE is broadcast over 8 padding sublanes in
  the kernel and sliced back to [BH, L] here. The waste is 7 f32 rows per
  (bh, L): ~3.5 MB at bh=8, L=16k — noise next to the k/v tensors.
  """
  bh, l_q, d = q.shape
  d_v = v.shape[2]
  kv = _kv_head(bh // k.shape[0])
  kernel = functools.partial(
      _flash_kernel, scale=scale, causal=causal, block_q=block_q,
      block_k=block_k, window=window, diffusion=diffusion, strips=strips)
  # Index maps receive the table's rows (but the head's, which is 0 for a
  # resident q block) and the spans as trailing arguments.
  q_block = lambda b, t, qs, *_: (b, qs[t], 0)
  kv_block = lambda b, t, qs, ks, *_: (kv(b), ks[t], 0)
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=4,
      grid=(bh, flags.shape[0]),
      in_specs=[
          pl.BlockSpec((1, block_q, d), q_block),
          pl.BlockSpec((1, block_k, d), kv_block),
          pl.BlockSpec((1, block_k, d_v), kv_block),
      ],
      out_specs=[
          pl.BlockSpec((1, block_q, d_v), q_block),
          pl.BlockSpec((1, 8, block_q), lambda b, t, qs, *_: (b, 0, qs[t])),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_q, d_v), jnp.float32),
          # 128 uniform lanes per scalar — see _block_update's m/l note.
          pltpu.VMEM((block_q, 128), jnp.float32),
          pltpu.VMEM((block_q, 128), jnp.float32),
      ],
  )
  out, lse8 = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=[
          jax.ShapeDtypeStruct((bh, l_q, d_v), q.dtype),
          jax.ShapeDtypeStruct((bh, 8, l_q), jnp.float32),
      ],
      interpret=interpret,
      name='flash_attention_fwd',
  )(q_blocks, k_blocks, flags, spans, q, k, v)
  return out, lse8[:, 0, :]


def _flash_carry_kernel(offsets_ref, q_ref, k_ref, v_ref, o_in_ref,
                        m_in_ref, l_in_ref, o_out_ref, m_out_ref,
                        l_out_ref, acc_ref, m_ref, l_ref, *, scale: float,
                        causal: bool, block_q: int, block_k: int):
  """Flash block update with EXTERNAL accumulators (for ring attention).

  Like _flash_kernel but the online-softmax state (o, m, l) is carried in
  and out UNNORMALIZED — the ring loop feeds each hop's outputs into the
  next and normalizes once at the end. ``offsets_ref`` (scalar prefetch)
  holds the global (q_offset, k_offset) so causal masking sees global
  positions even though each device only holds its shard.
  """
  i_q = pl.program_id(1)
  i_k = pl.program_id(2)
  n_k = pl.num_programs(2)

  @pl.when(i_k == 0)
  def _init():
    acc_ref[:] = o_in_ref[0].astype(jnp.float32)
    # m/l ride in [1, 8, block_q] blocks (8 broadcast sublanes — Mosaic's
    # output-block divisibility rule; see _flash_fwd_call's lse note). Reduce
    # over the uniform sublanes rather than slicing one (width-1 memref
    # slices are rejected by jax 0.9 Mosaic), then broadcast across the
    # 128 scalar lanes of the scratch.
    m_col = jnp.max(m_in_ref[0].astype(jnp.float32), axis=0)[:, None]
    l_col = jnp.max(l_in_ref[0].astype(jnp.float32), axis=0)[:, None]
    m_ref[...] = jnp.broadcast_to(m_col, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_col, l_ref.shape)

  def _do_update():
    # No table here: a causal tile is masked whole, interior or not.
    mask = _tile_mask(offsets_ref[0] + i_q * block_q,
                      offsets_ref[1] + i_k * block_k, block_q, block_k, None,
                      None) if causal else None
    _block_update(q_ref[0], k_ref[0], v_ref[0], acc_ref, m_ref, l_ref,
                  slice(None), scale=scale, mask=mask)

  if causal:
    # Global-position block skip (offsets are traced scalars): the block
    # contributes nothing when its largest q position is left of its
    # smallest k position.
    @pl.when(offsets_ref[0] + i_q * block_q + block_q - 1
             >= offsets_ref[1] + i_k * block_k)
    def _update():
      _do_update()
  else:
    _do_update()

  @pl.when(i_k == n_k - 1)
  def _finalize():
    o_out_ref[0] = acc_ref[:]
    m_row = jnp.max(m_ref[...], axis=-1)                     # [bq]
    l_row = jnp.max(l_ref[...], axis=-1)
    m_out_ref[0] = jnp.broadcast_to(m_row[None, :], (8, block_q))
    l_out_ref[0] = jnp.broadcast_to(l_row[None, :], (8, block_q))


def flash_attention_carry(q, k, v, o, m, l, q_offset, k_offset,
                          causal: bool, scale: float,
                          block_q: int = 128, block_k: int = 128,
                          interpret: Optional[bool] = None):
  """One unnormalized flash update of (o, m, l) with a new k/v block.

  Shapes: q [BH, Lq, D]; k/v [BH, Lk, D]; o [BH, Lq, D] f32; m/l [BH, Lq]
  f32. ``q_offset``/``k_offset`` are traced global-position scalars.
  Returns updated (o, m, l). This is the ring-attention inner kernel;
  forward-only (no VJP) — the differentiable ring path is the jnp one.
  """
  if interpret is None:
    interpret = not runtime.on_tpu()
  bh, l_q, d = q.shape
  l_k = k.shape[1]
  block_q = min(block_q, l_q)
  block_k = min(block_k, l_k)
  if l_q % block_q or l_k % block_k:
    raise ValueError(
        'Shard lengths ({}, {}) must be multiples of the block sizes '
        '({}, {}).'.format(l_q, l_k, block_q, block_k))
  n_q = l_q // block_q
  n_k = l_k // block_k
  kernel = functools.partial(
      _flash_carry_kernel, scale=scale, causal=causal, block_q=block_q,
      block_k=block_k)
  offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                       jnp.asarray(k_offset, jnp.int32)])
  # m/l carries are padded to 8 broadcast sublanes for Mosaic's block
  # divisibility rule (same scheme as _flash_fwd_call's lse output).
  m8 = jnp.broadcast_to(m[:, None, :], (bh, 8, l_q))
  l8 = jnp.broadcast_to(l[:, None, :], (bh, 8, l_q))
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(bh, n_q, n_k),
      # Index maps receive the scalar-prefetch ref as a trailing arg.
      in_specs=[
          pl.BlockSpec((1, block_q, d), lambda b, i, j, off: (b, i, 0)),
          pl.BlockSpec((1, block_k, d), lambda b, i, j, off: (b, j, 0)),
          pl.BlockSpec((1, block_k, d), lambda b, i, j, off: (b, j, 0)),
          pl.BlockSpec((1, block_q, d), lambda b, i, j, off: (b, i, 0)),
          pl.BlockSpec((1, 8, block_q), lambda b, i, j, off: (b, 0, i)),
          pl.BlockSpec((1, 8, block_q), lambda b, i, j, off: (b, 0, i)),
      ],
      out_specs=[
          pl.BlockSpec((1, block_q, d), lambda b, i, j, off: (b, i, 0)),
          pl.BlockSpec((1, 8, block_q), lambda b, i, j, off: (b, 0, i)),
          pl.BlockSpec((1, 8, block_q), lambda b, i, j, off: (b, 0, i)),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_q, d), jnp.float32),
          pltpu.VMEM((block_q, 128), jnp.float32),
          pltpu.VMEM((block_q, 128), jnp.float32),
      ],
  )
  o_out, m_out8, l_out8 = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=[
          jax.ShapeDtypeStruct(o.shape, jnp.float32),
          jax.ShapeDtypeStruct((bh, 8, l_q), jnp.float32),
          jax.ShapeDtypeStruct((bh, 8, l_q), jnp.float32),
      ],
      interpret=interpret,
  )(offsets, q, k, v, o, m8, l8)
  return o_out, m_out8[:, 0, :], l_out8[:, 0, :]


# Backward block sizes are DECOUPLED from the forward defaults: the
# forward's (1024, 1024) tuning holds one [bq, bk] f32 score block; the
# backward holds four ([s, p, dp, ds]) plus two accumulator blocks, so the
# same sizes would 4x the peak VMEM and OOM at the L=32k headline case.
# Defaults are L-adaptive from a v5e sweep (B=1 H=8 D=128 causal, fwd+bwd
# chained): (256, 256) wins at L<=4k (7.1 vs 10.5 ms); (512, 1024) wins
# from 8k up (18.8/19.4/58.0 ms at 8k/16k/32k vs 20.4/20.4/60.8 for the
# flat 512s).


def _bwd_default_blocks(l_q: int, l_k: int):
  # Keyed on the LARGER side so cross-attention with mismatched lengths
  # lands in the regime its bigger grid actually runs in.
  return (256, 256) if max(l_q, l_k) <= 4096 else (512, 1024)


def _bwd_part(t, q_blocks_ref, k_blocks_ref, q_ref, k_ref, v_ref, do_ref,
              lse_ref, delta_ref, row, rows, col, cols, masked, *, scale,
              causal, block_q, block_k, window=None, diffusion=None):
  """What every backward kernel forms of a part of step ``t``'s tile (the
  rows [row, row + rows) of the q block against the columns [col, col +
  cols) of the k block: ``_each_part``), from the saved log-sum-exp: (q,
  k, do, p, ds), float32 2D blocks."""
  q_rows, k_rows = _rows_at(row, rows), _rows_at(col, cols)
  q = q_ref[0, q_rows].astype(jnp.float32)
  k = k_ref[0, k_rows].astype(jnp.float32)
  do = do_ref[0, q_rows].astype(jnp.float32)
  # Reduce over the uniform broadcast sublanes instead of slicing one
  # (width-1 memref slices are rejected by jax 0.9 Mosaic).
  lse = jnp.max(lse_ref[0, :, q_rows].astype(jnp.float32), axis=0)[:, None]
  delta = jnp.max(delta_ref[0, :, q_rows].astype(jnp.float32),
                  axis=0)[:, None]
  s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32) * scale
  if masked:
    s = jnp.where(_tile_mask(q_blocks_ref[t] * block_q + row,
                             k_blocks_ref[t] * block_k + col, rows, cols,
                             window, diffusion), s, NEG_INF)
  p = jnp.exp(s - lse)
  if causal or diffusion is not None:
    # On every tile of a masked call, interior too: see _block_update.
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
  dp = jax.lax.dot_general(do, v_ref[0, k_rows].astype(jnp.float32),
                           (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)
  ds = p * (dp - delta) * scale
  return q, k, do, p, ds


def _rows_t(a, b):
  """a^T b in float32: [rows, m], [rows, n] -> [m, n] (dv = p^T do, dk =
  ds^T q)."""
  return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)


def _flash_bwd_fused_kernel(q_blocks_ref, k_blocks_ref, flags_ref, spans_ref,
                            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                            group: int, strips, **tile):
  """dq, dk and dv in one: grid (bh, steps of ``tile_table``), the dq
  kernel's, q block resident and k/v streaming; s and dp are formed once a
  tile (or once a strip's span of an edge tile: ``_each_part``) and all
  three gradients leave the step. dk and dv accumulate in float32 over the
  WHOLE k/v head ([l_k, d] scratch each, a part adds at its columns' rows):
  zeroed at the first step of the group's first query head, written at the
  last step of its last. The group's query heads follow one another on the
  first grid axis and the dk/dv output block is the whole k/v head, so it
  stays put over all of them and is written back once; a k/v block that
  the mask leaves no tile keeps its zeros."""
  b, t = pl.program_id(0), pl.program_id(1)
  flags = flags_ref[t]
  last_step = t == pl.num_programs(1) - 1
  block_k = tile['block_k']

  @pl.when((t == 0) & (b % group == 0))
  def _init_kv():
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

  @pl.when(flags & FIRST != 0)
  def _init_q():
    dq_acc[...] = jnp.zeros_like(dq_acc)

  def part(row, rows, col, cols, masked):
    q, k, do, p, ds = _bwd_part(t, q_blocks_ref, k_blocks_ref, q_ref, k_ref,
                                v_ref, do_ref, lse_ref, delta_ref, row, rows,
                                col, cols, masked, **tile)
    # A strip's span starts on a sub-block as wide as the strip is tall.
    kv_rows = pl.ds(pl.multiple_of(k_blocks_ref[t] * block_k + col,
                                   block_k if isinstance(col, int) else rows),
                    cols)
    dv_acc[kv_rows, :] += _rows_t(p, do)
    dk_acc[kv_rows, :] += _rows_t(ds, q)
    dq_acc[_rows_at(row, rows)] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

  _each_part(t, flags_ref, spans_ref, part,
             masked=tile['causal'] or tile['diffusion'] is not None,
             block_q=tile['block_q'], block_k=block_k, strips=strips)

  @pl.when(flags & LAST != 0)
  def _finalize_q():
    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

  @pl.when(last_step & (b % group == group - 1))
  def _finalize_kv():
    dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_kv_kernel(head_ref, q_blocks_ref, k_blocks_ref, flags_ref,
                         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, **tile):
  """dk/dv of the two-kernel backward: grid (bh of k/v, steps of
  ``tile_table(k_resident=True)``) — k/v block resident (accumulators in
  scratch), q/do/lse/delta stream through. With grouped-query heads a k/v
  block's steps run over the ``group`` query heads that read this k/v head,
  head-major, so dk/dv are summed over the group in the scratch and written
  once."""
  del head_ref
  t = pl.program_id(1)
  flags = flags_ref[t]

  @pl.when(flags & FIRST != 0)
  def _init():
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

  q, _, do, p, ds = _bwd_part(
      t, q_blocks_ref, k_blocks_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
      delta_ref, 0, tile['block_q'], 0, tile['block_k'],
      tile['causal'] or tile['diffusion'] is not None, **tile)
  dv_acc[...] += _rows_t(p, do)
  dk_acc[...] += _rows_t(ds, q)

  @pl.when(flags & LAST != 0)
  def _finalize():
    dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_q_kernel(q_blocks_ref, k_blocks_ref, flags_ref, spans_ref,
                        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_acc, **tile):
  """dq of the two-kernel backward: grid (bh, steps of ``tile_table``) — q
  block resident, k/v stream through; s and dp formed a second time."""
  del spans_ref  # no strips on this path
  t = pl.program_id(1)
  flags = flags_ref[t]

  @pl.when(flags & FIRST != 0)
  def _init():
    dq_acc[...] = jnp.zeros_like(dq_acc)

  _, k, _, _, ds = _bwd_part(
      t, q_blocks_ref, k_blocks_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
      delta_ref, 0, tile['block_q'], 0, tile['block_k'],
      tile['causal'] or tile['diffusion'] is not None, **tile)
  dq_acc[...] += jax.lax.dot_general(
      ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

  @pl.when(flags & LAST != 0)
  def _finalize():
    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# The fused backward holds, beside the blocks it streams, dk and dv of one
# whole k/v head in VMEM: two float32 accumulators and the two output
# blocks, which Pallas buffers twice. A call whose k/v head would take more
# than this runs the two-kernel backward, whose working set does not grow
# with the length. What it comes to by shape, and where the line was read
# on the chip: CHANGES.md, PR 35.
FUSED_BWD_RESIDENT_BYTES = 64 * 1024 * 1024
# Scoped VMEM asked for beside the resident bytes: the streamed blocks,
# twice, and the body's four [block_q, block_k] float32 tiles (the default
# scoped limit, 16 MiB, is what the two-kernel backward runs under).
_FUSED_BWD_STREAMED_BYTES = 32 * 1024 * 1024


def _fused_bwd_resident_bytes(l_k: int, d: int, dtype,
                              d_v: Optional[int] = None) -> int:
  """dk [l_k, d] and dv [l_k, d_v] (d_v None: d) of one k/v head: float32
  accumulators, output blocks twice."""
  return l_k * (d + (d if d_v is None else d_v)) * (
      4 + 2 * jnp.dtype(dtype).itemsize)


def _flash_bwd_pallas(q, k, v, out, lse, d_out, *, scale, causal,
                      block_q, block_k, interpret, window=None,
                      diffusion=None):
  """Full Pallas backward: dq over [BH, L, D], dk, dv over k/v's
  [BH/group, L, D].

  ONE kernel (``_flash_bwd_fused_kernel``, five products a tile) where dk
  and dv of a whole k/v head fit in VMEM (FUSED_BWD_RESIDENT_BYTES, judged
  from ``l_k``, ``d`` and the dtype alone); else two (FlashAttention-2
  structure, seven products): dk/dv with the k/v block resident and q
  streaming, dq with the q block resident and k/v streaming. Each runs over
  the tiles the mask keeps (``tile_table``). P is recomputed from the
  forward's saved log-sum-exp; no [L, L] tensor exists in either pass.
  delta = rowsum(do * out) is one fused elementwise pass XLA handles before
  the kernels. Grouped-query heads: every kernel reads k/v head
  ``b // group`` through its index maps, and dk/dv are summed over the
  group's query heads in VMEM (see the kernels). Why the fused kernel
  carries the dq kernel's name: the module docstring.
  """
  bh, l_q, d = q.shape
  l_k, d_v = k.shape[1], v.shape[2]
  group = bh // k.shape[0]
  fused = _fused_bwd_resident_bytes(l_k, d, k.dtype, d_v) <= \
      FUSED_BWD_RESIDENT_BYTES
  tiles = (l_q // block_q, l_k // block_k, block_q, block_k, causal, window,
           diffusion)
  q_table = tile_table(*tiles)
  kv_table = None if fused else tile_table(*tiles, k_resident=True,
                                           group=group)
  # ONE backward kernel's steps a query head. The two kernels' differ only
  # where the mask leaves a q block or a k/v block empty: the larger. The
  # two-kernel backward runs an edge tile whole.
  steps = q_table.shape[1] if fused else max(
      q_table.shape[1], kv_table.shape[1] // group)
  spans, strips = _strip_plan(
      q_table, l_q, l_k, block_q, block_k, causal, window, diffusion,
      whole=not fused, suffix='_bwd', bh=bh, steps=steps,
      backward_kernels=1 if fused else 2)
  do = d_out.astype(jnp.float32)
  delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)      # [BH, Lq]
  # lse/delta ride as [BH, 8, L] broadcast-sublane blocks (Mosaic's
  # second-minor divisibility rule — same scheme as the forward's lse).
  lse8 = jnp.broadcast_to(lse[:, None, :], (bh, 8, l_q))
  delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, l_q))
  return _flash_bwd_call(*q_table[1:], spans, kv_table, q, k, v, d_out, lse8,
                         delta8, scale=scale, causal=causal, block_q=block_q,
                         block_k=block_k, interpret=interpret, window=window,
                         diffusion=diffusion, strips=strips)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_bwd_call(q_blocks, k_blocks, flags, spans, kv_table, q, k, v,
                    d_out, lse8, delta8, *, scale, causal, block_q, block_k,
                    interpret, window, diffusion, strips):
  """The backward kernels over their tables (``_flash_bwd_pallas``): the
  fused one, or the two-kernel pair where ``kv_table`` (the dk/dv kernel's)
  is given."""
  bh, _, d = q.shape
  l_k, d_v = k.shape[1], v.shape[2]
  group = bh // k.shape[0]
  kv = _kv_head(group)
  tile = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              window=window, diffusion=diffusion)

  # Index maps receive the table's rows (and the spans) as trailing
  # arguments.
  q_of_q = lambda b, t, qs, *_: (b, qs[t], 0)
  row_of_q = lambda b, t, qs, *_: (b, 0, qs[t])
  kv_of_q = lambda b, t, qs, ks, *_: (kv(b), ks[t], 0)
  q_resident = dict(
      num_scalar_prefetch=4,
      grid=(bh, flags.shape[0]),
      in_specs=[
          pl.BlockSpec((1, block_q, d), q_of_q),
          pl.BlockSpec((1, block_k, d), kv_of_q),
          pl.BlockSpec((1, block_k, d_v), kv_of_q),
          pl.BlockSpec((1, block_q, d_v), q_of_q),
          pl.BlockSpec((1, 8, block_q), row_of_q),
          pl.BlockSpec((1, 8, block_q), row_of_q),
      ])
  dq_spec = pl.BlockSpec((1, block_q, d), q_of_q)
  dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
  dkv_shapes = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype)]
  if kv_table is None:
    resident = _fused_bwd_resident_bytes(l_k, d, k.dtype, d_v)
    kv_head = lambda width: pl.BlockSpec(
        (1, l_k, width), lambda b, t, *_: (kv(b), 0, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, group=group,
                          strips=strips, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            out_specs=[dq_spec, kv_head(d), kv_head(d_v)],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((l_k, d), jnp.float32),
                            pltpu.VMEM((l_k, d_v), jnp.float32)],
            **q_resident),
        out_shape=[dq_shape] + dkv_shapes,
        # The query heads of a group share the dk/dv accumulators: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=resident + _FUSED_BWD_STREAMED_BYTES),
        interpret=interpret,
        name='flash_attention_bwd_dq',
    )(q_blocks, k_blocks, flags, spans, q, k, v, d_out, lse8, delta8)

  # dk/dv: b is a k/v head, its step's query head b * group + head[t].
  q_of_kv = lambda b, t, head, qs, ks, flags: (b * group + head[t], qs[t], 0)
  row_of_kv = lambda b, t, head, qs, ks, flags: (b * group + head[t], 0, qs[t])
  kv_of_kv = lambda b, t, head, qs, ks, flags: (b, ks[t], 0)
  dk, dv = pl.pallas_call(
      functools.partial(_flash_bwd_kv_kernel, **tile),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=4,
          grid=(bh // group, kv_table.shape[1]),
          in_specs=[
              pl.BlockSpec((1, block_q, d), q_of_kv),
              pl.BlockSpec((1, block_k, d), kv_of_kv),
              pl.BlockSpec((1, block_k, d_v), kv_of_kv),
              pl.BlockSpec((1, block_q, d_v), q_of_kv),
              pl.BlockSpec((1, 8, block_q), row_of_kv),
              pl.BlockSpec((1, 8, block_q), row_of_kv),
          ],
          out_specs=[
              pl.BlockSpec((1, block_k, d), kv_of_kv),
              pl.BlockSpec((1, block_k, d_v), kv_of_kv),
          ],
          scratch_shapes=[
              pltpu.VMEM((block_k, d), jnp.float32),
              pltpu.VMEM((block_k, d_v), jnp.float32),
          ],
      ),
      out_shape=dkv_shapes,
      interpret=interpret,
      name='flash_attention_bwd_dkv',
  )(*kv_table, q, k, v, d_out, lse8, delta8)
  dq = pl.pallas_call(
      functools.partial(_flash_bwd_q_kernel, **tile),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          out_specs=[dq_spec],
          scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
          **q_resident),
      out_shape=[dq_shape],
      interpret=interpret,
      name='flash_attention_bwd_dq',
  )(q_blocks, k_blocks, flags, spans, q, k, v, d_out, lse8, delta8)[0]
  return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_diff(q, k, v, causal, scale, block_q, block_k, interpret,
                block_q_bwd, block_k_bwd, window, diffusion):
  """custom_vjp core over [BH, L, D] operands."""
  del block_q_bwd, block_k_bwd  # backward-only
  out, _ = _flash_bhld(q, k, v, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k,
                       interpret=interpret, window=window,
                       diffusion=diffusion)
  return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               block_q_bwd, block_k_bwd, window, diffusion):
  del block_q_bwd, block_k_bwd
  q, k, v = (checkpoint_name(x, name)
             for x, name in zip((q, k, v), (FLASH_Q, FLASH_K, FLASH_V)))
  out, lse = _flash_bhld(q, k, v, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret, window=window,
                         diffusion=diffusion)
  # The named ``out`` is both the result and the residual: one saved array
  # serves the caller's next layer and the backward kernels.
  out = checkpoint_name(out, FLASH_OUT)
  lse = checkpoint_name(lse, FLASH_LSE)
  return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, block_q_bwd,
               block_k_bwd, window, diffusion, residuals, d_out):
  """The Pallas backward (see _flash_bwd_pallas).

  Until round 4 this was an XLA lax.scan recompute; it is now the same
  kernel family as the forward, over the tiles the mask keeps and with its
  own block sizes (_bwd_default_blocks — the forward's 1024 would 4x the
  backward's VMEM working set and OOM the L=32k case)."""
  q, k, v, out, lse = residuals
  l_q = q.shape[1]
  l_k = k.shape[1]
  default_bq, default_bk = _bwd_default_blocks(l_q, l_k)
  bq = _dividing_block_or_raise(min(block_q_bwd or default_bq, l_q), l_q)
  bk = _dividing_block_or_raise(min(block_k_bwd or default_bk, l_k), l_k)
  dq, dk, dv = _flash_bwd_pallas(
      q, k, v, out, lse, d_out, scale=scale, causal=causal,
      block_q=bq, block_k=bk, interpret=interpret, window=window,
      diffusion=diffusion)
  return dq, dk, dv


_flash_diff.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 1024,
                    block_k: int = 1024,
                    interpret: Optional[bool] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    window: Optional[int] = None,
                    block_diffusion: Optional[Tuple[int, int]] = None):
  """Exact attention over [B, L, H, D] inputs, O(L) memory, differentiable.

  Forward runs the Pallas kernel (_flash_kernel); the backward is the
  blockwise FlashAttention recomputation (custom VJP; one more kernel, two
  where dk and dv of a k/v head outgrow VMEM: _flash_bwd_pallas) so
  training never sees an [L, L] tensor either. The grid of each kernel is
  the list of the tiles the mask keeps (``tile_table``; the module
  docstring has its order), every tile of the rectangle for an unmasked
  call. Blocks step down automatically to sizes dividing L.
  ``interpret=None`` auto-selects the Pallas interpreter off-TPU so
  tests run on CPU.

  Grouped-query attention: k and v may hold fewer heads than q
  ([B, L, H/group, D]); query head n reads key/value head n // group.
  The kernels index the shared k/v blocks, nothing is repeated in HBM,
  and dk/dv come back summed over the group. ``window`` (causal only)
  keeps, for row i, the columns j with ``0 <= i - j < window``; tiles
  that lie wholly outside that band are in no kernel's grid.
  ``window=None`` with equal head counts is the plain causal (or full)
  attention the kernels always computed.

  ``block_diffusion=(length, block)`` (not causal, no window) is the mask
  of block-diffusion training over 2 x ``length`` positions, the noised
  copy first and the clean copy behind it, ``block`` dividing ``length``
  (i, j positions, blk the block of a position within its own half):

    noised i, noised j:  blk(i) == blk(j)     its own block, both ways
    noised i, clean  j:  blk(j) <  blk(i)     clean tokens of earlier blocks
    clean  i, clean  j:  blk(j) <= blk(i)     block-causal
    clean  i, noised j:  never

  It keeps length^2 + length x block of the 4 length^2 pairs; the tiles
  that hold none (``_block_in_block_diffusion``) are in no kernel's grid.

  A masked call sets, on the host while it is traced, the gauges
  ``attention/mask_pairs_needed``, ``attention/mask_pairs_computed`` (the
  pairs the forward kernel computes: an interior tile whole, an edge
  tile's strips over their spans, or the whole edge tile where its blocks
  hold no strips; ``..._computed_bwd`` each backward kernel's),
  ``attention/grid_steps`` (the steps the forward kernel launches;
  ``..._steps_bwd`` each backward kernel's) and ``attention/edge_steps``
  (of those, the steps that run as strips; ``attention/edge_steps_bwd``
  the backward's), all heads of the call; and ``attention/backward_kernels``,
  the kernels the backward launches (1 fused, 2 not).

  Default block sizes come from v5e sweeps (B=1, H=8, D=128, causal,
  chained on-device timing): (1024, 1024) — grid-step count (fixed
  per-step overhead) and k/v re-fetch traffic are the levers, so bigger
  blocks win until the f32 score matrix presses the 16 MB scoped-VMEM
  limit.

  The value width may differ from the key width (latent attention: q and
  k of 192, v of 128): ``v`` [B, L, H_kv, D_v] gives an output of D_v; the
  default ``scale`` is 1 / sqrt of the KEY width.

  Head dims below 128 are zero-padded up to 128 for the kernels
  (``kernel_width``): jax 0.9's Mosaic rejects memref slices whose lane
  extent is not 128-aligned, which the accumulator sub-refs need. Exact —
  zero columns change neither scores nor outputs; padding/slicing happens
  outside the custom_vjp so the backward sees the padded problem and
  autodiff of the pad/slice restores [.., d] gradients.
  """
  if scale is None:
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
  if interpret is None:
    interpret = not runtime.on_tpu()
  b, l_q, h, d = q.shape
  l_k, h_kv, d_v = k.shape[1], k.shape[2], v.shape[3]
  if h % h_kv or v.shape[2] != h_kv:
    raise ValueError(
        'grouped-query attention needs the query heads ({}) to be a '
        'multiple of the key/value heads ({}, {}).'.format(
            h, h_kv, v.shape[2]))
  if k.shape[3] != d:
    raise ValueError('q and k need one head width; got {} and {}.'.format(
        d, k.shape[3]))
  if window is not None and (not causal or window < 1):
    raise ValueError('window={!r} needs causal=True and window >= 1.'.format(
        window))
  if block_diffusion is not None:
    length, block = block_diffusion
    if causal or window is not None or l_q != l_k or l_q != 2 * length or \
        block < 1 or length % block:
      raise ValueError(
          'block_diffusion={!r} needs causal=False, no window, q and k of '
          '2 x length positions ({}, {}) and a block that divides the '
          'length.'.format(block_diffusion, l_q, l_k))
    block_diffusion = (int(length), int(block))
  if jnp.dtype(q.dtype).itemsize >= 4:
    # f32 operands double the VMEM block footprint; the bf16-tuned
    # (1024, 1024) defaults press past the 16 MB scoped-VMEM limit at
    # L>=4096 (measured: 'Scoped allocation ... exceeded scoped vmem
    # limit'). Conservative caps keep the f32 working set a few MB.
    block_q = min(block_q, 256)
    block_k = min(block_k, 512)

  block_q = _dividing_block_or_raise(min(block_q, l_q), l_q)
  block_k = _dividing_block_or_raise(min(block_k, l_k), l_k)

  def _to_bhld(x):
    width = x.shape[3]
    x = x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], width)
    padded = kernel_width(width) if not interpret else width
    if padded != width:
      x = jnp.pad(x, ((0, 0), (0, 0), (0, padded - width)))
    return x

  out = _flash_diff(_to_bhld(q), _to_bhld(k), _to_bhld(v), causal, scale,
                    block_q, block_k, interpret, block_q_bwd, block_k_bwd,
                    window, block_diffusion)
  if out.shape[2] != d_v:
    out = out[:, :, :d_v]
  return out.reshape(b, h, l_q, d_v).transpose(0, 2, 1, 3)


def kernel_width(width: int) -> int:
  """The head width the kernels run at on the TPU: a width under 128 is
  zero-padded to 128 lanes (Mosaic slices the accumulators only at whole
  128-lane tiles), a wider one runs whole, one block of the array's full
  width (192 for latent attention's q and k: not padded to 256, which
  would cost a third more on the MXU)."""
  return 128 if width < 128 else width
