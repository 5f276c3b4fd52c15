"""Mixture-of-Experts MLP with expert parallelism over the mesh.

The reference has no MoE; this is part of the build-side mandate that
distributed training be first-class (SURVEY.md §5 build goals), filling
the 'ep' slot next to dp/fsdp/tp/sp. The design is the GShard/Switch
dispatch in its TPU-native form:

* **Static shapes everywhere.** Routing uses one-hot dispatch/combine
  einsums against a fixed per-expert capacity — no gather/scatter with
  data-dependent shapes, which XLA cannot tile. Tokens over capacity are
  dropped (their residual branch contributes zero), the standard
  Switch-style overflow semantics.
* **Experts as stacked params.** All experts live in single
  [E, d, h]/[E, h, d] tensors computed with einsums over the expert dim;
  under expert parallelism the params carry a ``P('expert', ...)``
  sharding (EP_RULES_MOE in parallel/sharding.py).
* **Explicit all-to-all dispatch under EP.** With ``ep_axis`` set, the
  expert computation runs in a shard_map: the TOKEN dim is split over
  the expert axis (GShard's groups — each shard routes its L/N tokens
  locally), ``lax.all_to_all`` exchanges the per-expert buffers so each
  shard holds ALL groups' tokens for its E/N resident experts, and a
  second all-to-all routes results back. Measured against leaving the
  einsums to GSPMD (which lowers this pattern to all-gathers + a
  combine all-reduce over the full [B, L, d] activations): the a2a
  pair moves ~2*k*C*d/N bytes per device vs ~3*B*L*d for the
  gather/reduce pattern — the difference between communication that
  SHRINKS with the expert axis and communication that does not.
* **Router in f32** (logits, softmax, and the load-balancing auxiliary
  loss) regardless of the activation dtype: top-k ties and the aux-loss
  gradients are precision-sensitive at bf16.

The auxiliary load-balancing loss is the Switch formulation
(mean over experts of fraction_dispatched * mean_router_prob, scaled by
E); consumers add ``aux_weight * aux_loss`` to their objective.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.parallel import grouped_matmul as gmm_lib
from tensor2robot_tpu.parallel.sharding import constrain


def _capacity(k: int, tokens: int, factor: float, num_experts: int) -> int:
  """Per-expert slots for a token group: ceil(k*T*f/E), 8-aligned, <= T."""
  capacity = int(np.ceil(k * tokens * factor / num_experts))
  capacity = max(8, -(-capacity // 8) * 8)
  return min(capacity, tokens)


def _dispatch_combine(probs, expert_idx, num_experts: int, k: int,
                      capacity: int):
  """(dispatch, combine) one-hot tensors [B, T, E, C] for one token group.

  Position of each (token, choice) in its expert's buffer is the running
  count of earlier assignments to that expert (k-major cumsum order);
  tokens over capacity are dropped. Gates: k == 1 uses the RAW router
  probability (Switch semantics — renormalizing over a single kept
  choice would make the gate identically 1.0 and starve the router of
  task-loss gradient); k > 1 renormalizes over the kept subset.
  """
  b, t, e = probs.shape
  onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # [B, T, K, E]
  flat = onehot.transpose(0, 2, 1, 3).reshape(b, k * t, e)    # [B, KT, E]
  position = jnp.cumsum(flat, axis=1) - flat
  flat = flat * (position < capacity)
  pos_onehot = flat[..., None] * jax.nn.one_hot(
      position.astype(jnp.int32), capacity, dtype=jnp.float32)
  dispatch = pos_onehot.reshape(b, k, t, e, capacity).sum(1)  # [B, T, E, C]
  gate = dispatch.sum(-1) * probs                             # [B, T, E]
  if k > 1:
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
  combine = gate[..., None] * dispatch
  return dispatch, combine


class MoEMlp(nn.Module):
  """Top-k routed expert MLP: [B, L, d] -> [B, L, d] (+ aux loss).

  ``capacity_factor``: per-expert slots = ceil(k * T * factor / E),
  rounded up to a multiple of 8 (sublane alignment), where T is the
  routing GROUP size: the full L without expert parallelism, L/N per
  shard with it (GShard grouped dispatch — each group routes and drops
  independently). With ``capacity_factor >= E / k`` no token can
  overflow in either regime, making the two paths numerically identical
  (the parity tests' setting). Returns ``(out, aux_loss)``; aux_loss is
  the Switch load-balance term computed over ALL tokens.
  """

  num_experts: int
  expert_dim: int
  top_k: int = 2
  capacity_factor: float = 1.25
  mesh: Optional[object] = None
  ep_axis: Optional[str] = None
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, l, d = x.shape
    e, k = self.num_experts, min(self.top_k, self.num_experts)
    ep_size = 1
    if self.ep_axis and self.mesh is not None:
      if self.ep_axis not in self.mesh.shape:
        raise ValueError(
            'ep_axis {!r} is not an axis of the mesh (axes: {}); build the '
            'mesh with an expert axis (parallel.create_mesh).'.format(
                self.ep_axis, tuple(self.mesh.axis_names)))
      ep_size = int(self.mesh.shape[self.ep_axis])
      if e % ep_size:
        raise ValueError(
            'expert parallelism needs num_experts ({}) divisible by the '
            '{!r} axis size ({}).'.format(e, self.ep_axis, ep_size))
      if l % ep_size:
        raise ValueError(
            'expert parallelism routes tokens in L/N groups: the token '
            'dim ({}) must be divisible by the {!r} axis size ({}).'
            .format(l, self.ep_axis, ep_size))

    # Router (f32): probs over experts per token. Replicated math — GSPMD
    # shards it over whatever axes the activations carry.
    logits = nn.Dense(e, dtype=jnp.float32, name='router')(
        x.astype(jnp.float32))                              # [B, L, E]
    probs = jax.nn.softmax(logits, axis=-1)
    _, expert_idx = jax.lax.top_k(probs, k)                 # [B, L, K]

    w_in = self.param('w_in', nn.initializers.lecun_normal(),
                      (e, d, self.expert_dim), jnp.float32)
    w_out = self.param('w_out', nn.initializers.lecun_normal(),
                       (e, self.expert_dim, d), jnp.float32)

    if ep_size > 1:
      out = self._expert_parallel_apply(x, probs, expert_idx, w_in, w_out,
                                        e, k, ep_size)
    else:
      out = self._dense_apply(x, probs, expert_idx, w_in, w_out, e, k)

    # Switch load-balance loss: E * sum_e fraction_tokens_e * mean_prob_e
    # (uses the pre-capacity primary assignments, the standard estimator).
    primary = jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32)
    fraction = primary.reshape(-1, e).mean(0)
    mean_prob = probs.reshape(-1, e).mean(0)
    aux_loss = e * jnp.sum(fraction * mean_prob)
    return out.astype(x.dtype), aux_loss

  def _dense_apply(self, x, probs, expert_idx, w_in, w_out, e, k):
    """Single-group dispatch: the whole L routes against global capacity."""
    capacity = _capacity(k, x.shape[1], self.capacity_factor, e)
    dispatch, combine = _dispatch_combine(probs, expert_idx, e, k, capacity)
    expert_in = jnp.einsum('blec,bld->ebcd', dispatch.astype(self.dtype),
                           x.astype(self.dtype))            # [E, B, C, d]
    h = nn.gelu(jnp.einsum('ebcd,edh->ebch', expert_in,
                           w_in.astype(self.dtype)))
    expert_out = jnp.einsum('ebch,ehd->ebcd', h,
                            w_out.astype(self.dtype))       # [E, B, C, d]
    return jnp.einsum('blec,ebcd->bld', combine.astype(self.dtype),
                      expert_out)

  def _expert_parallel_apply(self, x, probs, expert_idx, w_in, w_out,
                             e, k, ep_size):
    """GShard grouped dispatch in a shard_map: tokens split over the
    expert axis into N groups that route locally; ``lax.all_to_all``
    exchanges per-expert buffers so each shard computes its E/N resident
    experts over ALL groups' tokens, and a second all-to-all routes the
    results back (see module docstring for the measured byte comparison
    against leaving this pattern to GSPMD)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from tensor2robot_tpu.parallel.mesh import DATA_AXIS

    ep = self.ep_axis
    el = e // ep_size                                # local experts
    b, l, d = x.shape
    ls = l // ep_size                                # group (local) tokens
    capacity = _capacity(k, ls, self.capacity_factor, e)
    data_size = int(self.mesh.shape.get(DATA_AXIS, 1))
    batch_axis = (DATA_AXIS
                  if data_size > 1 and b % data_size == 0 else None)
    dtype = self.dtype

    def body(x_loc, probs_loc, idx_loc, w_in_loc, w_out_loc):
      # x_loc [b', Ls, d]; w_in_loc [El, d, h].
      dispatch, combine = _dispatch_combine(probs_loc, idx_loc, e, k,
                                            capacity)
      expert_in = jnp.einsum('blec,bld->ebcd', dispatch.astype(dtype),
                             x_loc.astype(dtype))    # [E, b', C, d]
      # Forward all-to-all: axis 0 (E = N*El, shard-contiguous expert
      # blocks) splits into N messages; received blocks stack source-
      # group-major -> [N, El, b', C, d] -> local experts over all groups.
      recv = jax.lax.all_to_all(expert_in, ep, split_axis=0,
                                concat_axis=0, tiled=True)
      bp = recv.shape[1]
      recv = recv.reshape(ep_size, el, bp, capacity, d)
      recv = recv.transpose(1, 2, 0, 3, 4).reshape(el, bp,
                                                   ep_size * capacity, d)
      h = nn.gelu(jnp.einsum('ebcd,edh->ebch', recv,
                             w_in_loc.astype(dtype)))
      out = jnp.einsum('ebch,ehd->ebcd', h, w_out_loc.astype(dtype))
      # Reverse all-to-all: regroup [El, b', N*C, d] by source group and
      # send each group its tokens back; received blocks stack
      # owner-shard-major, which IS global expert order (experts are
      # shard-contiguous) -> [E, b', C, d].
      out = out.reshape(el, bp, ep_size, capacity, d)
      out = out.transpose(2, 0, 1, 3, 4).reshape(ep_size * el, bp,
                                                 capacity, d)
      out = jax.lax.all_to_all(out, ep, split_axis=0, concat_axis=0,
                               tiled=True)           # [E, b', C, d]
      return jnp.einsum('blec,ebcd->bld', combine.astype(dtype), out)

    token_spec = P(batch_axis, ep, None)
    fn = shard_map(
        body, mesh=self.mesh,
        in_specs=(token_spec, token_spec, token_spec,
                  P(ep, None, None), P(ep, None, None)),
        out_specs=token_spec, check_rep=False)
    return fn(x, probs, expert_idx, w_in, w_out)


# -- the dropless layer -------------------------------------------------------
#
# ``MoEMlp`` above routes against a fixed per-expert capacity and drops what
# overflows. ``DroplessMoE`` below is the other contract: every (token,
# expert) pair whose expert is held here is computed, however uneven the
# routing, by grouping the pairs by expert and running grouped matrix
# products over the groups (parallel/grouped_matmul.py). It is told which
# experts it holds: the router is as wide as the whole model's, the layer
# computes its own experts' part of the result, and pairs routed to an
# absent expert are left out (on one chip of an expert-parallel deployment
# the other chips add their parts; nothing here stands in for them).


def route_top_k(router_logits: jnp.ndarray, top_k: int):
  """(expert index [T, k] int32, weight [T, k] f32): the ``top_k`` largest
  logits of each token and the softmax over just those, which is the softmax
  over all experts, cut to the chosen and renormalised."""
  values, index = jax.lax.top_k(router_logits.astype(jnp.float32), top_k)
  return index.astype(jnp.int32), jax.nn.softmax(values, axis=-1)


def route_sigmoid_bias(router_logits: jnp.ndarray, bias: jnp.ndarray,
                       top_k: int, scale: float = 1.0):
  """(expert index [T, k] int32, weight [T, k] f32) of a sigmoid router with
  a selection bias (auxiliary-loss-free balancing: DeepSeek-V3, LFM2): every
  expert scores s = sigmoid(logit) on its own; the ``top_k`` largest of
  s + ``bias`` [E] are CHOSEN, and weigh ``scale`` x s / (sum of the chosen
  s + 1e-6) (``scale`` is a config's ``routed_scaling_factor``). The bias
  chooses and never weighs; no gradient reaches it (it is state, moved by
  ``balanced_bias``)."""
  scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
  _, index = jax.lax.top_k(
      scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
  chosen = jnp.take_along_axis(scores, index, axis=-1)
  weight = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
  if scale != 1.0:
    weight = weight * scale
  return index.astype(jnp.int32), weight


def expert_counts(expert_index: jnp.ndarray, num_experts: int):
  """[E] f32: the pairs that chose each expert (no scatter: a comparison
  with every expert's index, summed)."""
  chose = expert_index.reshape(-1, 1) == jnp.arange(num_experts)[None, :]
  return jnp.sum(chose, axis=0, dtype=jnp.float32)


def balanced_bias(bias: jnp.ndarray, counts: jnp.ndarray, rate: float):
  """The selection bias after one step of the auxiliary-loss-free rule
  (DeepSeek-V3, arXiv:2412.19437 section 2.1.2): an expert that got fewer
  pairs than the mean rises by ``rate``, one that got more falls by it."""
  return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def buffer_rows(tokens: int, top_k: int, held: int, block_rows: int) -> int:
  """Rows that hold ANY routing of ``tokens`` tokens: every token's
  min(top_k, held) pairs, plus each expert's padding to whole tiles."""
  rows = tokens * min(top_k, held) + held * (block_rows - 1)
  return -(-rows // block_rows) * block_rows


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def group_pairs(expert_index: jnp.ndarray, first: int, held: int,
                block_rows: int):
  """Lays the pairs whose expert is in [first, first + held) out in rows.

  Expert after expert, in token order within an expert, each expert's rows
  padded to whole ``block_rows``-row tiles. Returns a dict:

    row_pair   [M]    flat pair (token * k + choice) of each row; T * k for
                      a padding row
    pair_row   [T, k] row of each pair; M for a pair whose expert is absent
    tile_group [M/block_rows] expert (0..held-1) of each tile
    num_tiles  [1]    tiles in use
    counts     [held] pairs of each held expert
    tile_chunks, tile_num_chunks  for each tile of ``token_block`` tokens the
                      ``chunk_rows``-row chunks of the buffer that hold its
                      pairs' rows, expert after expert, and how many they are
                      ([tiles x max_tile_chunks] flat and [tiles]; the sizes
                      are parallel/grouped_matmul.py's): what the kernels that
                      move rows walk
    pad_chunks [held x block_rows/chunk_rows] the chunks that are padding
                      alone, behind each expert's last row (-1: none):
                      ``moe_take_rows`` writes zeros there

  No scatter: rows come from one sort of the pairs by expert, the pairs'
  rows from a running count.
  """
  tokens, k = expert_index.shape
  pairs = tokens * k
  rows = buffer_rows(tokens, k, held, block_rows)
  local = expert_index.reshape(pairs) - first
  is_held = jnp.logical_and(local >= 0, local < held)
  local = jnp.where(is_held, local, held)
  onehot = (local[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
  counts = onehot.sum(0)
  rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
  padded = -(-counts // block_rows) * block_rows
  ends = jnp.cumsum(padded)
  starts = ends - padded
  pair_row = jnp.where(is_held, (onehot * starts).sum(1) + rank, rows)

  # Pairs in expert order (absent experts last), token order within.
  in_order = jnp.sort(local * pairs + jnp.arange(pairs, dtype=jnp.int32))
  in_order = in_order % pairs
  first_pair = jnp.cumsum(counts) - counts       # of each expert, in_order
  row = jnp.arange(rows, dtype=jnp.int32)
  group = jnp.minimum(jnp.searchsorted(ends, row, side='right'), held - 1)
  offset = row - starts[group]
  real = jnp.logical_and(row < ends[-1], offset < counts[group])
  row_pair = jnp.where(
      real, in_order[jnp.minimum(first_pair[group] + offset, pairs - 1)],
      pairs)

  tile_group = jnp.minimum(
      jnp.searchsorted(ends, jnp.arange(0, rows, block_rows, dtype=jnp.int32),
                       side='right'), held - 1)

  # An expert's rows are in token order, so the rows a tile of tokens owns in
  # one expert are one run: it starts where the earlier tiles' pairs end.
  token_block = gmm_lib.token_block(tokens)
  chunk = gmm_lib.chunk_rows(block_rows)
  max_chunks = gmm_lib.max_tile_chunks(tokens, k, held, block_rows)
  run_rows = onehot.reshape(tokens // token_block, token_block * k,
                            held).sum(1)                      # [tiles, held]
  run_start = starts[None, :] + jnp.cumsum(run_rows, axis=0) - run_rows
  first_chunk = run_start // chunk
  run_chunks = jnp.where(
      run_rows > 0, (run_start + run_rows - 1) // chunk - first_chunk + 1, 0)
  chunk_ends = jnp.cumsum(run_chunks, axis=1)
  slot = jnp.arange(max_chunks, dtype=jnp.int32)[None, :, None]
  slot_expert = jnp.sum(chunk_ends[:, None, :] <= slot, axis=-1)
  of_expert = slot_expert[..., None] == jnp.arange(held)      # one-hot, no gather
  tile_chunks = jnp.sum(
      of_expert * (first_chunk - chunk_ends + run_chunks)[:, None, :],
      axis=-1) + slot[..., 0]
  # Chunks of padding alone: behind an expert's last row, to its tiles' end.
  pad_chunks = ((starts + counts + chunk - 1) // chunk)[:, None] + jnp.arange(
      block_rows // chunk, dtype=jnp.int32)[None, :]
  pad_chunks = jnp.where(pad_chunks < (ends // chunk)[:, None], pad_chunks, -1)
  return {
      'row_pair': row_pair.astype(jnp.int32),
      'pair_row': pair_row.reshape(tokens, k).astype(jnp.int32),
      'tile_group': tile_group.astype(jnp.int32),
      'num_tiles': (ends[-1:] // block_rows).astype(jnp.int32),
      'counts': counts,
      'tile_chunks': tile_chunks.reshape(-1).astype(jnp.int32),
      'tile_num_chunks': chunk_ends[:, -1].astype(jnp.int32),
      'pad_chunks': pad_chunks.reshape(-1).astype(jnp.int32),
  }


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def dispatch_rows(x, layout, block_rows):
  """[M, d]: the row of each pair held here is its token's row of ``x``;
  padding inside a tile in use is zeros; later tiles are not written.
  ``layout`` is ``group_pairs``'s without its ``row_pair``.

  The backward pass is a gather too: a token's gradient is the sum over
  its pairs of their rows' gradients. Both ways only rows in use move
  (parallel/grouped_matmul.py: ``moe_take_rows``, ``moe_sum_rows``)."""
  return gmm_lib.moe_take_rows(x, None, layout, block_rows=block_rows)


def _dispatch_fwd(x, layout, block_rows):
  return dispatch_rows(x, layout, block_rows), layout


def _dispatch_bwd(block_rows, layout, d_rows):
  del block_rows
  return gmm_lib.moe_sum_rows(d_rows, None, layout,
                              out_dtype=d_rows.dtype), None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(rows, weight, layout):
  """[T, d] f32: token t's sum over its pairs of weight x the pair's row
  (a pair whose expert is absent has no row and adds nothing)."""
  return gmm_lib.moe_sum_rows(rows, weight, layout)


def _combine_fwd(rows, weight, layout):
  return combine_rows(rows, weight, layout), (rows, weight, layout)


def _combine_bwd(residuals, d_out):
  rows, weight, layout = residuals
  # At the rows' own width: the buffer is several times the tokens.
  d_rows = gmm_lib.moe_take_rows(
      d_out.astype(rows.dtype), weight, layout,
      block_rows=rows.shape[0] // layout['tile_group'].shape[0])
  d_weight = gmm_lib.moe_sum_rows(rows, None, layout, d_out=d_out)
  return d_rows, d_weight.astype(weight.dtype), None


combine_rows.defvjp(_combine_fwd, _combine_bwd)


def _pairs_moved(layout, block_rows: int):
  """Pairs whose row lies in a chunk listed for their token's tile."""
  pair_row = layout['pair_row']
  tiles = layout['tile_num_chunks'].shape[0]
  chunk = gmm_lib.chunk_rows(block_rows)
  listed = layout['tile_chunks'].reshape(tiles, 1, -1)
  listed = jnp.where(jnp.arange(listed.shape[-1]) <
                     layout['tile_num_chunks'][:, None, None], listed, -1)
  moved = (pair_row // chunk).reshape(tiles, -1, 1) == listed
  return jnp.sum(jnp.any(moved, axis=-1))


GATE_ACTIVATIONS = {'relu': nn.relu, 'silu': nn.silu}


class DroplessMoE(nn.Module):
  """Top-k routed gated experts without a capacity: [T, d] -> [T, d] f32.

  ``num_experts`` is the router's width (all the model's experts);
  ``experts_held`` = (first index, count) says which of them live here.
  ``__call__(u, routing)`` takes the ROUTING from the caller, a pair
  (expert index [T, k] int32, weight [T, k] f32), because where the router
  reads, how it scores, what it selects by and what it weighs by are the
  block's business (``route_top_k``, ``route_sigmoid_bias``). Experts are
  gated: ``(act(u Wg) * (u Wu)) Wd``, no bias, ``act`` the
  ``gate_activation``: ``'relu'`` (ReGLU) or ``'silu'`` (SwiGLU). Weights
  are f32 parameters, products run in ``dtype``.

  Returns ``(y, stats)``; ``stats`` are scalars of this call:
  ``pairs_held`` (pairs computed here), ``load_max_over_mean`` (largest
  expert's pairs over the mean, over the experts held), ``dropped_pairs``
  (pairs of a held expert that were not computed: 0 by construction, the
  buffer holds any routing; counted from the layout, as the pairs whose row
  lies in no chunk the kernels move for their token's tile, so that a fault
  in it would show) and ``rows_in_use`` (tiles in use x ``block_rows``: over
  ``buffer_rows`` it is the share of the buffer that is walked).
  """

  num_experts: int
  experts_held: Tuple[int, int]
  expert_dim: int
  gate_activation: str = 'relu'
  block_rows: int = 256
  down_init_std: float = 0.02   # of w_down, which writes into the residual
  dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, u: jnp.ndarray, routing):
    d = u.shape[1]
    first, held = self.experts_held
    if not 0 <= first <= first + held <= self.num_experts or held < 1:
      raise ValueError('experts_held {} is no range of the {} experts.'
                       .format(self.experts_held, self.num_experts))
    if self.gate_activation not in GATE_ACTIVATIONS:
      raise ValueError('gate_activation {!r} is none of {}.'.format(
          self.gate_activation, sorted(GATE_ACTIVATIONS)))
    activation = GATE_ACTIVATIONS[self.gate_activation]
    init = nn.initializers.normal(0.02)
    w_gate = self.param('w_gate', init, (held, d, self.expert_dim),
                        jnp.float32)
    w_up = self.param('w_up', init, (held, d, self.expert_dim), jnp.float32)
    w_down = self.param('w_down', nn.initializers.normal(self.down_init_std),
                        (held, self.expert_dim, d), jnp.float32)

    expert_index, weight = routing
    with jax.named_scope('moe_group'):
      layout = group_pairs(expert_index, first, held, self.block_rows)
      del layout['row_pair']   # the inverse map: no kernel reads it
      rows = dispatch_rows(u.astype(self.dtype), layout, self.block_rows)
    with jax.named_scope('moe_experts'):
      product = functools.partial(
          gmm_lib.grouped_matmul, tile_group=layout['tile_group'],
          num_tiles=layout['num_tiles'], block_m=self.block_rows)
      gate_up = product(rows, jnp.concatenate(
          [w_gate.astype(self.dtype), w_up.astype(self.dtype)], axis=-1))
      hidden = (activation(gate_up[:, :self.expert_dim]) *
                gate_up[:, self.expert_dim:])
      out_rows = product(hidden, w_down.astype(self.dtype))
    with jax.named_scope('moe_combine'):
      y = combine_rows(out_rows, weight, layout)

    counts = layout['counts'].astype(jnp.float32)
    pairs_held = counts.sum()
    stats = {
        'pairs_held': pairs_held,
        'load_max_over_mean': counts.max() / jnp.maximum(counts.mean(), 1.0),
        'dropped_pairs': pairs_held - _pairs_moved(
            layout, self.block_rows).astype(jnp.float32),
        'rows_in_use': (layout['num_tiles'][0] *
                        self.block_rows).astype(jnp.float32),
    }
    return y, stats
