"""The kernels that move rows between token order and expert order
(parallel/grouped_matmul.py: ``moe_take_rows``, ``moe_sum_rows``), in the
Pallas interpreter at tiny sizes, held to the plain gathers the dropless layer
used before them: ``table.at[i].get(mode='fill')`` over the whole buffer and
the sum of one such gather a choice."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.parallel import grouped_matmul as gmm_lib

EXPERTS, TOP_K, BLOCK, WIDTH = 8, 3, 8, 16


def _take_rows(table, index):
  """table[index], zeros where index == len(table)."""
  return table.at[index].get(mode='fill', fill_value=0)


def plain_dispatch(x, layout):
  row_pair = layout['row_pair']
  tokens, k = layout['pair_row'].shape
  return _take_rows(x, jnp.where(row_pair < tokens * k, row_pair // k, tokens))


def plain_combine(rows, weight, layout):
  pair_row = layout['pair_row']
  return sum(weight[:, j, None] *
             _take_rows(rows, pair_row[:, j]).astype(jnp.float32)
             for j in range(pair_row.shape[1]))


def _random_choices(tokens, seed, experts=EXPERTS):
  return jnp.stack([
      jax.random.permutation(jax.random.PRNGKey(seed * 1000 + t),
                             experts)[:TOP_K] for t in range(tokens)])


def _routing(case):
  """(expert index [T, k], first, held) of a named routing."""
  if case == 'no tile in use':          # every choice is an absent expert
    return jnp.tile(jnp.array([[0, 1, 2]]), (40, 1)), 4, 4
  if case == 'one tile in use':         # five pairs in all, one expert
    index = jnp.tile(jnp.array([[0, 1, 2]]), (40, 1))
    return index.at[3:8, 1].set(6), 4, 4
  if case == 'some tiles in use':
    return _random_choices(40, 1), 2, 4
  if case == 'every pair held':         # the worst routing: all T x k rows
    return jnp.argsort(jax.random.uniform(jax.random.PRNGKey(5), (64, 3)),
                       axis=1), 0, 3
  if case == 'a token with no pair, one with all, an expert with none':
    index = jnp.array([0, 1, 2, 3, 4, 6, 7])[      # expert 5: nobody's
        _random_choices(40, 3, experts=7)]
    index = index.at[0].set(jnp.array([0, 1, 7]))  # token 0: none of 2..5
    index = index.at[1].set(jnp.array([2, 3, 4]))  # token 1: all three
    return index, 2, 4
  if case == 'two tiles of tokens share a chunk':  # 16 tokens a tile, 1 expert
    return _random_choices(48, 4), 3, 1
  raise ValueError(case)


CASES = ['no tile in use', 'one tile in use', 'some tiles in use',
         'every pair held',
         'a token with no pair, one with all, an expert with none',
         'two tiles of tokens share a chunk']


def _distinct(index):
  """top-k never picks an expert twice for a token; nor may a case."""
  ordered = np.sort(np.asarray(index), axis=1)
  return bool(np.all(ordered[:, 1:] != ordered[:, :-1]))


@pytest.fixture(scope='module', params=CASES)
def routed(request):
  index, first, held = _routing(request.param)
  assert _distinct(index), request.param
  tokens = index.shape[0]
  layout = moe_lib.group_pairs(index.astype(jnp.int32), first, held, BLOCK)
  rows = layout['row_pair'].shape[0]
  owned = np.asarray(layout['row_pair']) < tokens * TOP_K
  in_use = np.arange(rows) < int(layout['num_tiles'][0]) * BLOCK
  keys = jax.random.split(jax.random.PRNGKey(len(request.param)), 4)
  return dict(
      case=request.param, layout=layout, owned=owned[:, None],
      in_use=in_use[:, None],
      kernel_layout={k: v for k, v in layout.items() if k != 'row_pair'},
      x=jax.random.normal(keys[0], (tokens, WIDTH)),
      rows=jax.random.normal(keys[1], (rows, WIDTH)),
      weight=jax.random.uniform(keys[2], (tokens, TOP_K)),
      d_out=jax.random.normal(keys[3], (tokens, WIDTH)))


class TestKernelsAgainstThePlainGathers:

  def test_the_cases_are_what_their_names_say(self, routed):
    tiles = int(routed['layout']['num_tiles'][0])
    counts = np.asarray(routed['layout']['counts'])
    held_of_token = np.sum(
        np.asarray(routed['layout']['pair_row']) < len(routed['owned']), 1)
    expected = {
        'no tile in use': tiles == 0,
        'one tile in use': tiles == 1,
        'some tiles in use': 1 < tiles < len(routed['owned']) // BLOCK,
        'every pair held': np.all(held_of_token == TOP_K),
        'a token with no pair, one with all, an expert with none':
            held_of_token[0] == 0 and held_of_token[1] == TOP_K and
            counts[3] == 0,
        'two tiles of tokens share a chunk': np.any(
            np.cumsum(np.sum(np.asarray(
                routed['layout']['pair_row']).reshape(3, -1) <
                             len(routed['owned']), axis=1))[:2] % BLOCK != 0),
    }
    assert expected[routed['case']]

  @pytest.mark.parametrize('weighted', [False, True], ids=['plain', 'weighted'])
  def test_take_rows_fills_the_tiles_in_use(self, routed, weighted):
    weight = routed['weight'] if weighted else None
    got = gmm_lib.moe_take_rows(routed['x'], weight, routed['kernel_layout'],
                                block_rows=BLOCK)
    want = plain_dispatch(routed['x'], routed['layout'])
    if weighted:
      want = want * _take_rows(routed['weight'].reshape(-1),
                               routed['layout']['row_pair'])[:, None]
    # Padding inside a tile in use is zeros, as in ``want``; what lies past
    # the tiles in use is nobody's to read.
    np.testing.assert_allclose(np.where(routed['in_use'], got, 0),
                               np.where(routed['in_use'], want, 0), atol=1e-6)

  @pytest.mark.parametrize('weighted', [False, True], ids=['plain', 'weighted'])
  def test_sum_rows_reads_no_row_its_tokens_do_not_own(self, routed, weighted):
    weight = routed['weight'] if weighted else jnp.ones_like(routed['weight'])
    poisoned = jnp.where(routed['owned'], routed['rows'], jnp.nan)
    got = gmm_lib.moe_sum_rows(poisoned, weight if weighted else None,
                               routed['kernel_layout'])
    want = plain_combine(routed['rows'], weight, routed['layout'])
    np.testing.assert_allclose(got, want, atol=1e-5)

  def test_the_row_dot_variant_gives_the_weights_gradient(self, routed):
    poisoned = jnp.where(routed['owned'], routed['rows'], jnp.nan)
    got = gmm_lib.moe_sum_rows(poisoned, None, routed['kernel_layout'],
                               d_out=routed['d_out'])
    want = jax.grad(lambda w: jnp.sum(routed['d_out'] * plain_combine(
        routed['rows'], w, routed['layout'])))(routed['weight'])
    np.testing.assert_allclose(got, want, atol=2e-5)

  def test_gradients_of_dispatch_and_combine(self, routed):
    layout, kernel_layout = routed['layout'], routed['kernel_layout']
    in_use, owned = routed['in_use'], routed['owned']

    def kernels(x, rows, weight):
      sent = moe_lib.dispatch_rows(x, kernel_layout, BLOCK)
      back = moe_lib.combine_rows(jnp.where(owned, rows, jnp.nan), weight,
                                  kernel_layout)
      return jnp.sum(jnp.where(in_use, jnp.sin(sent), 0)) + jnp.sum(
          jnp.sin(back))

    def plain(x, rows, weight):
      sent = plain_dispatch(x, layout)
      back = plain_combine(rows, weight, layout)
      return jnp.sum(jnp.where(in_use, jnp.sin(sent), 0)) + jnp.sum(
          jnp.sin(back))

    args = routed['x'], routed['rows'], routed['weight']
    np.testing.assert_allclose(kernels(*args), plain(*args), rtol=1e-5)
    got = jax.grad(kernels, (0, 1, 2))(*args)
    want = jax.grad(plain, (0, 1, 2))(*args)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    # A row no token owns has no gradient to receive; the kernel writes zeros
    # into the padding of a tile in use and leaves the rest alone.
    np.testing.assert_allclose(np.where(owned, got[1], 0), want[1], atol=1e-5)
    np.testing.assert_allclose(got[2], want[2], atol=2e-5)


def _dense_experts(params, u, router_logits, first, held, top_k):
  values, index = jax.lax.top_k(router_logits, top_k)
  weight = jax.nn.softmax(values, -1)
  y = 0
  for e in range(held):
    gate = ((index == first + e) * weight).sum(-1)
    y = y + gate[:, None] * (
        (jax.nn.relu(u @ params['w_gate'][e]) * (u @ params['w_up'][e]))
        @ params['w_down'][e])
  return y


class TestTheLayerReadsNothingItDidNotWrite:
  """Every buffer of the layer poisoned where nothing may read it: NaN in
  the rows past the tiles in use of everything a kernel writes, and in every
  row of the experts' output and of its gradient that no pair owns."""

  @pytest.fixture
  def poisoned(self, monkeypatch):
    take, product, total = (gmm_lib.moe_take_rows,
                            gmm_lib._grouped_matmul_call, gmm_lib.moe_sum_rows)
    seen = []

    def past_the_tiles_in_use(out, num_tiles, block):
      seen.append('past')
      return jnp.where(
          (jnp.arange(out.shape[0]) < num_tiles[0] * block)[:, None], out,
          jnp.nan)

    def poisoned_take(table, weight, layout, *, block_rows, **kwargs):
      out = take(table, weight, layout, block_rows=block_rows, **kwargs)
      return past_the_tiles_in_use(out, layout['num_tiles'], block_rows)

    def poisoned_product(lhs, rhs, tile_group, num_tiles, *, block_m, **kwargs):
      out = product(lhs, rhs, tile_group, num_tiles, block_m=block_m, **kwargs)
      return past_the_tiles_in_use(out, num_tiles, block_m)

    def poisoned_sum(rows, weight, layout, **kwargs):
      seen.append('unowned')
      pair_row = layout['pair_row'].reshape(-1)
      owned = jnp.zeros((rows.shape[0] + 1,), bool).at[pair_row].set(True)
      return total(jnp.where(owned[:-1, None], rows, jnp.nan), weight, layout,
                   **kwargs)

    monkeypatch.setattr(gmm_lib, 'moe_take_rows', poisoned_take)
    monkeypatch.setattr(gmm_lib, '_grouped_matmul_call', poisoned_product)
    monkeypatch.setattr(gmm_lib, 'moe_sum_rows', poisoned_sum)
    return seen

  @pytest.mark.parametrize('first, held', [(0, 8), (2, 4), (5, 1)])
  def test_outputs_and_gradients_are_finite_and_the_dense_loops(
      self, poisoned, first, held):
    u = jax.random.normal(jax.random.PRNGKey(4), (40, 16))
    logits = jax.random.normal(jax.random.PRNGKey(5), (40, 8))
    layer = moe_lib.DroplessMoE(num_experts=8, experts_held=(first, held),
                                expert_dim=12, block_rows=8)
    route = lambda logits: moe_lib.route_top_k(logits, 3)
    params = jax.tree.map(
        lambda x: 20 * x,
        layer.init(jax.random.PRNGKey(6), u, route(logits))['params'])
    del poisoned[:]
    y, stats = layer.apply({'params': params}, u, route(logits))
    assert poisoned.count('past') == 3 and poisoned.count('unowned') == 1
    np.testing.assert_allclose(
        y, _dense_experts(params, u, logits, first, held, 3), atol=1e-5)
    assert float(stats['dropped_pairs']) == 0
    tiles = int(np.sum(-(-np.asarray(moe_lib.group_pairs(
        moe_lib.route_top_k(logits, 3)[0], first, held, 8)['counts']) // 8)))
    assert float(stats['rows_in_use']) == tiles * 8
    assert tiles * 8 < moe_lib.buffer_rows(40, 3, held, 8)
    del poisoned[:]
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(
        layer.apply({'params': a[0]}, a[1], route(a[2]))[0])), (0, 1, 2))(
            params, u, logits)
    # Forward: one take, two products, one sum. Backward: the combine's take
    # and row-dot, two products to the left (the two to the weights have no
    # rows to poison), the dispatch's sum.
    assert poisoned.count('past') == 3 + 3 and poisoned.count('unowned') == 3
    wanted = jax.grad(lambda *a: jnp.sum(jnp.sin(
        _dense_experts(a[0], a[1], a[2], first, held, 3))), (0, 1, 2))(
            params, u, logits)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(wanted)):
      assert np.all(np.isfinite(g))
      np.testing.assert_allclose(g, w, atol=2e-5)


# The Pallas interpreter the tests above run in copies at ``start`` and makes
# nothing of a ``wait``. Mosaic's own interpreter keeps the semaphores: a
# wait for a copy that was never started hangs there as it would on the chip
# (hence a process of its own and a time limit), rows nobody wrote read NaN,
# a read outside an array raises, and a buffer rewritten while a copy still
# reads it is a race.
_UNDER_MOSAICS_INTERPRETER = """
import json
import jax, jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax._src.pallas.mosaic.interpret import interpret_pallas_call
from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.parallel import grouped_matmul as gmm_lib

take = lambda table, index: table.at[index].get(mode='fill', fill_value=0)
mosaic = pltpu.InterpretParams(detect_races=True, uninitialized_memory='nan')
worst = {}
for tokens, first, held, block in [(40, 2, 4, 8), (48, 3, 1, 8), (40, 0, 8, 16)]:
  index = jnp.stack([jax.random.permutation(jax.random.PRNGKey(t), 8)[:3]
                     for t in range(tokens)]).astype(jnp.int32)
  layout = moe_lib.group_pairs(index, first, held, block)
  row_pair, pair_row = layout.pop('row_pair'), layout['pair_row']
  rows = row_pair.shape[0]
  owned = (row_pair < tokens * 3)[:, None]
  in_use = (jnp.arange(rows) < layout['num_tiles'][0] * block)[:, None]
  keys = jax.random.split(jax.random.PRNGKey(tokens), 4)
  x = jax.random.normal(keys[0], (tokens, 16))
  buffer = jnp.where(owned, jax.random.normal(keys[1], (rows, 16)), jnp.nan)
  weight = jax.random.uniform(keys[2], (tokens, 3))
  d_out = jax.random.normal(keys[3], (tokens, 16))
  sent = take(x, jnp.where(owned[:, 0], row_pair // 3, tokens))
  row_weight = take(weight.reshape(-1), row_pair)[:, None]
  gathered = [take(jnp.nan_to_num(buffer), pair_row[:, j]) for j in range(3)]
  pairs = {
      'take': (gmm_lib.moe_take_rows(x, None, layout, block_rows=block,
                                     interpret=mosaic), sent, in_use),
      'take weighted': (gmm_lib.moe_take_rows(
          x, weight, layout, block_rows=block, interpret=mosaic),
                        row_weight * sent, in_use),
      'sum': (gmm_lib.moe_sum_rows(buffer, None, layout, interpret=mosaic),
              sum(gathered), True),
      'sum weighted': (gmm_lib.moe_sum_rows(buffer, weight, layout,
                                            interpret=mosaic),
                       sum(weight[:, j, None] * gathered[j] for j in range(3)),
                       True),
      'row dot': (gmm_lib.moe_sum_rows(buffer, None, layout, d_out=d_out,
                                       interpret=mosaic),
                  jnp.stack([jnp.sum(d_out * g, -1) for g in gathered], 1),
                  True),
  }
  for name, (got, want, where) in pairs.items():
    error = float(jnp.max(jnp.abs(jnp.where(where, got - want, 0))))
    worst[name] = max(worst.get(name, 0.0), error)
  # Past the tiles in use the buffer was left as it was: never written.
  unwritten = gmm_lib.moe_take_rows(x, None, layout, block_rows=block,
                                    interpret=mosaic)
  worst['written past the tiles in use'] = max(
      worst.get('written past the tiles in use', 0.0),
      float(jnp.sum(jnp.where(in_use, 0, ~jnp.isnan(unwritten)))))
print(json.dumps({'worst': worst,
                  'races': bool(interpret_pallas_call.races.races_found)}))
"""


def test_the_copies_balance_and_do_not_race_under_mosaics_interpreter():
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  run = subprocess.run(
      [sys.executable, '-c', _UNDER_MOSAICS_INTERPRETER], cwd=root,
      env=dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=root),
      capture_output=True, text=True, timeout=600)
  assert run.returncode == 0, run.stderr[-3000:]
  result = json.loads(run.stdout.strip().splitlines()[-1])
  assert not result['races']
  assert set(result['worst']) == {
      'take', 'take weighted', 'sum', 'sum weighted', 'row dot',
      'written past the tiles in use'}
  assert max(result['worst'].values()) <= 2e-5, result['worst']
