"""The SmallThinker-style backbone at a small size on the CPU, against its
independent reference (benchmark/harness/smallthinker_reference.py): loss and every
gradient leaf; the flash kernels with grouped-query heads and a window
against dense masked attention; the shares of an expert-parallel layer add
up to the uncut layer; the dropless layer under the most uneven routing."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.parallel import grouped_matmul as gmm_lib
from tensor2robot_tpu.parallel.flash_attention import flash_attention
from tensor2robot_tpu.research.smallthinker import SmallThinkerModel
from tensor2robot_tpu.research.smallthinker import smallthinker_model
from benchmark.harness import smallthinker_reference as reference

SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=8,
             moe_num_active_primary_experts=3, num_hidden_layers=4,
             sliding_window_size=8, vocab_rows=64, sequence_length=32,
             moe_block_rows=8, loss_block_tokens=16, device_type='cpu')


def _settings(experts_held=(0, 8), rope_layers=(False, True, True, True),
              window=8):
  return dict(num_heads=4, num_kv_heads=2, head_dim=16, top_k=3,
              experts_held=experts_held, window=window, rope_theta=1.5e6,
              eps=1e-6, window_layers=(False, True, True, True),
              rope_layers=rope_layers, query_block=16, head_block=16)


def _relative(got, want):
  return float(jnp.max(jnp.abs(got - want)) /
               (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.fixture(scope='module')
def small():
  model = SmallThinkerModel(experts_held=(2, 4), **SMALL)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
  state = model.create_train_state(jax.random.PRNGKey(1), {'tokens': tokens},
                                   None)

  def program(params):
    return model.loss_fn(params, state.model_state, {'tokens': tokens}, None,
                         ModeKeys.TRAIN, None)[0]

  # Three times the initial weights: at their initial size the layers move
  # the residual stream too little for a planted fault to show in the loss.
  return model, jax.tree.map(lambda x: 3 * x, state.params), tokens, program


class TestModelAgainstReference:

  def test_loss_and_every_gradient_leaf(self, small):
    _, params, tokens, program = small
    loss, grads = jax.value_and_grad(program)(params)
    want_loss, want = jax.value_and_grad(reference.loss)(
        params, tokens, _settings((2, 4)))
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == len(jax.tree.leaves(grads)) == 4 * 10 + 3
    for (path, want_leaf), got in zip(flat, jax.tree.leaves(grads)):
      assert _relative(got, want_leaf) <= 1e-5, jax.tree_util.keystr(path)

  @pytest.mark.parametrize('fault, settings', [
      ('window ignored', _settings((2, 4), window=None)),
      ('a full layer given rotary positions',
       _settings((2, 4), rope_layers=(True, True, True, True))),
      ('the held experts paired with their neighbours\' routing',
       _settings((3, 4))),
  ])
  def test_a_reference_with_a_fault_does_not_agree(self, small, fault,
                                                   settings):
    _, params, tokens, program = small
    if settings['window'] is None:
      settings = dict(settings, window_layers=(False,) * 4)
    want = float(reference.loss(params, tokens, settings))
    assert abs(float(program(params)) - want) > 1e-5 * want, fault

  def test_the_step_reports_the_gradient_norm_and_the_expert_stats(self,
                                                                   small):
    model, _, tokens, program = small
    state = model.create_train_state(jax.random.PRNGKey(1),
                                     {'tokens': tokens}, None)
    grads = jax.grad(program)(state.params)
    _, metrics = jax.jit(model.train_step)(state, {'tokens': tokens}, None,
                                           jax.random.PRNGKey(2))
    squares = {name: sum(float(jnp.sum(g * g)) for g in jax.tree.leaves(part))
               for name, part in grads.items()}
    assert float(metrics['grad_norm']) == pytest.approx(
        np.sqrt(sum(squares.values())), rel=1e-5)
    assert set(squares) == {'embedding', 'head', 'norm_final', 'block0',
                            'block1', 'block2', 'block3'}
    for name, value in squares.items():
      assert float(metrics['grad_group_norm/' + name]) == pytest.approx(
          np.sqrt(value), rel=1e-5), name
    assert float(metrics['moe/dropped_pairs']) == 0
    # 4 of 8 experts held, 3 of 8 chosen: 1.5 pairs a token a layer expected.
    assert 0.8 < float(metrics['moe/pairs_held']) / (4 * 64) < 2.2
    assert float(metrics['moe/expert_load_max_over_mean']) >= 1

  def test_other_models_compute_no_gradient_norm(self):
    from tensor2robot_tpu.models.abstract_model import AbstractT2RModel

    assert AbstractT2RModel.report_gradient_norm is False


FLASH_KERNELS = ('flash_attention_fwd', 'flash_attention_bwd_dkv',
                 'flash_attention_bwd_dq')


def _block_stack(block_cls, blocks, windowed, attention_mode='flash'):
  """(loss over parameters and input, parameters, input) of ``blocks``
  blocks of ``block_cls``, on the flash kernels unless told otherwise: a
  window layer with rotary positions, or a full layer with none."""
  x = jax.random.normal(jax.random.PRNGKey(10), (2, 32, 64))

  class Stack(nn.Module):

    @nn.compact
    def __call__(self, x):
      for i in range(blocks):
        x, _ = block_cls(
            num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
            experts_held=(2, 4), expert_dim=32, top_k=3,
            window=8 if windowed else None,
            rope_theta=1.5e6 if windowed else None,
            attention_mode=attention_mode,
            moe_block_rows=8, name='block{}'.format(i))(x)
      return jnp.sum(jnp.sin(x))

  stack = Stack()
  return stack.apply, stack.init(jax.random.PRNGKey(11), x), x


class TestTheBlockCheckpointKeepsWhatTheAttentionBackwardReads:
  """Pallas interpreter, tiny widths. ``nn.remat`` with no policy is the
  checkpoint the model had before: it ran the flash forward twice a block."""

  @pytest.mark.parametrize('blocks', [1, 2])
  @pytest.mark.parametrize('windowed', [False, True], ids=['full', 'window'])
  def test_one_forward_kernel_a_block_and_the_same_gradients(
      self, windowed, blocks, jaxpr_calls):
    results = {}
    for name, block_cls in [
        ('kept', smallthinker_model.CheckpointedBlock),
        ('policy-less', nn.remat(transformer_lib.MoEBlock))]:
      loss, params, x = _block_stack(block_cls, blocks, windowed)
      grad = jax.value_and_grad(loss, argnums=(0, 1))
      calls, _ = jaxpr_calls(grad, params, x)
      results[name] = dict(kernels=[calls[k] for k in FLASH_KERNELS],
                           products=calls['dot_general'],
                           grads=jax.tree.leaves(grad(params, x)))
    kept, before = results['kept'], results['policy-less']
    # One forward kernel a block; the backward is one kernel, which
    # carries the dq kernel's name (parallel/flash_attention.py).
    assert kept['kernels'] == [blocks, 0, blocks]
    assert before['kernels'] == [2 * blocks, 0, blocks]
    # q, k and v are kept too: their three projections are not run again.
    assert kept['products'] == before['products'] - 3 * blocks
    got, want = kept['grads'], before['grads']
    assert len(got) == len(want) == 1 + 10 * blocks + 1
    # The kept arrays are the ones the second forward produced: on the CPU
    # loss and every gradient leaf come out the same to the last bit.
    for g, w in zip(got, want):
      np.testing.assert_array_equal(g, w)

  def test_the_names_kept_are_the_ones_the_kernels_give(self, jaxpr_calls):
    loss, params, x = _block_stack(smallthinker_model.CheckpointedBlock, 1,
                                   True)
    # The policy is made from BACKWARD_READS; a tag renamed in the forward
    # rule alone fails here and does not silently bring the second forward
    # back.
    _, tags = jaxpr_calls(jax.grad(loss), params, x)
    assert set(tags) == set(transformer_lib.flash_lib.BACKWARD_READS)

  def test_the_dense_backend_carries_no_names(self, jaxpr_calls):
    loss, params, x = _block_stack(smallthinker_model.CheckpointedBlock, 1,
                                   True, attention_mode='xla')
    calls, tags = jaxpr_calls(jax.grad(loss), params, x)
    assert not tags and not any(calls[k] for k in FLASH_KERNELS)

  def test_the_model_checkpoints_its_blocks_with_that_policy(
      self, small, jaxpr_calls, monkeypatch):
    _, params, _, program = small
    # 'auto' picks the dense backend on the CPU at this length; the chip at
    # 8,192 tokens picks the flash kernels.
    monkeypatch.setattr(transformer_lib, 'resolve_attention_mode',
                        lambda mode, length: 'flash')
    calls, tags = jaxpr_calls(jax.grad(program), params)
    assert [calls[k] for k in FLASH_KERNELS] == [4, 0, 4]
    assert set(tags) == set(transformer_lib.flash_lib.BACKWARD_READS)


def _equations(jaxpr):
  """Every equation of a jaxpr, nested ones included, kernels' bodies not."""
  for eqn in jaxpr.eqns:
    yield eqn
    if eqn.primitive.name != 'pallas_call':
      for inner in jax.core.jaxprs_in_params(eqn.params):
        yield from _equations(inner)


class TestTheExpertLayerMovesOnlyTheRowsInUse:
  """The model's step at the tiny size, as a jaxpr: nothing runs."""

  def test_no_gather_over_the_whole_buffer_and_the_kernels_counted(
      self, small):
    _, params, _, program = small
    # 2 x 32 tokens, 3 choices, 4 of 8 experts held, tiles of 8 rows.
    rows = moe_lib.buffer_rows(64, 3, 4, 8)
    equations = list(
        _equations(jax.make_jaxpr(jax.grad(program))(params).jaxpr))
    gathers = [[v.aval.shape for v in (eqn.invars[0], eqn.outvars[0])]
               for eqn in equations if eqn.primitive.name == 'gather']
    assert gathers, 'the embedding is a gather'
    for shapes in gathers:
      assert not any(shape and shape[0] == rows for shape in shapes), shapes
    kernels = [eqn.params['name'] for eqn in equations
               if eqn.primitive.name == 'pallas_call']
    # A block: rows are taken for the forward pass, again when the block is
    # computed again for its backward pass, and for the combine's gradient;
    # summed for the combine (its second forward is dead: the block's output
    # is no residual), for the dispatch's gradient and, dotted with the
    # output's gradient in place of the sum, for the weights' gradient.
    assert kernels.count('moe_take_rows') == 4 * 3
    assert kernels.count('moe_sum_rows') == 4 * 3

  def test_the_step_reports_the_rows_in_use(self, small):
    model, _, tokens, _ = small
    state = model.create_train_state(jax.random.PRNGKey(1),
                                     {'tokens': tokens}, None)
    _, metrics = jax.jit(model.train_step)(state, {'tokens': tokens}, None,
                                           jax.random.PRNGKey(2))
    in_use = float(metrics['moe/rows_in_use'])
    # Whole tiles of 8 rows that hold the pairs, four layers: at least the
    # pairs, at most 7 rows of padding more for each of 4 experts a layer.
    assert in_use % 8 == 0
    pairs = float(metrics['moe/pairs_held'])
    assert pairs <= in_use <= pairs + 4 * 4 * 7
    assert in_use < 4 * moe_lib.buffer_rows(64, 3, 4, 8)


def _dense_attention(q, k, v, window):
  b, l, h, d = q.shape
  group = h // k.shape[2]
  k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
  scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(d)
  i, j = jnp.arange(l)[:, None], jnp.arange(l)[None]
  mask = j <= i
  if window is not None:
    mask = mask & (i - j < window)
  scores = jnp.where(mask, scores, -jnp.inf)
  return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1), v)


class TestFlashKernelsGroupedAndWindowed:
  """Interpret mode, L = 64: eight windows of 8, blocks of 16 and 8, so
  blocks are skipped on both sides of the band."""

  @pytest.fixture(scope='class')
  def qkv(self):
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    return tuple(jax.random.normal(key, (2, 64, heads, 16))
                 for key, heads in zip(keys, (4, 2, 2)))

  @pytest.mark.parametrize('window', [None, 8, 20])
  @pytest.mark.parametrize('kv_heads', [4, 2, 1])
  def test_forward_and_gradients_match_dense(self, qkv, window, kv_heads):
    q, k, v = qkv
    k, v = (jnp.tile(x, (1, 1, 2, 1))[:, :, :kv_heads] for x in (k, v))
    flash = functools.partial(
        flash_attention, causal=True, window=window, block_q=16, block_k=8,
        block_q_bwd=8, block_k_bwd=16)
    np.testing.assert_allclose(flash(q, k, v),
                               _dense_attention(q, k, v, window), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(
        _dense_attention(*a, window))), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
      np.testing.assert_allclose(g, w, atol=1e-5)

  def test_it_fails_if_the_window_is_ignored(self, qkv):
    q, k, v = qkv
    windowed = flash_attention(q, k, v, causal=True, window=8, block_q=16,
                               block_k=8)
    assert float(jnp.max(jnp.abs(
        windowed - _dense_attention(q, k, v, None)))) > 1e-2

  def test_the_dense_backend_has_the_same_window_and_groups(self, qkv):
    q, k, v = qkv
    np.testing.assert_allclose(
        transformer_lib.scaled_dot_attention(q, k, v, True, window=8),
        _dense_attention(q, k, v, 8), atol=2e-6)

  def test_a_window_needs_causal_and_heads_must_divide(self, qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match='causal'):
      flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match='multiple'):
      flash_attention(q[:, :, :3], k, v, causal=True)


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


def _route(logits):
  return moe_lib.route_top_k(logits, 3)


class TestDroplessLayer:

  @pytest.fixture(scope='class')
  def layer_inputs(self):
    u = jax.random.normal(jax.random.PRNGKey(4), (40, 16))
    logits = jax.random.normal(jax.random.PRNGKey(5), (40, 8))
    layer = moe_lib.DroplessMoE(num_experts=8, experts_held=(0, 8),
                                expert_dim=8, block_rows=8)
    params = jax.tree.map(
        lambda x: 20 * x,
        layer.init(jax.random.PRNGKey(6), u, _route(logits))['params'])
    return u, logits, params

  @pytest.mark.parametrize('first, held', [(0, 8), (2, 4), (6, 2), (3, 1)])
  def test_output_and_gradients_match_the_dense_loop(self, layer_inputs,
                                                     first, held):
    u, logits, params = layer_inputs
    params = jax.tree.map(lambda x: x[first:first + held], params)
    layer = moe_lib.DroplessMoE(num_experts=8, experts_held=(first, held),
                                expert_dim=8, block_rows=8)
    y, stats = layer.apply({'params': params}, u, _route(logits))
    want = _dense_experts(params, u, logits, first, held, 3)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert float(stats['dropped_pairs']) == 0
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(
        layer.apply({'params': a[0]}, a[1], _route(a[2]))[0])), (0, 1, 2))(
            params, u, logits)
    wanted = jax.grad(lambda *a: jnp.sum(jnp.sin(
        _dense_experts(a[0], a[1], a[2], first, held, 3))), (0, 1, 2))(
            params, u, logits)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(wanted)):
      np.testing.assert_allclose(g, w, atol=2e-5)

  def test_four_shares_of_two_experts_add_up_to_the_whole_layer(
      self, layer_inputs):
    u, logits, params = layer_inputs
    whole = _dense_experts(params, u, logits, 0, 8, 3)
    total = 0
    pairs = 0
    for first in (0, 2, 4, 6):
      share = jax.tree.map(lambda x: x[first:first + 2], params)
      y, stats = moe_lib.DroplessMoE(
          num_experts=8, experts_held=(first, 2), expert_dim=8,
          block_rows=8).apply({'params': share}, u, _route(logits))
      total = total + y
      pairs += float(stats['pairs_held'])
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert pairs == 40 * 3

  def test_every_token_picks_the_same_experts_and_none_is_dropped(
      self, layer_inputs):
    u, _, params = layer_inputs
    logits = jnp.tile(jnp.array([[5., 4, 3, 0, 0, 0, 0, 0]]), (40, 1))
    y, stats = moe_lib.DroplessMoE(
        num_experts=8, experts_held=(0, 8), expert_dim=8,
        block_rows=8).apply({'params': params}, u, _route(logits))
    np.testing.assert_allclose(
        y, _dense_experts(params, u, logits, 0, 8, 3), atol=1e-5)
    assert float(stats['pairs_held']) == 120
    assert float(stats['load_max_over_mean']) == pytest.approx(8 / 3)
    assert float(stats['dropped_pairs']) == 0
    # The capacity path at its default factor keeps 24 slots an expert and
    # drops the other 16 tokens of each of the three experts.
    assert moe_lib._capacity(3, 40, 1.25, 8) == 24

  def test_the_buffer_holds_any_routing(self):
    assert moe_lib.buffer_rows(40, 3, 8, 8) == 40 * 3 + 8 * 7 + 0
    assert moe_lib.buffer_rows(40, 6, 2, 8) % 8 == 0
    assert moe_lib.buffer_rows(40, 6, 2, 8) >= 40 * 2 + 2 * 7

  def test_experts_held_must_be_a_range_of_the_experts(self, layer_inputs):
    u, logits, _ = layer_inputs
    with pytest.raises(ValueError, match='experts_held'):
      moe_lib.DroplessMoE(num_experts=8, experts_held=(6, 4),
                          expert_dim=8).init(jax.random.PRNGKey(0), u,
                                             _route(logits))


class TestGroupedMatmulKernels:

  @pytest.mark.parametrize('tile_group, num_tiles', [
      ([0, 0, 1, 3, 3, 3], 6), ([0, 2, 2, 3, 3, 3], 3), ([1, 1, 1, 1], 0)])
  def test_products_and_their_gradients(self, tile_group, num_tiles):
    block, k, n, groups = 8, 16, 24, 4
    rows = len(tile_group) * block
    lhs = jax.random.normal(jax.random.PRNGKey(7), (rows, k))
    rhs = jax.random.normal(jax.random.PRNGKey(8), (groups, k, n))
    tiles = jnp.asarray(tile_group, jnp.int32)
    used = jnp.asarray([num_tiles], jnp.int32)
    in_use = (jnp.arange(rows) < num_tiles * block)[:, None]

    def kernel(lhs, rhs):
      out = gmm_lib.grouped_matmul(lhs, rhs, tiles, used, block_m=block)
      return jnp.where(in_use, out, 0)

    def dense(lhs, rhs):
      out = jnp.einsum('mk,mkn->mn', lhs, rhs[jnp.repeat(tiles, block)])
      return jnp.where(in_use, out, 0)

    np.testing.assert_allclose(kernel(lhs, rhs), dense(lhs, rhs), atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(kernel(*a))), (0, 1))(lhs, rhs)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(in_use, got[0], 0), want[0],
                               atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)

  def test_rows_must_be_whole_tiles(self):
    with pytest.raises(ValueError, match='whole number'):
      gmm_lib.grouped_matmul(jnp.zeros((12, 4)), jnp.zeros((1, 4, 4)),
                             jnp.zeros((1,), jnp.int32),
                             jnp.ones((1,), jnp.int32), block_m=8)

  def test_group_pairs_lays_every_held_pair_in_its_experts_tiles(self):
    index = jax.random.randint(jax.random.PRNGKey(9), (20, 3), 0, 8)
    layout = moe_lib.group_pairs(index, 2, 4, 8)
    row_pair = np.asarray(layout['row_pair'])
    pair_row = np.asarray(layout['pair_row']).reshape(-1)
    flat = np.asarray(index).reshape(-1)
    held = (flat >= 2) & (flat < 6)
    assert np.all(pair_row[~held] == len(row_pair))
    assert np.array_equal(row_pair[pair_row[held]], np.flatnonzero(held))
    assert np.sum(row_pair < 60) == held.sum() == int(layout['counts'].sum())
    tiles = np.asarray(layout['tile_group'])
    for row in pair_row[held]:
      assert tiles[row // 8] == flat[row_pair[row]] - 2
    assert int(layout['num_tiles'][0]) == int(
        np.sum(-(-np.asarray(layout['counts']) // 8)))
