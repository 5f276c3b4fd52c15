"""The SDAR-style block-diffusion backbone at a small size on the CPU, against
its independent reference (benchmark/harness/sdar_reference.py): loss and
every gradient leaf; the corruption as a pure function of a row and a key;
the flash kernels' block-diffusion mask against the dense mask, forward and
gradients; the tile-level predicate and the gauges it feeds; the step
watcher's event carries the step metrics the model names."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import get_registry, spans
from tensor2robot_tpu.research.sdar import SDARModel, corrupt, sequence_key
from tensor2robot_tpu.research.sdar import sdar_model
from benchmark.harness import sdar_reference as reference

flash_lib = importlib.import_module(
    'tensor2robot_tpu.parallel.flash_attention')

LENGTH = 48
SMALL = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, moe_intermediate_size=16, num_experts=8,
             num_experts_per_tok=3, num_hidden_layers=2, vocab_rows=64,
             sequence_length=LENGTH, block_length=4, moe_block_rows=8,
             loss_block_tokens=16, device_type='cpu')


def _settings(**changed):
  settings = dict(
      hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=16,
      num_experts=8, experts_held=(2, 4), top_k=3, rope_theta=1e6, eps=1e-6,
      vocab_rows=64, window_layers=(False, False), block_length=4,
      noise_eps=1e-3, mask_token_id=63, trainer_seed=0, query_block=16,
      head_block=16, mask='block_diffusion', qk_norm=True, gate='silu',
      loss_weight='1/t', loss_shift=0)
  settings.update(changed)
  return settings


def _relative(got, want):
  return float(jnp.max(jnp.abs(got - want)) /
               (jnp.max(jnp.abs(want)) + 1e-30))


def _brute_mask(length, block):
  """The four rules of the issue, one pair at a time."""
  mask = np.zeros((2 * length, 2 * length), bool)
  for i in range(2 * length):
    for j in range(2 * length):
      i_block, j_block = (i % length) // block, (j % length) // block
      if i < length and j < length:
        mask[i, j] = i_block == j_block
      elif i < length:
        mask[i, j] = j_block < i_block
      elif j >= length:
        mask[i, j] = j_block <= i_block
  return mask


@pytest.fixture(scope='module')
def small():
  model = SDARModel(experts_held=(2, 4), **SMALL)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (2, LENGTH), 0, 64)
  state = model.create_train_state(jax.random.PRNGKey(1), {'tokens': tokens},
                                   None)
  rng = reference.first_step_rng(_settings())

  def program(params):
    return model.loss_fn(params, state.model_state, {'tokens': tokens}, None,
                         ModeKeys.TRAIN, rng)[0]

  # Larger than the initial weights: at their initial size the layers move
  # the residual stream too little for a planted fault to show in the loss.
  params = jax.tree.map(
      lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(5), x.shape),
      state.params)
  return model, params, tokens, program


class TestModelAgainstReference:

  def test_loss_and_every_gradient_leaf(self, small):
    _, params, tokens, program = small
    with jax.default_matmul_precision('highest'):
      loss, grads = jax.value_and_grad(program)(params)
    want, want_grads = jax.value_and_grad(reference.loss)(
        params, tokens, _settings())
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(want_grads):
      assert _relative(got[path], leaf) < 1e-4, jax.tree_util.keystr(path)
    for group in want_grads:
      norm = lambda tree: float(jnp.sqrt(sum(
          jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(tree))))
      assert abs(norm(grads[group]) - norm(want_grads[group])) <= \
          1e-5 * norm(want_grads[group]), group

  @pytest.mark.parametrize('fault', [
      dict(mask='causal'), dict(mask='clean_token_causal'),
      dict(qk_norm=False), dict(gate='relu'), dict(loss_weight='1'),
      dict(loss_shift=1), dict(experts_held=(3, 4)), dict(block_length=2),
      dict(mask_token_id=62), dict(trainer_seed=1)],
                           ids=lambda fault: '-'.join(
                               '{}={}'.format(*item) for item in fault.items()))
  def test_a_reference_with_a_fault_does_not_agree(self, small, fault):
    _, params, tokens, program = small
    with jax.default_matmul_precision('highest'):
      loss = float(program(params))
    wrong = float(reference.loss(params, tokens, _settings(**fault)))
    assert abs(loss - wrong) > 1e-4 * abs(loss), (loss, wrong)

  def test_the_step_reports_its_norms_and_its_counters(self, small):
    model, params, tokens, _ = small
    state = model.create_train_state(jax.random.PRNGKey(1),
                                     {'tokens': tokens}, None)
    _, metrics = jax.jit(model.train_step)(
        state.replace(params=params), {'tokens': tokens}, None,
        jax.random.PRNGKey(3))
    assert set(sdar_model.STEP_METRICS) <= set(metrics)
    assert {'grad_norm', 'grad_group_norm/block0', 'grad_group_norm/block1',
            'grad_group_norm/embedding', 'grad_group_norm/head'} <= set(
                metrics)
    assert float(metrics['moe/dropped_pairs']) == 0
    # 2 sequences x 2 x 48 positions x 2 layers, 3 of 8 chosen, 4 held.
    assert 0.5 < float(metrics['moe/pairs_held']) / (2 * 96 * 2) < 2.5
    assert 0 < float(metrics['diffusion/masked_positions']) < 2 * LENGTH
    assert 1e-3 <= float(metrics['diffusion/mean_noise_level']) <= 1
    assert set(model.traced_step_metrics) <= set(metrics)

  def test_the_blocks_are_checkpointed_with_the_flash_policy(self, small):
    assert sdar_model.CheckpointedBlock.__name__.lower().count('moeblock')
    assert not hasattr(transformer_lib, 'RouterFirstMoEBlock')

  def test_prediction_gives_the_last_blocks_logits(self, small):
    model, params, tokens, _ = small
    outputs, _ = model.inference_network_fn(
        {'params': params}, {'tokens': tokens}, None, ModeKeys.PREDICT, None)
    assert outputs['block_logits'].shape == (2, 4, 64)


class TestCorruption:

  def test_it_is_a_pure_function_of_the_row_and_the_key(self):
    row = jnp.arange(32, dtype=jnp.int32) % 7
    first = corrupt(row, jax.random.PRNGKey(4), 4, 1e-3, 63)
    again = corrupt(row, jax.random.PRNGKey(4), 4, 1e-3, 63)
    other = corrupt(row, jax.random.PRNGKey(5), 4, 1e-3, 63)
    for a, b in zip(first, again):
      np.testing.assert_array_equal(a, b)
    assert not np.array_equal(first[1], other[1])
    noised, level, masked = first
    assert level.shape == (8,) and masked.shape == (32,)
    assert float(level.min()) >= 1e-3 and float(level.max()) <= 1
    np.testing.assert_array_equal(noised, np.where(masked, 63, row))

  def test_what_is_masked_is_the_draw_not_the_id(self):
    row = jnp.full((64,), 63, jnp.int32)   # every id IS the mask id
    noised, _, masked = corrupt(row, jax.random.PRNGKey(0), 4, 1e-3, 63)
    np.testing.assert_array_equal(noised, row)
    assert 0 < int(masked.sum()) < 64

  def test_the_share_masked_follows_the_blocks_level(self):
    row = jnp.zeros((4096,), jnp.int32)
    _, level, masked = corrupt(row, jax.random.PRNGKey(2), 1024, 1e-3, 63)
    share = np.asarray(masked).reshape(4, 1024).mean(axis=1)
    np.testing.assert_allclose(share, np.asarray(level), atol=0.06)

  def test_the_noise_follows_the_sequence_not_its_place_in_the_batch(self):
    model = SDARModel(experts_held=(2, 4), **SMALL)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (3, LENGTH), 0, 64)
    rng = jax.random.PRNGKey(9)
    whole = model.corrupted({'tokens': tokens}, rng)
    turned = model.corrupted({'tokens': tokens[::-1]}, rng)
    alone = model.corrupted({'tokens': tokens[1:2]}, rng)
    for name in ('noised_tokens', 'noise_level', 'masked'):
      np.testing.assert_array_equal(whole[name], turned[name][::-1])
      np.testing.assert_array_equal(whole[name][1:2], alone[name])
    later = model.corrupted({'tokens': tokens}, jax.random.PRNGKey(10))
    assert not np.array_equal(whole['noise_level'], later['noise_level'])

  def test_the_reference_draws_the_same_noise_from_its_settings(self):
    settings = _settings()
    row = jax.random.randint(jax.random.PRNGKey(3), (LENGTH,), 0, 64)
    want = corrupt(row, sequence_key(reference.first_step_rng(settings), row),
                   4, 1e-3, 63)
    for a, b in zip(reference.corruption(row, settings), want):
      np.testing.assert_array_equal(a, b)

  def test_the_reference_follows_the_trainers_chain_of_keys(self):
    """``first_step_rng`` is what ``Trainer``'s step hands ``loss_fn`` at
    step 0: fold the step in, the second half of one split, the first half
    of the next (trainer/train_eval.py, models/abstract_model.py)."""
    from tensor2robot_tpu.trainer.train_eval import Trainer
    import inspect

    assert inspect.signature(Trainer.__init__).parameters['seed'].default == \
        _settings()['trainer_seed']
    base = jax.random.PRNGKey(0 + 1)
    _, step_rng = jax.random.split(jax.random.fold_in(base, 0))
    np.testing.assert_array_equal(
        jax.random.split(step_rng)[0], reference.first_step_rng(_settings()))


class TestTheMaskInTheFlashKernels:

  @pytest.mark.parametrize('length, block', [(24, 4), (16, 1), (32, 32),
                                             (24, 3)])
  def test_the_mask_is_the_four_rules(self, length, block):
    want = _brute_mask(length, block)
    np.testing.assert_array_equal(
        flash_lib.block_diffusion_mask(length, block), want)
    assert flash_lib.mask_pairs(2 * length, 2 * length, False, None,
                                (length, block)) == want.sum()
    rows = jnp.arange(2 * length)
    np.testing.assert_array_equal(
        reference.allowed(rows, length, _settings(block_length=block)), want)

  @pytest.mark.parametrize('length, block, block_q, block_k', [
      (24, 4, 8, 8), (24, 4, 16, 8), (24, 4, 8, 16), (24, 4, 16, 16),
      (32, 1, 8, 16), (32, 32, 16, 8), (24, 3, 8, 8), (40, 4, 16, 16)])
  def test_the_tile_predicate_keeps_just_the_tiles_with_a_pair(
      self, length, block, block_q, block_k):
    mask = _brute_mask(length, block)
    n_q, n_k = 2 * length // block_q, 2 * length // block_k
    want = sum(mask[i * block_q:(i + 1) * block_q,
                    j * block_k:(j + 1) * block_k].any()
               for i in range(n_q) for j in range(n_k))
    assert flash_lib.tiles_computed(n_q, n_k, block_q, block_k, False, None,
                                    (length, block)) == want

  def test_at_the_cells_size_the_tiles_hold_a_quarter_more_than_the_mask(
      self):
    """L = 8192, B = 4: the forward kernel's 1024 x 1024 tiles and the
    backward kernels' 512 x 1024."""
    needed = flash_lib.mask_pairs(16384, 16384, False, None, (8192, 4))
    assert needed == 67141632
    forward = flash_lib.tiles_computed(16, 16, 1024, 1024, False, None,
                                       (8192, 4))
    backward = flash_lib.tiles_computed(32, 16, 512, 1024, False, None,
                                        (8192, 4))
    assert (forward, backward) == (80, 160)
    computed = forward * 1024 * 1024 + 2 * backward * 512 * 1024
    assert computed / (3 * needed) <= 1.25

  @pytest.mark.parametrize('length, block, blocks', [
      (24, 4, (16, 16, 16, 8)), (24, 1, (8, 16, 8, 8)),
      (32, 32, (16, 8, 8, 16)), (40, 4, (16, 16, 16, 16)),
      (24, 3, (16, 8, 8, 8))], ids=str)
  @pytest.mark.parametrize('kv_heads', [4, 2])
  def test_forward_and_gradients_match_the_dense_mask(self, length, block,
                                                      blocks, kv_heads):
    """Interpret mode; the tiles (16 rows) do not divide L = 24 or 40, so
    tiles straddle the border between the noised and the clean half."""
    key = jax.random.PRNGKey(length + block)
    q = jax.random.normal(key, (2, 2 * length, 4, 16))
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (2, 2 * length, kv_heads, 16)) for i in (1, 2))
    weight = jax.random.normal(jax.random.fold_in(key, 3), q.shape)
    block_q, block_k, block_q_bwd, block_k_bwd = blocks

    def kernels(q, k, v):
      return flash_lib.flash_attention(
          q, k, v, block_diffusion=(length, block), block_q=block_q,
          block_k=block_k, block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd)

    def dense(q, k, v):
      return transformer_lib.scaled_dot_attention(
          q, k, v, causal=False, block_diffusion=(length, block))

    assert _relative(kernels(q, k, v), dense(q, k, v)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * weight), (0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight), (0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
      assert _relative(a, b) < 1e-5

  def test_it_fails_if_the_mask_is_taken_as_causal(self):
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 48, 2, 16))
               for i in range(3))
    masked = flash_lib.flash_attention(q, k, v, block_diffusion=(24, 4))
    causal = flash_lib.flash_attention(q, k, v, causal=True)
    assert _relative(masked, causal) > 1e-2

  def test_the_mask_needs_two_halves_no_causal_and_no_window(self):
    q = jnp.zeros((1, 48, 2, 16))
    for kwargs in (dict(block_diffusion=(24, 4), causal=True),
                   dict(block_diffusion=(16, 4)),
                   dict(block_diffusion=(24, 5)),
                   dict(block_diffusion=(24, 4), causal=True, window=8)):
      with pytest.raises(ValueError):
        flash_lib.flash_attention(q, q, q, **kwargs)
    with pytest.raises(ValueError):
      transformer_lib.run_attention(q, q, q, mode='ring', causal=False,
                                    mesh=object(), block_diffusion=(24, 4))

  def test_a_traced_call_sets_the_pair_gauges(self):
    q = jnp.zeros((2, 48, 4, 16))
    k = jnp.zeros((2, 48, 2, 16))
    jax.grad(lambda q: jnp.sum(flash_lib.flash_attention(
        q, k, k, block_diffusion=(24, 4), block_q=16, block_k=16,
        block_q_bwd=16, block_k_bwd=8)))(q)
    registry = get_registry()
    mask = _brute_mask(24, 4)
    tiles = lambda bq, bk: sum(
        mask[i:i + bq, j:j + bk].any()
        for i in range(0, 48, bq) for j in range(0, 48, bk)) * bq * bk
    assert registry.gauge('attention/mask_pairs_needed').value == \
        8 * mask.sum()
    assert registry.gauge('attention/mask_pairs_computed').value == \
        8 * tiles(16, 16)
    assert registry.gauge('attention/mask_pairs_computed_bwd').value == \
        8 * tiles(16, 8)
    # ... of ONE backward kernel, and one is all the backward launches.
    assert registry.gauge('attention/backward_kernels').value == 1


class TestLayersTakePositionsAndNorms:

  def test_rotary_positions_at_given_positions(self):
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    np.testing.assert_array_equal(
        transformer_lib.rotary_positions(x, 1e4),
        transformer_lib.rotary_positions(x, 1e4, jnp.arange(8)))
    twice = jnp.concatenate([x[:, :4], x[:, :4]], axis=1)
    turned = transformer_lib.rotary_positions(twice, 1e4,
                                              jnp.tile(jnp.arange(4), 2))
    np.testing.assert_allclose(turned[:, :4], turned[:, 4:], atol=1e-6)

  def test_the_router_reads_what_the_block_is_told(self):
    kwargs = dict(num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
                  experts_held=(0, 8), expert_dim=16, top_k=2,
                  moe_block_rows=8, attention_mode='xla')
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    first = transformer_lib.MoEBlock(**kwargs)
    normed = transformer_lib.MoEBlock(router_reads='normed', **kwargs)
    params = first.init(jax.random.PRNGKey(1), x)
    assert jax.tree.structure(params) == jax.tree.structure(
        normed.init(jax.random.PRNGKey(1), x))
    a, _ = first.apply(params, x)
    b, _ = normed.apply(params, x)
    # Another routing: another (small, untrained) contribution of the
    # expert layer, and nothing else.
    assert 1e-6 < float(jnp.max(jnp.abs(a - b))) < 1e-2
    with pytest.raises(ValueError):
      transformer_lib.MoEBlock(router_reads='output', **kwargs).init(
          jax.random.PRNGKey(1), x)


class TestTheStepWatcherCarriesTheModelsMetrics:

  def test_the_event_holds_the_named_values(self):
    from tensor2robot_tpu.trainer.train_eval import _StepWatcher

    seen = max([r.id for r in spans.records()] or [0])
    watcher = _StepWatcher(('diffusion/masked_positions', 'absent'))
    try:
      for step in (1, 2):
        watcher.submit(step, {
            'loss': jnp.float32(1.0),
            'diffusion/masked_positions': jnp.float32(10 * step),
            'moe/pairs_held': jnp.float32(3.0)})
        deadline = 200
        while deadline and not [
            r for r in spans.records(seen)
            if r.name == 'train.step_done' and r.attrs['step'] == step]:
          import time
          time.sleep(0.01)
          deadline -= 1
    finally:
      watcher.stop()
    events = [r for r in spans.records(seen) if r.name == 'train.step_done']
    assert [e.attrs['diffusion/masked_positions'] for e in events] == [10.0,
                                                                       20.0]
    assert all(set(e.attrs) == {'step', 'steps_covered',
                                'diffusion/masked_positions'}
               for e in events)

  def test_a_model_that_names_none_gets_the_event_as_it_was(self):
    from tensor2robot_tpu.models.abstract_model import AbstractT2RModel
    from tensor2robot_tpu.research.smallthinker import SmallThinkerModel

    assert AbstractT2RModel.traced_step_metrics == ()
    assert SmallThinkerModel.traced_step_metrics == ()
    assert SDARModel.traced_step_metrics == ('moe/pairs_held',
                                             'diffusion/masked_positions')
