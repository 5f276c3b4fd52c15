"""The LFM2-style hybrid backbone at a small size on the CPU, against its
independent reference (benchmark/harness/lfm2_reference.py), with seeded
random weights INCLUDING a non-zero router bias: loss and every gradient
leaf; the short-convolution kernel pair on the interpreter against the
``jax.numpy`` oracle; the sigmoid router chooses by score + bias and weighs
by the score; the bias after one step is one application of the balancing
rule, rematerialisation or not; the four chips' shares of an expert layer
add up to the uncut layer; softmax routing handed to the re-cut expert layer
is the old path to the bit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.parallel import short_conv as conv_lib
from tensor2robot_tpu.research.lfm2 import LFM2Model, lfm2_model
from benchmark.harness import lfm2_reference as reference

LENGTH = 32
KINDS = ('conv', 'full_attention', 'conv', 'conv', 'conv')
SMALL = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=192, moe_intermediate_size=64, num_experts=8,
             num_experts_per_tok=3, num_hidden_layers=5, num_dense_layers=1,
             first_layer=1, vocab_rows=64, sequence_length=LENGTH,
             moe_block_rows=8, loss_block_tokens=16, device_type='cpu')


def _settings(**changed):
  settings = dict(
      hidden_size=128, num_heads=4, num_kv_heads=2, head_dim=32,
      dense_dim=192, expert_dim=64, num_experts=8, experts_held=(2, 4),
      top_k=3, layer_types=KINDS, num_dense_layers=1,
      window_layers=(False,) * 5, rope_theta=1e6, eps=1e-5, vocab_rows=64,
      query_block=16, head_block=16, taps='causal', c_gate=True,
      router='sigmoid', renormalise=True, qk_norm=True)
  settings.update(changed)
  return settings


def _relative(got, want):
  return float(jnp.max(jnp.abs(got - want)) /
               (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.fixture(scope='module')
def small():
  model = LFM2Model(experts_held=(2, 4), **SMALL)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (2, LENGTH), 0, 64)
  state = model.create_train_state(jax.random.PRNGKey(1), {'tokens': tokens},
                                   None)
  # A bias large enough to change who is chosen (sigmoid scores differ by
  # tenths), and weights larger than the initial ones: at their initial
  # size the layers move the stream too little for a fault to show.
  biased = jax.tree.map(
      lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(7), b.shape),
      state.model_state)
  params = jax.tree.map(
      lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(5), x.shape),
      state.params)
  bias_rows = [np.asarray(biased['router_state']['block{}'.format(i)]['bias'])
               for i in range(1, 5)]

  def program(params, model_state=biased):
    return model.loss_fn(params, model_state, {'tokens': tokens}, None,
                         ModeKeys.TRAIN, None)[0]

  return model, state.replace(params=params), tokens, program, bias_rows


class TestModelAgainstReference:

  def test_loss_and_every_gradient_leaf(self, small):
    _, state, tokens, program, bias_rows = small
    with jax.default_matmul_precision('highest'):
      loss, grads = jax.value_and_grad(program)(state.params)
    want, want_grads = jax.value_and_grad(reference.loss)(
        state.params, tokens, _settings(router_bias=bias_rows))
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(want_grads):
      assert _relative(got[path], leaf) < 1e-4, jax.tree_util.keystr(path)
    assert set(want_grads) == {'block0', 'block1', 'block2', 'block3',
                               'block4', 'embedding', 'norm_final'}

  @pytest.mark.parametrize('fault', [
      dict(taps='reversed'), dict(c_gate=False), dict(router='softmax'),
      dict(renormalise=False), dict(qk_norm=False), dict(router_bias=None),
      dict(experts_held=(3, 4)), dict(num_dense_layers=0),
      dict(layer_types=('conv', 'conv', 'full_attention', 'conv', 'conv'))],
                           ids=lambda fault: '-'.join(
                               '{}={}'.format(*item) for item in fault.items()))
  def test_a_reference_with_a_fault_does_not_agree(self, small, fault):
    _, state, tokens, program, bias_rows = small
    if 'num_dense_layers' in fault or 'layer_types' in fault:
      # Another layout reads other parameters: the tree has no such leaf.
      with pytest.raises(KeyError):
        reference.loss(state.params, tokens,
                       _settings(router_bias=bias_rows, **fault))
      return
    with jax.default_matmul_precision('highest'):
      loss = float(program(state.params))
    wrong = float(reference.loss(
        state.params, tokens, _settings(**dict(dict(router_bias=bias_rows),
                                               **fault))))
    assert abs(loss - wrong) > 1e-5 * abs(loss), (loss, wrong)

  def test_the_head_is_the_embedding(self, small):
    _, state, _, _, _ = small
    assert 'head' not in state.params
    assert state.params['embedding'].shape == (64, 128)

  def test_the_step_reports_its_norms_and_its_counters(self, small):
    model, state, tokens, _, _ = small
    _, metrics = jax.jit(model.train_step)(state, {'tokens': tokens}, None,
                                           jax.random.PRNGKey(3))
    assert set(lfm2_model.STEP_METRICS) <= set(metrics)
    assert {'grad_norm', 'grad_group_norm/block0', 'grad_group_norm/block4',
            'grad_group_norm/embedding'} <= set(metrics)
    assert float(metrics['moe/dropped_pairs']) == 0
    # 2 sequences x 32 tokens x 4 expert layers, 3 of 8 chosen, 4 held.
    assert 0.5 < float(metrics['moe/pairs_held']) / (2 * LENGTH * 4) < 2.5
    assert float(metrics['moe/chosen_load_max_over_mean']) >= 1
    assert model.traced_step_metrics == ('moe/chosen_load_max_over_mean',)

  def test_prediction_gives_the_last_logits(self, small):
    model, state, tokens, _, _ = small
    outputs, new_state = model.inference_network_fn(
        state.variables(), {'tokens': tokens}, None, ModeKeys.PREDICT, None)
    assert outputs['last_logits'].shape == (2, 64)
    assert new_state is None

  def test_only_the_published_form_is_built(self):
    for wrong in (dict(conv_L_cache=4), dict(conv_bias=True),
                  dict(use_expert_bias=False), dict(norm_topk_prob=False),
                  dict(routed_scaling_factor=2.5),
                  dict(layer_types=('conv', 'mamba') * 3)):
      with pytest.raises(ValueError):
        LFM2Model(**dict(SMALL, **wrong))
    assert lfm2_model.PUBLISHED_LAYER_TYPES.count('full_attention') == 6
    assert lfm2_model.PUBLISHED_LAYER_TYPES[1:6] == KINDS


class TestTheBiasIsStateTheOptimizerDoesNotOwn:

  def test_one_step_applies_the_rule_once(self, small):
    """Under ``nn.remat`` the backward pass computes each block again; the
    bias the step hands back is still ONE application of the rule to the
    bias it was given, and the step stays one executable."""
    model, state, tokens, _, _ = small
    assert 'bias' not in str(jax.tree_util.tree_structure(state.params))
    start = state.model_state['router_state']
    assert all(float(jnp.abs(leaf).max()) == 0
               for leaf in jax.tree.leaves(start))
    step = jax.jit(model.train_step)
    after, metrics = step(state, {'tokens': tokens}, None,
                          jax.random.PRNGKey(3))
    # The same forward pass with no gradient and no rematerialised run.
    _, plain = model.create_network().apply(
        state.variables(), {'tokens': tokens}, mode=ModeKeys.TRAIN,
        train=True, mutable=['router_state'])
    got = after.model_state['router_state']
    for name in ('block1', 'block2', 'block3', 'block4'):
      bias = np.asarray(got[name]['bias'])
      np.testing.assert_array_equal(
          bias, np.asarray(plain['router_state'][name]['bias']))
      np.testing.assert_allclose(np.abs(bias)[bias != 0], 1e-3, rtol=1e-6)
      assert np.abs(bias).max() == pytest.approx(1e-3)
    assert 'block0' not in got          # the dense layer has no router
    assert float(metrics['moe/router_bias_abs_mean']) == pytest.approx(
        np.mean([np.abs(np.asarray(got[n]['bias'])).mean() for n in got]))
    # A second step moves it again, by the same program.
    again, _ = step(after, {'tokens': tokens}, None, jax.random.PRNGKey(4))
    assert step._cache_size() == 1
    assert np.abs(np.asarray(
        again.model_state['router_state']['block1']['bias'])).max() <= 2e-3 + 1e-9

  def test_the_rule_by_hand_on_one_block(self):
    block = transformer_lib.MoEBlock(
        num_heads=4, num_kv_heads=2, head_dim=32, num_experts=8,
        experts_held=(0, 4), expert_dim=64, top_k=3, mixer='short_conv',
        router_reads='normed', router='sigmoid_bias', router_bias_rate=0.01,
        gate_activation='silu', moe_block_rows=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128))
    variables = block.init(jax.random.PRNGKey(1), x)
    assert float(jnp.abs(variables['router_state']['bias']).max()) == 0
    given = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    variables = {'params': variables['params'],
                 'router_state': {'bias': given}}
    (_, stats), new = block.apply(variables, x, mutable=['router_state'])
    # By hand: the logits of the normed post-mixer stream.
    p = variables['params']
    h = transformer_lib.RMSNorm(1e-6).apply({'params': p['norm_attn']}, x)
    x1 = x + transformer_lib.ShortConvolution().apply({'params': p['conv']}, h)
    u = transformer_lib.RMSNorm(1e-6).apply({'params': p['norm_moe']}, x1)
    logits = (u @ p['router']['kernel']).reshape(32, 8)
    index, _ = moe_lib.route_sigmoid_bias(logits, given, 3)
    counts = np.bincount(np.asarray(index).reshape(-1), minlength=8)
    want = np.asarray(given) + 0.01 * np.sign(counts.mean() - counts)
    np.testing.assert_allclose(np.asarray(new['router_state']['bias']), want,
                               rtol=0, atol=1e-7)
    assert float(stats['chosen_load_max_over_mean']) == pytest.approx(
        counts.max() / counts.mean())
    # Not mutable (evaluation, serving): the bias is read and stays.
    _, stats = block.apply(variables, x)
    assert float(stats['router_bias_abs_mean']) == pytest.approx(
        float(jnp.abs(given).mean()))


class TestTheSigmoidRouter:

  def test_it_chooses_by_score_plus_bias_and_weighs_by_score(self):
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, -3.0]])
    bias = jnp.asarray([-1.0, 0.0, 0.0, 1.0])
    index, weight = moe_lib.route_sigmoid_bias(logits, bias, 2)
    scores = 1 / (1 + np.exp(-np.asarray(logits)))
    # Row 0: s + b = [-0.12, 0.73, 0.5, 1.27]: experts 3 and 1, though
    # expert 0 has the largest score.
    assert sorted(np.asarray(index[0]).tolist()) == [1, 3]
    assert sorted(np.asarray(index[1]).tolist()) == [2, 3]
    for row in range(2):
      chosen = scores[row, np.asarray(index[row])]
      np.testing.assert_allclose(np.asarray(weight[row]),
                                 chosen / (chosen.sum() + 1e-6), rtol=1e-6)
    unbiased, _ = moe_lib.route_sigmoid_bias(logits, jnp.zeros(4), 2)
    assert sorted(np.asarray(unbiased[0]).tolist()) == [0, 1]

  def test_no_gradient_reaches_the_bias_and_the_weights_sum_to_one(self):
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    weigh = lambda l, b: jnp.sum(
        moe_lib.route_sigmoid_bias(l, b, 3)[1] ** 2)
    d_logits, d_bias = jax.grad(weigh, (0, 1))(logits, bias)
    assert float(jnp.abs(d_bias).max()) == 0
    assert float(jnp.abs(d_logits).max()) > 0
    # Renormalised, and by nothing else (routed_scaling_factor is 1).
    np.testing.assert_allclose(
        np.asarray(moe_lib.route_sigmoid_bias(logits, bias, 3)[1].sum(-1)),
        1.0, atol=1e-5)

  def test_the_reference_routes_alike(self):
    logits = jax.random.normal(jax.random.PRNGKey(3), (64, 8))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    index, weight = moe_lib.route_sigmoid_bias(logits, bias, 3)
    dense = np.zeros((64, 8), np.float32)
    np.put_along_axis(dense, np.asarray(index), np.asarray(weight), axis=1)
    want = reference.routing_weights(logits, bias, _settings())
    np.testing.assert_allclose(dense, np.asarray(want), rtol=1e-6, atol=1e-7)


class TestTheShortConvolutionKernels:
  """On the interpreter, against the ``jax.numpy`` oracle."""

  @pytest.mark.parametrize('batch,length,d', [
      (1, 16, 128),     # one tile: the sequence's start alone
      (2, 32, 128),     # two sequences: nothing crosses between them
      (3, 48, 256),     # tiles of 16 rows: two tile edges a sequence
      (2, 512, 128),    # tiles of 256 rows with their 16-row halos
  ])
  def test_forward_and_all_four_gradients(self, batch, length, d):
    keys = jax.random.split(jax.random.PRNGKey(length + d), 3)
    bcx = jax.random.normal(keys[0], (batch, length, 3 * d))
    taps = jax.random.normal(keys[1], (d, 3))
    dy = jax.random.normal(keys[2], (batch, length, d))
    oracle = lambda bcx, taps: conv_lib.short_conv(bcx, taps, mode='xla')
    kernels = lambda bcx, taps: conv_lib.short_conv(
        bcx, taps, mode='pallas', interpret=True)
    assert _relative(kernels(bcx, taps), oracle(bcx, taps)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * dy), (0, 1))(bcx, taps)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * dy), (0, 1))(bcx, taps)
    for chunk, name in enumerate(('dB', 'dC', 'dX')):
      piece = slice(chunk * d, (chunk + 1) * d)
      assert _relative(got[0][..., piece], want[0][..., piece]) < 1e-5, name
    assert _relative(got[1], want[1]) < 1e-5, 'd filter'
    assert got[1].shape == (d, 3)

  def test_the_oracle_is_the_three_taps_written_out(self):
    bcx = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 12))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    b, c, x = (np.asarray(bcx[..., i * 4:(i + 1) * 4]) for i in range(3))
    z = b * x
    want = np.zeros((2, 5, 4), np.float32)
    for t in range(5):
      for tap in range(3):
        if t - 2 + tap >= 0:
          want[:, t] += np.asarray(taps)[:, tap] * z[:, t - 2 + tap]
    np.testing.assert_allclose(
        np.asarray(conv_lib.short_conv_reference(bcx, taps)), c * want,
        rtol=1e-5, atol=1e-6)
    # The first token of the second sequence sees nothing of the first.
    moved = bcx.at[0].set(bcx[0] + 1.0)
    np.testing.assert_array_equal(
        np.asarray(conv_lib.short_conv_reference(moved, taps)[1]),
        np.asarray(conv_lib.short_conv_reference(bcx, taps)[1]))

  def test_bfloat16_goes_through_float32_inside(self):
    bcx = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 384)).astype(
        jnp.bfloat16)
    taps = jax.random.normal(jax.random.PRNGKey(1), (128, 3))
    got = conv_lib.short_conv(bcx, taps, mode='pallas', interpret=True)
    want = conv_lib.short_conv_reference(bcx, taps)
    assert got.dtype == jnp.bfloat16
    assert _relative(got.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2

  def test_shapes_the_kernels_do_not_take_go_the_plain_way(self):
    bcx = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 96))
    taps = jax.random.normal(jax.random.PRNGKey(1), (32, 3))
    assert not conv_lib.supported(10, 32)
    np.testing.assert_array_equal(
        np.asarray(conv_lib.short_conv(bcx, taps)),
        np.asarray(conv_lib.short_conv_reference(bcx, taps)))
    with pytest.raises(ValueError):
      conv_lib.short_conv(bcx, taps, mode='pallas')
    with pytest.raises(ValueError):
      conv_lib.short_conv(bcx, taps[:, :2])

  def test_a_program_of_two_layers_holds_one_copy_of_each_kernel(self):
    """The kernels are ``jax.jit`` functions: however many layers call
    them, the program has each once, under the name the trace finds."""
    import re

    kernels = functools.partial(conv_lib.short_conv, mode='pallas',
                                interpret=True)

    def two_layers(bcx, taps):
      y = kernels(bcx, taps)
      return jnp.sum(jnp.sin(kernels(jnp.tile(y, (1, 1, 3)), taps)))

    text = jax.jit(jax.grad(two_layers, (0, 1))).lower(
        jax.ShapeDtypeStruct((2, 64, 384), jnp.float32),
        jax.ShapeDtypeStruct((128, 3), jnp.float32)).as_text()
    assert sorted(re.findall(r'func\.func private @(short_conv_\w+)\(',
                             text)) == ['short_conv_bwd', 'short_conv_fwd']
    assert len(re.findall(r'call @short_conv_fwd\(', text)) == 2
    assert len(re.findall(r'call @short_conv_bwd\(', text)) == 2


class TestTheBlocksFields:

  def test_the_four_kinds_of_block_hold_what_they_should(self):
    x = jnp.zeros((1, 16, 128))
    common = dict(num_heads=4, num_kv_heads=2, head_dim=32, num_experts=8,
                  experts_held=(0, 4), expert_dim=64, top_k=3, dense_dim=192,
                  moe_block_rows=8)
    names = lambda **kw: set(transformer_lib.MoEBlock(**common, **kw).init(
        jax.random.PRNGKey(0), x)['params'])
    norms = {'norm_attn', 'norm_moe'}
    assert names() == norms | {'attn', 'router', 'moe'}
    assert names(mixer='short_conv') == norms | {'conv', 'router', 'moe'}
    assert names(feed_forward='dense') == norms | {'attn', 'mlp'}
    assert names(mixer='short_conv', feed_forward='dense') == norms | {
        'conv', 'mlp'}
    for wrong in (dict(mixer='mamba'), dict(feed_forward='shared'),
                  dict(router='top1'), dict(router_reads='output')):
      with pytest.raises(ValueError):
        names(**wrong)

  def test_the_models_blocks_are_checkpointed_with_the_flash_policy(self):
    from tensor2robot_tpu.research.smallthinker import smallthinker_model

    assert lfm2_model.CheckpointedBlock is smallthinker_model.CheckpointedBlock


class TestTheShareOfAFourChipDeployment:

  def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(self):
    """Guide section 4: the parts of the result that all the shares give,
    with what every chip computes alike (the mixer) counted once, add up to
    what the uncut reference gives for the whole layer."""
    experts, shares = 8, 4
    held = experts // shares
    block = lambda first, count: transformer_lib.MoEBlock(
        num_heads=4, num_kv_heads=2, head_dim=32, num_experts=experts,
        experts_held=(first, count), expert_dim=64, top_k=3,
        mixer='short_conv', router_reads='normed', router='sigmoid_bias',
        gate_activation='silu', eps=1e-5, moe_block_rows=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, 128))
    whole = block(0, experts).init(jax.random.PRNGKey(1), x)['params']
    whole = jax.tree.map(
        lambda w: w + 0.1 * jax.random.normal(jax.random.PRNGKey(2), w.shape),
        whole)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (experts,))

    def share(first, zero_experts=False):
      params = dict(whole, moe={
          name: (0 if zero_experts else 1) * w[first:first + held]
          for name, w in whole['moe'].items()})
      with jax.default_matmul_precision('highest'):
        out, stats = block(first, held).apply(
            {'params': params, 'router_state': {'bias': bias}}, x)
      return out[0], stats

    stream = share(0, zero_experts=True)[0]         # x1: every chip's alike
    parts = [share(first) for first in range(0, experts, held)]
    summed = stream + sum(out - stream for out, _ in parts)
    want = reference.layer(
        whole, x[0], bias, 'conv', False,
        _settings(experts_held=(0, experts)), jnp.float32)
    assert _relative(summed, want) < 1e-5
    # Every pair was computed by exactly one chip.
    assert sum(float(stats['pairs_held']) for _, stats in parts) == LENGTH * 3
    # And one share alone is not the layer.
    assert _relative(parts[0][0], want) > 1e-3


class TestSoftmaxRoutingThroughTheRecutExpertLayer:

  def test_handed_the_routing_it_is_the_old_path_to_the_bit(self):
    """The expert layer handed (expert index, weight) by its caller computes
    what it computed when it routed the logits itself: the body it had then,
    written out here from the layer's own pieces."""
    from tensor2robot_tpu.parallel import grouped_matmul as gmm_lib

    u = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    logits = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    layer = moe_lib.DroplessMoE(num_experts=8, experts_held=(2, 4),
                                expert_dim=64, block_rows=8)
    params = layer.init(jax.random.PRNGKey(2), u,
                        moe_lib.route_top_k(logits, 3))

    def routed_inside(variables, u, logits):
      p = variables['params']
      expert_index, weight = moe_lib.route_top_k(logits, 3)
      layout = moe_lib.group_pairs(expert_index, 2, 4, 8)
      del layout['row_pair']
      rows = moe_lib.dispatch_rows(u, layout, 8)
      product = functools.partial(
          gmm_lib.grouped_matmul, tile_group=layout['tile_group'],
          num_tiles=layout['num_tiles'], block_m=8)
      gate_up = product(rows, jnp.concatenate([p['w_gate'], p['w_up']], -1))
      hidden = jax.nn.relu(gate_up[:, :64]) * gate_up[:, 64:]
      return moe_lib.combine_rows(product(hidden, p['w_down']), weight,
                                  layout)

    def run(apply):
      def loss(params, u, logits):
        y = apply(params, u, logits)
        return jnp.sum(jnp.sin(y)), y
      (_, y), grads = jax.value_and_grad(
          loss, (0, 1, 2), has_aux=True)(params, u, logits)
      return jax.tree.leaves((y, grads))

    old = run(routed_inside)
    new = run(lambda params, u, logits: layer.apply(
        params, u, moe_lib.route_top_k(logits, 3))[0])
    for got, want in zip(new, old):
      np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

  def test_the_block_routes_softmax_as_before(self):
    """The block's softmax path: ``route_top_k`` of the logits, now called
    by the block; stats it did not have are added, none changed."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128))
    block = transformer_lib.MoEBlock(
        num_heads=4, num_kv_heads=2, head_dim=32, num_experts=8,
        experts_held=(2, 4), expert_dim=64, top_k=3, moe_block_rows=8)
    variables = block.init(jax.random.PRNGKey(1), x)
    assert set(variables) == {'params'}      # no state under a softmax router
    out, stats = block.apply(variables, x)
    p = variables['params']
    logits = (x @ p['router']['kernel']).reshape(32, 8)
    h = transformer_lib.RMSNorm(1e-6).apply({'params': p['norm_attn']}, x)
    x1 = x + transformer_lib.GroupedQueryAttention(
        num_heads=4, num_kv_heads=2, head_dim=32).apply(
            {'params': p['attn']}, h)
    u = transformer_lib.RMSNorm(1e-6).apply({'params': p['norm_moe']}, x1)
    y, _ = moe_lib.DroplessMoE(
        num_experts=8, experts_held=(2, 4), expert_dim=64,
        block_rows=8).apply({'params': p['moe']}, u.reshape(32, 128),
                            moe_lib.route_top_k(logits, 3))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x1 + y.reshape(2, 16, 128)),
                               rtol=1e-6, atol=1e-6)
    assert float(stats['router_bias_abs_mean']) == 0
    assert {'pairs_held', 'load_max_over_mean', 'dropped_pairs',
            'rows_in_use', 'chosen_load_max_over_mean'} <= set(stats)
