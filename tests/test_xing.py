"""The Xing4.0-style backbone at a small size on the CPU, against its
independent reference (benchmark/harness/xing_reference.py), with seeded
random weights INCLUDING a non-zero router bias: loss, gradient by group and
the parameters after one step; every fault planted in the reference refused
by the comparison the chip makes (the gradient by group); YaRN's frequencies
against a hand table; the latent attention's shapes and its value width; the
eight chips' shares of an expert layer adding up to the uncut layer with the
shared expert, attention and streams counted once; the published form only;
the block's checkpoint keeping what the stream kernels' backward reads, and
the other token models' steps untouched by it."""

import hashlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensor2robot_tpu import runtime
from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import get_registry
from tensor2robot_tpu.parallel import hyper_connections as hc_lib
from tensor2robot_tpu.research.lfm2 import LFM2Model, lfm2_model
from tensor2robot_tpu.research.sdar import SDARModel, sdar_model
from tensor2robot_tpu.research.smallthinker import SmallThinkerModel
from tensor2robot_tpu.research.smallthinker import smallthinker_model
from tensor2robot_tpu.research.xing import XingModel, xing_model
from benchmark.harness import xing_reference as reference

LENGTH = 32
ROPE = dict(type='yarn', factor=64, original_max_position_embeddings=4096,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
SMALL = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=16, v_head_dim=8, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=8,
             num_hidden_layers=3, first_k_dense_replace=1, vocab_rows=64,
             sequence_length=LENGTH, moe_block_rows=8, loss_block_tokens=16,
             embedding_init_std=1.0, residual_init_layers=6,
             device_type='cpu')
# The comparison (5) the chip makes: the worst top-level group of |step
# gradient - reference gradient| over |reference gradient|, and the tiny
# configuration's limit for it (float32 on both sides).
LIMIT = 1e-4


def _settings(**changed):
  settings = dict(
      hidden_size=128, num_heads=4, q_lora_rank=32, kv_lora_rank=32,
      qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=8,
      rope_theta=10000, rope_scaling=ROPE, dense_dim=96, expert_dim=32,
      shared_expert_dim=32, num_experts=8, experts_held=(2, 4), top_k=4,
      num_dense_layers=1, window_layers=(False,) * 3, routed_scaling=2,
      streams=4, sinkhorn_iters=20, stream_eps=1e-6, clamp=30, eps=1e-6,
      vocab_rows=64, sinkhorn='sinkhorn', post_factor=2,
      mscale_squared=True, yarn=True, shared_expert=True, k_pe='shared',
      kv_norm=True, query_block=16, head_block=16)
  settings.update(changed)
  return settings


def _relative(got, want):
  return float(jnp.max(jnp.abs(got - want)) /
               (jnp.max(jnp.abs(want)) + 1e-30))


def _worst_group(got, want):
  """(group, |got - want| / |want|) of the worst top-level group."""
  errors = {}
  for name in want:
    pairs = zip(jax.tree.leaves(got[name]), jax.tree.leaves(want[name]))
    squares = [(float(jnp.sum((a - b) ** 2)), float(jnp.sum(b ** 2)))
               for a, b in pairs]
    errors[name] = (sum(d for d, _ in squares) /
                    max(sum(n for _, n in squares), 1e-60)) ** 0.5
  name = max(errors, key=errors.get)
  return name, errors[name]


@pytest.fixture(scope='module')
def small():
  model = XingModel(experts_held=(2, 4), **SMALL)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (2, LENGTH), 1, 64)
  state = model.create_train_state(jax.random.PRNGKey(1), {'tokens': tokens},
                                   None)
  # A bias large enough to change who is chosen (sigmoid scores differ by
  # tenths).
  biased = jax.tree.map(
      lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(7), b.shape),
      state.model_state)
  bias_rows = [np.asarray(biased['router_state']['block{}'.format(i)]['bias'])
               for i in range(1, 3)]

  def program(params, model_state=biased):
    return model.loss_fn(params, model_state, {'tokens': tokens}, None,
                         ModeKeys.TRAIN, None)[0]

  with jax.default_matmul_precision('highest'):
    loss, grads = jax.jit(jax.value_and_grad(program))(state.params)
  return model, state, tokens, bias_rows, biased, (float(loss), grads)


def _reference(small, dtype=jnp.float32, **changed):
  _, state, tokens, bias_rows, _, _ = small
  settings = _settings(**dict(dict(router_bias=bias_rows), **changed))
  return jax.jit(jax.value_and_grad(
      lambda p: reference.loss(p, tokens, settings, dtype)))(state.params)


class TestModelAgainstReference:

  def test_loss_gradient_by_group_and_one_steps_parameters(self, small):
    model, state, _, _, _, (loss, grads) = small
    want, want_grads = _reference(small)
    assert abs(loss - float(want)) <= 1e-6 * abs(float(want))
    assert set(want_grads) == {'block0', 'block1', 'block2', 'embedding',
                               'head', 'norm_final'}
    assert _worst_group(grads, want_grads)[1] < 1e-5
    for name in want_grads:
      got_norm = optax.global_norm(grads[name])
      assert abs(float(got_norm / optax.global_norm(want_grads[name])) - 1) \
          < 1e-5, name
    # One step of the model's optimizer on each gradient.
    optimizer = model.create_optimizer()

    def stepped(g):
      updates, _ = optimizer.update(g, optimizer.init(state.params),
                                    state.params)
      return optax.apply_updates(state.params, updates)

    moved = jax.tree.map(lambda a, b: a - b, stepped(want_grads),
                         state.params)
    off = jax.tree.map(lambda a, b: a - b, stepped(grads),
                       stepped(want_grads))
    assert float(optax.global_norm(off) / optax.global_norm(moved)) < 0.05

  @pytest.mark.parametrize('fault', [
      dict(sinkhorn='row_softmax'), dict(post_factor=1),
      dict(mscale_squared=False), dict(yarn=False),
      dict(shared_expert=False), dict(routed_scaling=1),
      dict(k_pe='per_head'), dict(kv_norm=False), dict(router_bias=None),
      dict(dtype=jnp.float8_e4m3fn)],
                           ids=lambda fault: '-'.join(
                               '{}={}'.format(k, getattr(v, '__name__', v))
                               for k, v in fault.items()))
  def test_a_reference_with_a_fault_is_refused(self, small, fault):
    *_, (_, grads) = small
    fault = dict(fault)
    _, wrong = _reference(small, fault.pop('dtype', jnp.float32), **fault)
    group, error = _worst_group(grads, wrong)
    assert error > 3 * LIMIT, (group, error)

  def test_the_clamp_at_thirty_is_held_here(self, small):
    """Nothing reaches 30 at initialisation, so no comparison on the chip can
    see the clamp: here the res biases are pushed past it (to 50 on the
    diagonal and 40 off it, which the clamp makes equal), and the program
    agrees with the reference that clamps and not with one that does
    not."""
    _, state, tokens, bias_rows, biased, _ = small
    model = XingModel(experts_held=(2, 4), **SMALL)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 40.0 + 10.0 * jnp.eye(4).reshape(
            leaf.shape) if 'b_res' in jax.tree_util.keystr(path) else leaf,
        state.params)
    with jax.default_matmul_precision('highest'):
      loss, grads = jax.value_and_grad(lambda p: model.loss_fn(
          p, biased, {'tokens': tokens}, None, ModeKeys.TRAIN, None)[0])(
              params)
    run = lambda clamp: jax.value_and_grad(lambda p: reference.loss(
        p, tokens, _settings(router_bias=bias_rows, clamp=clamp)))(params)
    want, want_grads = run(30)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert _worst_group(grads, want_grads)[1] < 1e-4
    _, unclamped = run(1e9)
    assert _worst_group(grads, unclamped)[1] > 3 * LIMIT

  def test_the_step_reports_its_counters_and_the_streams_error(self, small):
    model, state, tokens, _, _, _ = small
    _, metrics = jax.jit(model.train_step)(state, {'tokens': tokens}, None,
                                           jax.random.PRNGKey(3))
    assert set(xing_model.STEP_METRICS) <= set(metrics)
    assert {'grad_norm', 'grad_group_norm/block0', 'grad_group_norm/head',
            'grad_group_norm/embedding'} <= set(metrics)
    assert float(metrics['moe/dropped_pairs']) == 0
    # 2 sequences x 32 tokens x 2 expert layers, 4 of 8 chosen, 4 held.
    assert 0.5 < float(metrics['moe/pairs_held']) / (2 * LENGTH * 2) < 4
    # Twenty iterations leave every column sum at 1 and the rows near it.
    assert 0 < float(metrics['hc/res_stochastic_error']) < 1e-2
    # The most chosen of the router's experts over the mean: 1 is even.
    assert float(metrics['moe/chosen_load_max_over_mean']) >= 1
    assert model.traced_step_metrics == (
        'hc/res_stochastic_error', 'moe/chosen_load_max_over_mean')

  def test_the_gauges_say_the_streams_and_the_iterations(self, small):
    from tensor2robot_tpu.observability import get_registry

    model, state, tokens, _, _, _ = small
    model.loss_fn(state.params, state.model_state, {'tokens': tokens}, None,
                  ModeKeys.TRAIN, None)
    registry = get_registry()
    assert registry.gauge('hc/streams').value == 4
    assert registry.gauge('hc/sinkhorn_iters').value == 20

  def test_prediction_gives_the_last_logits(self, small):
    model, state, tokens, _, _, _ = small
    outputs, _ = model.inference_network_fn(
        state.variables(), {'tokens': tokens}, None, ModeKeys.PREDICT, None)
    assert outputs['last_logits'].shape == (2, 64)

  def test_only_the_published_form_is_built(self):
    for wrong in (dict(num_nextn_predict_layers=1), dict(n_shared_experts=2),
                  dict(scoring_func='softmax'), dict(topk_method='greedy'),
                  dict(n_group=8), dict(tie_word_embeddings=True),
                  dict(attention_bias=True), dict(num_key_value_heads=2),
                  dict(rope_scaling=dict(ROPE, type='linear')),
                  dict(mhc_h_res_clamp_min=-10)):
      with pytest.raises(ValueError):
        XingModel(**dict(SMALL, **wrong))


class TestYarn:

  def test_the_32_frequencies_against_the_hand_table(self):
    """The hand table of the equations: f_i = 10000^(-2i/64), low = 10, high = 23, ramp_i =
    clip((i - 10) / 13, 0, 1), inv_i = f_i / 64 ramp_i + f_i (1 - ramp_i)."""
    table = []
    for i in range(32):
      f = 10000.0 ** (-2.0 * i / 64)
      ramp = min(max((i - 10) / 13.0, 0.0), 1.0)
      table.append(f / 64 * ramp + f * (1 - ramp))
    got = transformer_lib.yarn_frequencies(64, 10000.0, 64, 4096, 32, 1)
    np.testing.assert_allclose(got, np.asarray(table, np.float32),
                               rtol=1e-6)
    assert got[9] == pytest.approx(10000.0 ** (-18 / 64), rel=1e-6)
    assert got[31] == pytest.approx(10000.0 ** (-62 / 64) / 64, rel=1e-6)
    np.testing.assert_allclose(reference.rotary_frequencies(_settings(
        qk_rope_head_dim=64)), table, rtol=1e-6)

  def test_the_scale_is_mscale_squared_over_sqrt_192(self):
    layer = transformer_lib.LatentAttention(
        num_heads=32, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=1e4,
        rope_scaling=(64, 4096, 32, 1, 1, 1))
    _, on_cos_sin, scale = layer.rotary()
    assert on_cos_sin == 1.0
    assert scale == pytest.approx(0.144680, abs=1e-6)
    assert reference.attention_scale(_settings(
        qk_nope_head_dim=128, qk_rope_head_dim=64)) == pytest.approx(
            0.144680, abs=1e-6)


class TestTheBlocksFields:

  def test_latent_attention_streams_and_the_shared_expert(self):
    common = dict(num_heads=4, num_kv_heads=4, head_dim=32, num_experts=8,
                  experts_held=(0, 4), expert_dim=32, top_k=4,
                  moe_block_rows=8, mixer='latent_attention', q_lora_rank=32,
                  kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
                  v_head_dim=8, rope_theta=1e4, router_reads='normed')
    x = jnp.ones((1, 16, 4 * 128))
    block = transformer_lib.MoEBlock(hc_streams=4, shared_expert_dim=32,
                                     **common)
    params = block.init(jax.random.PRNGKey(0), x)['params']
    assert set(params) == {'norm_attn', 'norm_moe', 'attn', 'router', 'moe',
                           'shared_expert', 'hc_attn', 'hc_ff'}
    assert set(params['attn']) == {'q_a', 'q_a_norm', 'q_b', 'kv_a',
                                   'kv_a_norm', 'kv_b', 'out'}
    assert params['attn']['kv_b']['kernel'].shape == (32, 4 * (16 + 8))
    assert params['attn']['out']['kernel'].shape == (4 * 8, 128)
    assert params['hc_attn']['phi_res'].shape == (512, 16)
    out, stats = block.apply({'params': params}, x)
    assert out.shape == x.shape and out.dtype == jnp.float32
    assert 'res_stochastic_error' in stats
    # One stream, no shared expert: the fields are off by default.
    plain = transformer_lib.MoEBlock(**common).init(
        jax.random.PRNGKey(0), jnp.ones((1, 16, 128)))['params']
    assert set(plain) == {'norm_attn', 'norm_moe', 'attn', 'router', 'moe'}
    with pytest.raises(ValueError):
      transformer_lib.MoEBlock(hc_streams=4, **dict(
          common, router_reads='input')).init(jax.random.PRNGKey(0), x)

  def test_the_router_weighs_by_the_scaling_factor(self):
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    bias = jnp.zeros((8,))
    index, weight = moe_lib.route_sigmoid_bias(logits, bias, 4)
    index2, weight2 = moe_lib.route_sigmoid_bias(logits, bias, 4, 2.0)
    assert (index == index2).all()
    np.testing.assert_allclose(weight2, 2 * weight, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(weight2, -1), 2.0, rtol=1e-5)


class TestTheShareOfAnEightChipDeployment:

  def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
      self):
    """Guide section 4: the parts of the result that all the shares give,
    with what every chip computes alike (attention, the streams' maps and
    mixes, the shared expert) counted once, add up to what the uncut
    reference gives for the whole layer."""
    experts, shares = 16, 8
    held = experts // shares
    block = lambda first, count: transformer_lib.MoEBlock(
        num_heads=4, num_kv_heads=4, head_dim=32, num_experts=experts,
        experts_held=(first, count), expert_dim=32, top_k=4,
        mixer='latent_attention', q_lora_rank=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=8,
        rope_theta=1e4, rope_scaling=(64, 4096, 32, 1, 1, 1),
        router_reads='normed', router='sigmoid_bias', routed_scaling=2.0,
        shared_expert_dim=32, hc_streams=4, gate_activation='silu',
        moe_block_rows=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, 4 * 128))
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

    stream = share(0, zero_experts=True)[0]         # every chip's alike
    parts = [share(first) for first in range(0, experts, held)]
    summed = stream + sum(out - stream for out, _ in parts)
    with jax.default_matmul_precision('highest'):
      want = reference.layer(
          whole, x[0].reshape(LENGTH, 4, 128), bias, False,
          _settings(experts_held=(0, experts), num_experts=experts,
                    rope_theta=1e4), jnp.float32)
    assert _relative(summed, want.reshape(LENGTH, -1)) < 1e-5
    # Every pair was computed by exactly one chip.
    assert sum(float(stats['pairs_held']) for _, stats in parts) == LENGTH * 4
    # And one share alone is not the layer.
    assert _relative(parts[0][0], want.reshape(LENGTH, -1)) > 1e-3


# The checkpoint the model had before: it kept the flash kernels' arrays and
# ran the rest of the block, the stream kernels' forwards among it, again.
FLASH_ONLY = nn.remat(
    transformer_lib.MoEBlock,
    policy=jax.checkpoint_policies.save_only_these_names(
        *transformer_lib.flash_lib.BACKWARD_READS))
STREAM_KERNELS = ('hc_pre_fwd', 'hc_post_fwd', 'hc_post_bwd', 'hc_pre_bwd')
FLASH_KERNELS = ('flash_attention_fwd', 'flash_attention_bwd_dq')


@pytest.fixture
def kernels_selected(monkeypatch):
  """The flash and stream kernels wherever the TPU would take them, on the
  Pallas interpreter here."""
  monkeypatch.setattr(transformer_lib, 'resolve_attention_mode',
                      lambda mode, length: 'flash')
  monkeypatch.setattr(hc_lib, '_use_kernels',
                      lambda mode, rows, c: mode != 'xla')


def _stream_stack(block_cls, blocks):
  """(loss over parameters and input, parameters, input): ``blocks`` blocks
  of latent attention over four streams of 128, 128 tokens; the first
  block's feed-forward dense, the second's routed experts beside a shared
  one."""
  x = jax.random.normal(jax.random.PRNGKey(10), (1, 128, 4 * 128))

  class Stack(nn.Module):

    @nn.compact
    def __call__(self, x):
      for i in range(blocks):
        x, _ = block_cls(
            num_heads=2, num_kv_heads=2, head_dim=32, num_experts=8,
            experts_held=(2, 4), expert_dim=32, top_k=4, rope_theta=1e4,
            mixer='latent_attention', q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
            feed_forward='dense' if i == 0 else 'experts', dense_dim=64,
            router_reads='normed', router='sigmoid_bias', routed_scaling=2.0,
            shared_expert_dim=32, hc_streams=4, gate_activation='silu',
            moe_block_rows=8, name='block{}'.format(i))(x)
      return jnp.sum(jnp.sin(x))

  stack = Stack()
  return stack.apply, stack.init(jax.random.PRNGKey(11), x), x


class TestTheBlockCheckpointKeepsWhatTheStreamKernelsBackwardReads:
  """Pallas interpreter, tiny widths: the model's checkpoint against the
  one that kept the flash arrays alone."""

  @pytest.mark.parametrize('blocks', [1, 2], ids=['dense', 'dense_experts'])
  def test_each_stream_forward_once_a_sublayer_and_the_same_gradients(
      self, blocks, jaxpr_calls, kernels_selected):
    results = {}
    for name, block_cls in [('kept', smallthinker_model.CheckpointedBlock),
                            ('flash only', FLASH_ONLY)]:
      loss, params, x = _stream_stack(block_cls, blocks)
      grad = jax.value_and_grad(loss, argnums=(0, 1))
      calls, _ = jaxpr_calls(grad, params, x)
      results[name] = dict(
          calls=calls, grads=jax.tree.leaves(grad(params, x)))
    kept, before = results['kept']['calls'], results['flash only']['calls']
    # Each sublayer runs its pre and post kernels once; the flash only
    # checkpoint ran both pre kernels and the first post kernel again.
    assert [kept[k] for k in STREAM_KERNELS] == [2 * blocks] * 4
    assert [before[k] for k in STREAM_KERNELS] == [
        4 * blocks, 3 * blocks, 2 * blocks, 2 * blocks]
    assert [kept[k] for k in FLASH_KERNELS] == [blocks] * 2 == [
        before[k] for k in FLASH_KERNELS]
    # f is kept: attention's `out` and the feed-forward's last product (the
    # dense w2; the shared expert's w2) are not run again, nor the routed
    # experts' sum of rows. Their down product is: the sum's backward reads
    # its rows for the routing weights' gradient.
    assert kept['dot_general'] == before['dot_general'] - 2 * blocks
    assert kept['moe_sum_rows'] == before['moe_sum_rows'] - (blocks - 1)
    assert kept['moe_grouped_matmul'] == before['moe_grouped_matmul']
    got, want = results['kept']['grads'], results['flash only']['grads']
    assert len(got) == len(want)
    # The kept arrays are the ones the second forward produced: on the CPU
    # loss and every gradient leaf come out the same to the last bit.
    for g, w in zip(got, want):
      np.testing.assert_array_equal(g, w)

  def test_the_step_names_the_flash_and_the_stream_reads_and_no_more(
      self, small, kernels_selected):
    model, state, tokens, _, biased, _ = small
    program = lambda params: model.loss_fn(
        params, biased, {'tokens': tokens}, None, ModeKeys.TRAIN, None)[0]
    get_registry().gauge('hc/kept_bytes_per_token').set(-1.0)
    jaxpr = jax.make_jaxpr(jax.grad(program))(state.params).jaxpr
    tagged = _tagged(jaxpr)
    # The policy is made of the two BACKWARD_READS; a tag renamed in a
    # forward rule alone fails here rather than bring the second forward
    # back.
    assert set(tagged) == set(transformer_lib.flash_lib.BACKWARD_READS) | \
        set(hc_lib.BACKWARD_READS)
    # Each name once a sublayer, twice a block, in the forward alone.
    layers = SMALL['num_hidden_layers']
    assert {name: len(tagged[name]) for name in hc_lib.BACKWARD_READS} == \
        dict.fromkeys(hc_lib.BACKWARD_READS, 2 * layers)
    # The gauge is the tagged bytes a token a block, less the second
    # sublayer's state: the block's output, kept as the next one's input.
    rows = tokens.size
    width = 4 * SMALL['hidden_size'] * 4
    per_block = (sum(sum(tagged[name]) for name in hc_lib.BACKWARD_READS) /
                 layers - rows * width) / rows
    assert get_registry().gauge('hc/kept_bytes_per_token').value == \
        per_block == hc_lib.kept_bytes_per_token(rows, 4, 128, 4)
    # At the cell's widths (four streams of 3,584, f in bf16).
    assert hc_lib.kept_bytes_per_token(4096, 4, 3584, mode='pallas') == \
        100608

  def test_off_the_kernels_nothing_is_named_and_nothing_counted(self, small):
    model, state, tokens, _, biased, _ = small
    program = lambda params: model.loss_fn(
        params, biased, {'tokens': tokens}, None, ModeKeys.TRAIN, None)[0]
    jaxpr = jax.make_jaxpr(jax.grad(program))(state.params).jaxpr
    assert not set(_tagged(jaxpr)) & set(hc_lib.BACKWARD_READS)
    assert get_registry().gauge('hc/kept_bytes_per_token').value == 0


def _tagged(jaxpr, out=None):
  """{checkpoint name: [bytes of each tagged array]} over a jaxpr, nested
  ones included."""
  out = {} if out is None else out
  for eqn in jaxpr.eqns:
    if eqn.primitive.name == 'name':
      aval = eqn.outvars[0].aval
      out.setdefault(eqn.params['name'], []).append(
          aval.size * aval.dtype.itemsize)
    elif eqn.primitive.name != 'pallas_call':
      for inner in jax.core.jaxprs_in_params(eqn.params):
        _tagged(inner, out)
  return out


# The other token models at their tests' tiny sizes.
OTHER_MODELS = {
    'smallthinker': (smallthinker_model, lambda: SmallThinkerModel(
        experts_held=(2, 4), hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
        moe_num_primary_experts=8, moe_num_active_primary_experts=3,
        num_hidden_layers=4, sliding_window_size=8, vocab_rows=64,
        sequence_length=32, moe_block_rows=8, loss_block_tokens=16,
        device_type='cpu'), 32),
    'sdar': (sdar_model, lambda: SDARModel(
        experts_held=(2, 4), hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=16,
        num_experts=8, num_experts_per_tok=3, num_hidden_layers=2,
        vocab_rows=64, sequence_length=48, block_length=4, moe_block_rows=8,
        loss_block_tokens=16, device_type='cpu'), 48),
    'lfm2': (lfm2_model, lambda: LFM2Model(
        experts_held=(2, 4), hidden_size=128, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=192,
        moe_intermediate_size=64, num_experts=8, num_experts_per_tok=3,
        num_hidden_layers=5, num_dense_layers=1, first_layer=1,
        vocab_rows=64, sequence_length=32, moe_block_rows=8,
        loss_block_tokens=16, device_type='cpu'), 32),
}


# The sha256 of each model's differentiated step below, as the code gave it
# before the multi-token-prediction module (`layers/mtp.py`) and the shift of
# `next_token_loss` were added: a change to shared code that moves these
# steps shows here. Read again when the flash kernels took the interior /
# edge split of their tiles (`tile_table`'s EDGE flag, a table of strip
# spans): with the flash kernels replaced by one plain function in both
# codes, the steps' texts were the same, so only the kernels moved.
PARENT_STEP_DIGESTS = {'lfm2': '4fd4f8532d0501ee', 'sdar': '4e472b746297f3f0',
                       'smallthinker': '3ba6f0dc2ef42e44'}


class TestTheOtherTokenModelsNameNoStreamArray:
  """Their blocks have one stream: the checkpoint that keeps the stream
  kernels' arrays gives them, to the text, the step the flash only
  checkpoint gave, with every kernel the TPU selects (nothing runs); and
  that step is the one they had before multi-token prediction was added."""

  @pytest.mark.parametrize('name', sorted(OTHER_MODELS))
  def test_the_differentiated_step_is_the_flash_only_one(
      self, name, monkeypatch, jaxpr_calls):
    module, make, length = OTHER_MODELS[name]
    model = make()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, length), 1, 64)
    state = model.create_train_state(jax.random.PRNGKey(1),
                                     {'tokens': tokens}, None)
    monkeypatch.setattr(runtime, 'on_tpu', lambda: True)
    monkeypatch.setattr(transformer_lib, 'resolve_attention_mode',
                        lambda mode, length: 'flash')
    step = lambda params: model.loss_fn(
        params, state.model_state, {'tokens': tokens}, None, ModeKeys.TRAIN,
        jax.random.PRNGKey(2))[0]
    get_registry().gauge('hc/kept_bytes_per_token').set(-1.0)
    texts, counts = [], []
    for block_cls in (module.CheckpointedBlock, FLASH_ONLY):
      monkeypatch.setattr(module, 'CheckpointedBlock', block_cls)
      texts.append(re.sub(r' at 0x[0-9a-f]+', '', str(
          jax.make_jaxpr(jax.grad(step))(state.params))))
      counts.append(jaxpr_calls(jax.grad(step), state.params))
    (calls, tags), (calls_before, tags_before) = counts
    assert texts[0] == texts[1]
    assert hashlib.sha256(texts[0].encode()).hexdigest()[:16] == \
        PARENT_STEP_DIGESTS[name]
    assert calls == calls_before and tags == tags_before
    assert set(tags) == set(transformer_lib.flash_lib.BACKWARD_READS)
    assert calls['flash_attention_fwd'] >= 1
    assert get_registry().gauge('hc/kept_bytes_per_token').value == 0
