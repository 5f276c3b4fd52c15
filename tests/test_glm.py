"""The GLM-4.7-Flash-style backbone with its multi-token-prediction module at
a small size on the CPU, against its independent reference
(benchmark/harness/glm_reference.py), with seeded random weights and norm
scales INCLUDING a non-zero router bias: loss, gradient by group (the MTP's
among them) and the parameters after one step; every fault planted in the
reference refused by the comparison the chip makes (the gradient by group);
what the first step cannot tell apart; the MTP alone against a hand-written
form, and its causality; the eight chips' shares of an expert layer adding up
to the uncut layer, in the trunk and in the MTP's block; the published form
only; the Xing model's step the one it had before, to the text."""

import hashlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensor2robot_tpu import runtime
from tensor2robot_tpu.layers import mtp as mtp_lib
from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.research.glm import GlmModel, glm_model
from tensor2robot_tpu.research.smallthinker import smallthinker_model
from tensor2robot_tpu.research.xing import XingModel
from benchmark.harness import glm_reference as reference

LENGTH = 32
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=16, v_head_dim=32, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=8,
             num_hidden_layers=2, first_k_dense_replace=1, vocab_rows=64,
             sequence_length=LENGTH, moe_block_rows=8, loss_block_tokens=16,
             embedding_init_std=1.0, residual_init_layers=6,
             device_type='cpu')
# The comparison (5) the chip makes: the worst top-level group of |step
# gradient - reference gradient| over |reference gradient|, and the tiny
# configuration's limit for it (float32 on both sides).
LIMIT = 1e-4


def _settings(**changed):
  settings = dict(
      hidden_size=64, num_heads=4, q_lora_rank=16, kv_lora_rank=16,
      qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
      rope_theta=1e6, dense_dim=96, expert_dim=32, shared_expert_dim=32,
      num_experts=8, experts_held=(0, 8), top_k=4, num_dense_layers=1,
      window_layers=(False,) * 2, routed_scaling=1.8, eps=1e-5,
      vocab_rows=64, mtp_weight=0.3, mtp_target_shift=2,
      mtp_embedding_shift=1, mtp_concat='embedding_first', enorm=True,
      hnorm=True, mtp_reads='normed', mtp_head_norm='own',
      shared_expert=True, scale_width='key', query_block=16, head_block=16)
  settings.update(changed)
  return settings


def _group_errors(got, want):
  """{group: |got - want| / |want|} over the top-level groups."""
  errors = {}
  for name in want:
    pairs = zip(jax.tree.leaves(got[name]), jax.tree.leaves(want[name]))
    squares = [(float(jnp.sum((a - b) ** 2)), float(jnp.sum(b ** 2)))
               for a, b in pairs]
    errors[name] = (sum(d for d, _ in squares) /
                    max(sum(n for _, n in squares), 1e-60)) ** 0.5
  return errors


def _worst_group(got, want):
  errors = _group_errors(got, want)
  name = max(errors, key=errors.get)
  return name, errors[name]


def _relative(got, want):
  return float(jnp.max(jnp.abs(got - want)) /
               (jnp.max(jnp.abs(want)) + 1e-30))


def _program(model, tokens):
  """(params, model_state) -> the program's (loss, (step metrics, outputs,
  model state after the call)) and gradient, in float32 products."""
  program = jax.jit(jax.value_and_grad(lambda p, model_state: model.loss_fn(
      p, model_state, {'tokens': tokens}, None, ModeKeys.TRAIN, None),
                                       has_aux=True))

  def run(params, model_state):
    with jax.default_matmul_precision('highest'):
      return program(params, model_state)

  return run


@pytest.fixture(scope='module')
def small():
  """The model (all 8 experts held: the shares' arithmetic is the share
  test's and the tiny cell's), its state, tokens, the routers' biases (the
  trunk's expert layer, then the MTP's), the state holding them, the norm
  scales drawn about 1 (at 1 a norm left out of the embedding, whose rows
  have unit scale, would not show), the program and its loss and gradient
  there. One sequence: the reference unrolls its loop over the batch."""
  model = GlmModel(experts_held=(0, 8), **SMALL)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (1, LENGTH), 1, 64)
  state = model.create_train_state(jax.random.PRNGKey(1), {'tokens': tokens},
                                   None)
  # A bias large enough to change who is chosen.
  biased = jax.tree.map(
      lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(7), b.shape),
      state.model_state)
  router = biased['router_state']
  bias_rows = [np.asarray(router['block1']['bias']),
               np.asarray(router['mtp']['block']['bias'])]
  keys = iter(jax.random.split(jax.random.PRNGKey(8), 64))
  params = jax.tree_util.tree_map_with_path(
      lambda path, leaf: leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
      if jax.tree_util.keystr(path).endswith("['scale']") else leaf,
      state.params)
  program = _program(model, tokens)
  return (model, state, tokens, bias_rows, biased, params, program,
          program(params, biased))


def _reference(small, dtype=jnp.float32, params=None, **changed):
  _, _, tokens, bias_rows, _, drawn, _, _ = small
  settings = _settings(**dict(dict(router_bias=bias_rows), **changed))
  return jax.jit(jax.value_and_grad(
      lambda p: reference.loss(p, tokens, settings, dtype)))(
          drawn if params is None else params)


class TestModelAgainstReference:

  def test_loss_gradient_by_group_and_one_steps_parameters(self, small):
    model, state, _, _, _, params, _, ((loss, _), grads) = small
    want, want_grads = _reference(small)
    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))
    assert set(want_grads) == {'block0', 'block1', 'embedding', 'head',
                               'norm_final', 'mtp'}
    assert set(want_grads['mtp']) == {'enorm', 'hnorm', 'eh_proj', 'block',
                                      'shared_head_norm'}
    assert _worst_group(grads, want_grads)[1] < 1e-5
    for name in want_grads:
      ratio = optax.global_norm(grads[name]) / optax.global_norm(
          want_grads[name])
      assert abs(float(ratio) - 1) < 1e-5, name
    # One step of the model's optimizer on each gradient.
    optimizer = model.create_optimizer()

    def stepped(g):
      updates, _ = optimizer.update(g, optimizer.init(params), params)
      return optax.apply_updates(params, updates)

    moved = jax.tree.map(lambda a, b: a - b, stepped(want_grads), params)
    off = jax.tree.map(lambda a, b: a - b, stepped(grads),
                       stepped(want_grads))
    assert float(optax.global_norm(off) / optax.global_norm(moved)) < 0.05

  @pytest.mark.parametrize('fault', [
      dict(mtp_target_shift=1), dict(mtp_embedding_shift=0),
      dict(mtp_weight=1.0), dict(mtp_concat='hidden_first'),
      dict(enorm=False), dict(hnorm=False), dict(shared_expert=False),
      dict(routed_scaling=1.0), dict(scale_width='nope'),
      dict(router_bias=None), dict(dtype=jnp.float8_e4m3fn)],
                           ids=lambda fault: '-'.join(
                               '{}={}'.format(k, getattr(v, '__name__', v))
                               for k, v in fault.items()))
  def test_a_reference_with_a_fault_is_refused(self, small, fault):
    *_, (_, grads) = small
    fault = dict(fault)
    _, wrong = _reference(small, fault.pop('dtype', jnp.float32), **fault)
    group, error = _worst_group(grads, wrong)
    assert error > 3 * LIMIT, (group, error)

  @pytest.mark.parametrize('form, told', [
      (dict(mtp_reads='residual'), {'norm_final'}),
      (dict(mtp_head_norm='trunk'), {'norm_final', 'mtp/shared_head_norm'})],
                           ids=['h_before_norm_final', 'head_norm_final'])
  def test_what_the_first_step_cannot_tell_apart(self, small, form, told):
    """At initialisation every norm scale is 1: the MTP reading the trunk's
    output before norm_final, or its head reading through norm_final in
    place of its own shared_head.norm, gives the same loss to rounding; the
    gradient differs in the scales of the norms concerned and nowhere else.
    So of the chip's comparisons, whose first step starts at scale 1, only
    (4) and (5) on the norm_final group (2,048 of 706.5M parameters) can see
    either."""
    _, state, _, _, biased, _, program, _ = small
    _, grads = program(state.params, biased)
    want, want_grads = _reference(small, params=state.params)
    other, other_grads = _reference(small, params=state.params, **form)
    assert abs(float(other) - float(want)) <= 1e-6 * abs(float(want))
    assert _worst_group(grads, want_grads)[1] < 1e-5
    errors = _group_errors(grads, other_grads)
    errors.update({'mtp/' + name: error for name, error in _group_errors(
        grads['mtp'], other_grads['mtp']).items()})
    assert {name for name, error in errors.items()
            if error > 3 * LIMIT} - {'mtp'} == told, errors

  def test_the_step_reports_both_losses_and_five_expert_layers_pairs(
      self, small):
    """All experts held: the pairs counted are exactly tokens x top_k x the
    expert layers, the MTP's among them; the MTP's router bias moves."""
    model, *_, biased, _, _, ((loss, (metrics, _, moved)), _) = small
    assert set(metrics) == set(glm_model.STEP_METRICS)
    assert float(loss) == pytest.approx(
        float(metrics['main/loss']) + 0.3 * float(metrics['mtp/loss']),
        rel=1e-6)
    # Two expert layers here: the trunk's one and the MTP's.
    assert float(metrics['moe/pairs_held']) == LENGTH * 4 * 2
    assert float(metrics['moe/dropped_pairs']) == 0
    assert float(metrics['moe/chosen_load_max_over_mean']) >= 1
    for path in (('block1',), ('mtp', 'block')):
      bias, before = moved['router_state'], biased['router_state']
      for key in path:
        bias, before = bias[key], before[key]
      np.testing.assert_allclose(jnp.abs(bias['bias'] - before['bias']),
                                 1e-3, rtol=1e-3)
    assert model.traced_step_metrics == (
        'moe/chosen_load_max_over_mean', 'main/loss', 'mtp/loss')

  def test_prediction_gives_the_last_logits(self, small):
    model, state, tokens, *_ = small
    outputs, _ = model.inference_network_fn(
        state.variables(), {'tokens': tokens}, None, ModeKeys.PREDICT, None)
    assert outputs['last_logits'].shape == (1, 64)

  @pytest.mark.parametrize('wrong', [
      dict(num_nextn_predict_layers=0), dict(num_nextn_predict_layers=2),
      dict(n_shared_experts=2), dict(topk_method='greedy'), dict(n_group=8),
      dict(tie_word_embeddings=True), dict(attention_bias=True),
      dict(num_key_value_heads=2), dict(partial_rotary_factor=0.5),
      dict(rope_scaling=dict(type='yarn', factor=4)), dict(hidden_act='gelu'),
      dict(norm_topk_prob=False)],
                           ids=lambda wrong: '-'.join(map(str, wrong)))
  def test_only_the_published_form_is_built(self, wrong):
    with pytest.raises(ValueError):
      GlmModel(**dict(SMALL, **wrong))


class _Block(nn.Module):
  """A stand-in block for the module alone: position-wise, with stats."""

  @nn.compact
  def __call__(self, x):
    return x + jnp.tanh(nn.Dense(x.shape[-1], name='proj')(x)), {'n': 1.0}


class TestTheModuleAlone:

  def test_against_the_hand_written_form(self):
    """m = [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i)] W_eh, g = block(m), the
    head reads RMSNorm_s(g) against t_{i+2} over positions 0..L-3."""
    b, l, d, v, eps = 2, 12, 16, 32, 1e-5
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    tokens = jax.random.randint(keys[0], (b, l), 0, v)
    hidden = jax.random.normal(keys[1], (b, l, d))
    embedding = jax.random.normal(keys[2], (v, d))
    head = 0.3 * jax.random.normal(keys[3], (d, v))
    module = mtp_lib.MultiTokenPrediction(_Block(parent=None), eps=eps)
    params = module.init(jax.random.PRNGKey(1), hidden, tokens, embedding)
    params = jax.tree.map(
        lambda w: w + 0.2 * jax.random.normal(jax.random.PRNGKey(2),
                                              w.shape), params)
    p = params['params']
    with jax.default_matmul_precision('highest'):
      ahead, stats = module.apply(params, hidden, tokens, embedding)
      loss = smallthinker_model.next_token_loss(ahead, head, tokens, 5,
                                                jnp.float32, shift=2)
      norm = lambda x, s: x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) +
                                       eps) * s
      m = jnp.concatenate([norm(embedding[jnp.roll(tokens, -1, 1)],
                                p['enorm']['scale']),
                           norm(hidden, p['hnorm']['scale'])], -1) @ \
          p['eh_proj']['kernel']
      g = m + jnp.tanh(m @ p['block']['proj']['kernel'] +
                       p['block']['proj']['bias'])
      want = norm(g, p['shared_head_norm']['scale'])
      logits = want[:, :l - 2] @ head
      picked = jnp.take_along_axis(logits, tokens[:, 2:, None], -1)[..., 0]
      want_loss = jnp.mean(jax.nn.logsumexp(logits, -1) - picked)
    assert stats == {'n': 1.0}
    assert _relative(ahead, want) < 1e-5
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert p['eh_proj']['kernel'].shape == (2 * d, d)

  def test_the_tail_reaches_no_earlier_position(self):
    """Through a latent-attention expert block: the last position's inputs
    (its trunk output and the wrapped-round embedding it reads) change
    nothing before it."""
    b, l, d, v = 1, 16, 64, 32
    block = transformer_lib.MoEBlock(
        num_heads=4, num_kv_heads=4, head_dim=32, num_experts=8,
        experts_held=(0, 8), expert_dim=32, top_k=4, rope_theta=1e6,
        mixer='latent_attention', q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
        router_reads='normed', router='sigmoid_bias', routed_scaling=1.8,
        shared_expert_dim=32, gate_activation='silu', moe_block_rows=8,
        parent=None)
    module = mtp_lib.MultiTokenPrediction(block)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (b, l), 0, v)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (b, l, d))
    embedding = jax.random.normal(jax.random.PRNGKey(2), (v, d))
    variables = jax.jit(module.init)(jax.random.PRNGKey(3), hidden, tokens,
                                     embedding)
    apply = jax.jit(module.apply)
    out, _ = apply(variables, hidden, tokens, embedding)
    moved, _ = apply(
        variables, hidden.at[:, -1].add(5.0), tokens.at[:, 0].set(
            (tokens[0, 0] + 1) % v), embedding)
    # Position 0's own row is untouched (it reads E[t_1]); position L-1
    # reads E[t_0] and h_{L-1}, both moved.
    np.testing.assert_array_equal(out[:, :-1], moved[:, :-1])
    assert float(jnp.max(jnp.abs(out[:, -1] - moved[:, -1]))) > 1e-3

  def test_a_depth_other_than_one_is_refused(self):
    module = mtp_lib.MultiTokenPrediction(_Block(parent=None), depth=2)
    with pytest.raises(ValueError, match='depth 1'):
      module.init(jax.random.PRNGKey(0), jnp.ones((1, 4, 8)),
                  jnp.zeros((1, 4), jnp.int32), jnp.ones((4, 8)))


class TestTheShareOfAnEightChipDeployment:

  @pytest.mark.parametrize('where', ['trunk', 'mtp'])
  def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
      self, where):
    """Guide section 4: the parts of the result that all the shares give,
    with what every chip computes alike (attention, the norms, the shared
    expert) counted once, add up to what the uncut reference gives for the
    whole layer; in the MTP's block on its own input, eh_proj's m."""
    experts, shares, d = 16, 8, 64
    held = experts // shares
    block = lambda first, count: transformer_lib.MoEBlock(
        num_heads=4, num_kv_heads=4, head_dim=32, num_experts=experts,
        experts_held=(first, count), expert_dim=32, top_k=4, eps=1e-5,
        rope_theta=1e6, mixer='latent_attention', q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=32, router_reads='normed', router='sigmoid_bias',
        routed_scaling=1.8, shared_expert_dim=32, gate_activation='silu',
        moe_block_rows=8)
    settings = _settings(experts_held=(0, experts), num_experts=experts)
    x = jax.random.normal(jax.random.PRNGKey(0), (LENGTH, d))
    if where == 'mtp':
      mtp = {name: {'scale': 1 + 0.3 * jax.random.normal(
          jax.random.PRNGKey(i), (d,))} for i, name in enumerate(
              ('enorm', 'hnorm'))}
      mtp['eh_proj'] = {'kernel': 0.1 * jax.random.normal(
          jax.random.PRNGKey(5), (2 * d, d))}
      tokens = jax.random.randint(jax.random.PRNGKey(6), (LENGTH,), 0, 64)
      embedding = jax.random.normal(jax.random.PRNGKey(7), (64, d))
      with jax.default_matmul_precision('highest'):
        x = reference.mtp_input(mtp, embedding, x, tokens, settings,
                                jnp.float32)
    whole = block(0, experts).init(jax.random.PRNGKey(1), x[None])['params']
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
            {'params': params, 'router_state': {'bias': bias}}, x[None])
      return out[0], stats

    alike = share(0, zero_experts=True)[0]          # every chip's alike
    parts = [share(first) for first in range(0, experts, held)]
    summed = alike + sum(out - alike for out, _ in parts)
    with jax.default_matmul_precision('highest'):
      want = reference.layer(whole, x, bias, False, settings, jnp.float32)
    assert _relative(summed, want) < 1e-5
    # Every pair was computed by exactly one chip.
    assert sum(float(stats['pairs_held']) for _, stats in parts) == LENGTH * 4
    # And one share alone is not the layer.
    assert _relative(parts[0][0], want) > 1e-3


# The sha256 of the Xing model's differentiated step's jaxpr text, at a tiny
# size that keeps its dense and expert layers, with every kernel the TPU
# selects (nothing runs), as the code gave it before this module and the
# shift of `next_token_loss` were added, read again when the flash kernels
# took the interior / edge split of their tiles (only the kernels moved:
# tests/test_xing.py). The three other token models' steps are held the same
# way in tests/test_xing.py.
XING_STEP_DIGEST = 'bfe5aa61660eb8be'


def test_the_xing_models_step_is_the_one_it_had(monkeypatch):
  model = XingModel(
      experts_held=(2, 4), hidden_size=128, num_attention_heads=4,
      num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=32,
      qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=8,
      intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
      num_hidden_layers=2, first_k_dense_replace=1, vocab_rows=64,
      sequence_length=32, moe_block_rows=8, loss_block_tokens=16,
      device_type='cpu')
  tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 1, 64)
  state = model.create_train_state(jax.random.PRNGKey(1), {'tokens': tokens},
                                   None)
  monkeypatch.setattr(runtime, 'on_tpu', lambda: True)
  monkeypatch.setattr(transformer_lib, 'resolve_attention_mode',
                      lambda mode, length: 'flash')
  step = lambda params: model.loss_fn(
      params, state.model_state, {'tokens': tokens}, None, ModeKeys.TRAIN,
      jax.random.PRNGKey(2))[0]
  text = re.sub(r' at 0x[0-9a-f]+', '', str(
      jax.make_jaxpr(jax.grad(step))(state.params)))
  assert hashlib.sha256(text.encode()).hexdigest()[:16] == XING_STEP_DIGEST
