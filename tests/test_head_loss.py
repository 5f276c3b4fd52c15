"""The head's blocked cross-entropy (``layers/transformer.py``): its loss and
both gradients against a plain, unblocked float32 ``jax.numpy``
cross-entropy; the compiled program's loops and products over the
vocabulary, differentiated and not; the gauge its differentiated trace
sets."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.observability import get_registry

D, V = 32, 97


def _plain_loss(hidden, head, targets, weights):
  """Sum of weights x cross-entropy, every token at once, in float32."""
  logits = hidden.astype(jnp.float32) @ head.astype(jnp.float32)
  picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weights * (jax.nn.logsumexp(logits, axis=-1) - picked))


def _inputs(b, l, zero_weights, seed=0):
  keys = jax.random.split(jax.random.PRNGKey(seed), 4)
  hidden = jax.random.normal(keys[0], (b, l, D))
  param = jax.random.normal(keys[1], (D, V)) * 0.3
  targets = jax.random.randint(keys[2], (b, l), 0, V)
  weights = jax.random.uniform(keys[3], (b, l), minval=0.5, maxval=1.5)
  if zero_weights:
    weights = jnp.where(jnp.arange(l) % 3 == 0, 0.0, weights)
  return hidden, param, targets, weights


def _relative(got, want):
  return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) /
               jnp.max(jnp.abs(want)))


# (tokens a sequence, block_tokens): 2 x 32 in blocks of 16 (four); 2 x 20,
# which 16 does not divide (blocks of 10); 2 x 16 in one block.
SHAPES = {'several_blocks': (32, 16), 'block_not_dividing': (20, 16),
          'one_block': (16, 64)}
# Relative to the loss and to a gradient's largest element. float32: the
# blocked sums against the unblocked ones (read: 1.4e-7, 2.5e-7).
# bfloat16: hidden and head rounded to 8 bits of mantissa (a step of 2^-8
# = 3.9e-3), the logits' gradient rounded again, the head's gradient
# carried at bf16 across the blocks: four steps (read: 1.9e-4, 6.2e-3;
# the loop this one replaced read the same, 6.2e-3).
TOLERANCE = {'float32': dict(loss=1e-6, grad=2e-6),
             'bfloat16': dict(loss=2e-3, grad=1.6e-2)}


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('tied', [False, True], ids=['untied', 'tied'])
@pytest.mark.parametrize('zero_weights', [False, True],
                         ids=['all_weighted', 'zero_in_part'])
@pytest.mark.parametrize('shape', sorted(SHAPES))
def test_value_and_grad_match_the_plain_cross_entropy(shape, zero_weights,
                                                      tied, dtype):
  l, block_tokens = SHAPES[shape]
  hidden, param, targets, weights = _inputs(2, l, zero_weights)
  if tied:
    # LFM2's head: the embedding [V, d], transposed at the call.
    param = param.T
  as_head = (lambda p: p.T) if tied else (lambda p: p)

  def blocked(h, p):
    return transformer_lib.blocked_cross_entropy(
        h, as_head(p), targets, weights, block_tokens, jnp.dtype(dtype))

  def plain(h, p):
    return _plain_loss(h, as_head(p), targets, weights)

  loss, grads = jax.value_and_grad(blocked, argnums=(0, 1))(hidden, param)
  want, want_grads = jax.value_and_grad(plain, argnums=(0, 1))(hidden, param)
  tolerance = TOLERANCE[dtype]
  assert abs(float(loss) - float(want)) <= tolerance['loss'] * abs(float(want))
  for got, expected in zip(grads, want_grads):
    assert got.shape == expected.shape and got.dtype == expected.dtype
    assert _relative(got, expected) <= tolerance['grad']
  if zero_weights:
    # A weight of 0 gives its token's row no gradient at all.
    silent = (jnp.arange(l) % 3 == 0)
    assert float(jnp.max(jnp.abs(grads[0][:, silent]))) == 0.0
  # The loss under differentiation is the undifferentiated loss to the bit.
  assert float(loss) == float(jax.jit(blocked)(hidden, param))


def _count(program, op):
  """Instructions ``op`` in a compiled program; the head's products are
  the only products these programs hold."""
  return len(re.findall(r' {}\('.format(op), program))


def test_the_differentiated_program_forms_each_block_s_logits_once():
  hidden, head, targets, weights = _inputs(2, 32, True)
  loss = lambda h, w: transformer_lib.blocked_cross_entropy(
      h, w, targets, weights, 16, jnp.bfloat16)
  trained = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
      hidden, head).compile().as_text()
  # One loop; in it the logits, dh and dW: three products over the
  # vocabulary (a forward loop and its transpose held two loops and four).
  assert _count(trained, 'while') == 1
  assert _count(trained, 'dot') == 3
  primal = jax.jit(loss).lower(hidden, head).compile().as_text()
  # Undifferentiated: the logits alone, one product a block.
  assert _count(primal, 'while') == 1
  assert _count(primal, 'dot') == 1


def test_a_differentiated_trace_sets_the_vocab_products_gauge():
  hidden, head, targets, weights = _inputs(2, 32, False)
  loss = lambda h, w: transformer_lib.blocked_cross_entropy(
      h, w, targets, weights, 16, jnp.bfloat16)
  gauge = get_registry().gauge('head_loss/vocab_products')
  gauge.set(0.0)
  jax.jit(loss).lower(hidden, head)
  assert gauge.value == 0.0
  jax.jit(jax.grad(loss, argnums=(0, 1))).lower(hidden, head)
  assert gauge.value == 3.0


def test_targets_and_weights_take_no_cotangent():
  hidden, head, targets, weights = _inputs(1, 16, False)
  d_weights = jax.grad(lambda w: transformer_lib.blocked_cross_entropy(
      hidden, head, targets, w, 16, jnp.float32))(weights)
  np.testing.assert_array_equal(d_weights, np.zeros_like(weights))
