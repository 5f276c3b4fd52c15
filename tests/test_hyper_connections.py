"""The four stream kernels of manifold-constrained hyper-connections
(parallel/hyper_connections.py) on the interpreter against the plain
``jax.numpy`` formulation, forward and ``jax.grad``; the maps' hand-written
backward against JAX's gradient of the same maps; Sinkhorn-Knopp's result
doubly stochastic; the bytes a kernel says it moves; one copy of each kernel
however many sublayers call them."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.parallel import hyper_connections as hc

KW = dict(n=4, iters=20, eps=1e-6, clamp=30.0)


def _relative(got, want):
  return float(jnp.max(jnp.abs(got - want)) /
               (jnp.max(jnp.abs(want)) + 1e-30))


def _inputs(rows, c, seed=0):
  keys = jax.random.split(jax.random.PRNGKey(seed), 5)
  x = jax.random.normal(keys[0], (rows, 4 * c))
  phi = 0.05 * jax.random.normal(keys[1], (4 * c, 24))
  alpha = jnp.array([0.8, 1.1, 0.9])
  bias = jax.random.normal(keys[2], (24,))
  f = jax.random.normal(keys[3], (rows, c))
  return x, phi, alpha, bias, f


def _sublayer(mode, c, x, phi, alpha, bias, f):
  """pre, a sublayer that reads h, post: what a block does with the state."""
  h, maps, state = hc.hc_pre(x, phi, alpha, bias, mode=mode, interpret=True,
                             **KW)
  out = hc.hc_post(state, f * jnp.tanh(h[:, :c]), maps, n=4, mode=mode,
                   interpret=True)
  return h, maps, out


class TestTheKernelsOnTheInterpreter:

  @pytest.mark.parametrize('rows,c', [(128, 128), (512, 128)],
                           ids=['one_tile', 'two_tiles_of_256'])
  def test_forward_and_every_gradient(self, rows, c):
    args = _inputs(rows, c)
    got = _sublayer('pallas', c, *args)
    want = _sublayer('xla', c, *args)
    for name, a, b in zip(('h', 'maps', 'state'), got, want):
      assert _relative(a, b) < 1e-5, name
    weight = jax.random.normal(jax.random.PRNGKey(9), want[2].shape)

    def loss(mode, *args):
      h, _, out = _sublayer(mode, c, *args)
      return jnp.sum(out * weight) + 0.1 * jnp.sum(h * h)

    grads = [jax.grad(functools.partial(loss, mode), (0, 1, 2, 3, 4))(*args)
             for mode in ('pallas', 'xla')]
    for name, a, b in zip(('x', 'phi', 'alpha', 'bias', 'f'), *grads):
      assert a.shape == b.shape, name
      assert _relative(a, b) < 2e-5, name

  def test_bfloat16_projection_and_sublayer_output(self):
    x, phi, alpha, bias, f = _inputs(128, 128, seed=3)
    phi, f = phi.astype(jnp.bfloat16), f.astype(jnp.bfloat16)
    got = _sublayer('pallas', 128, x, phi, alpha, bias, f)
    want = _sublayer('xla', 128, x, phi, alpha, bias, f)
    assert got[2].dtype == jnp.float32
    for a, b in zip(got, want):
      assert _relative(a, b) < 1e-3

  def test_shapes_the_kernels_do_not_take_go_the_plain_way(self):
    x, phi, alpha, bias, _ = _inputs(24, 32)
    assert not hc.supported(24, 32)
    assert hc.supported(4096, 3584)
    h, _, state = hc.hc_pre(x, phi, alpha, bias, **KW)
    assert state is x
    np.testing.assert_array_equal(
        np.asarray(h), np.asarray(hc.hc_pre_reference(x, phi, alpha, bias,
                                                      **KW)[0]))
    with pytest.raises(ValueError):
      hc.hc_pre(x, phi, alpha, bias, mode='pallas', **KW)


class TestTheMaps:

  def test_the_hand_written_backward_is_the_gradient_of_the_maps(self):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    z = list(jax.random.normal(keys[0], (24, 1, 8)) * 2)
    bias = list(jax.random.normal(keys[1], (24,)))
    cotangent = list(jax.random.normal(keys[2], (24, 1, 8)))
    alpha = (0.7, 1.3, 0.9)

    def maps(pre_activations):
      # The maps of z' = (u - b) / a: their gradient in u is the
      # pre-activations' gradient that ``_maps_backward`` returns.
      groups = [0] * 4 + [1] * 4 + [2] * 16
      z = [(u - b) / alpha[g] for u, b, g in zip(pre_activations, bias,
                                                  groups)]
      pre, post, res, _ = hc.stream_maps(z, alpha, bias, 4, 20, 1e-6, 30.0)
      return pre + post + res

    u = [alpha[g] * w + b for w, b, g in zip(
        z, bias, [0] * 4 + [1] * 4 + [2] * 16)]
    _, vjp = jax.vjp(maps, u)
    want = vjp(cotangent)[0]
    pre, post, _, saved = hc.stream_maps(z, alpha, bias, 4, 20, 1e-6, 30.0)
    got = hc._maps_backward(cotangent[:4], cotangent[4:8], cotangent[8:],
                            pre, post, saved, 4, 30.0)
    for k, (a, b) in enumerate(zip(got, want)):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                 atol=2e-6, err_msg=str(k))

  def test_sinkhorn_leaves_a_doubly_stochastic_matrix(self):
    x, phi, alpha, bias, _ = _inputs(64, 32)
    _, maps = hc.hc_pre_reference(x, phi, alpha, 3.0 * bias, **KW)
    res = maps[:, 8:24].reshape(-1, 4, 4)
    assert float(jnp.min(res)) > 0
    np.testing.assert_allclose(np.asarray(jnp.sum(res, axis=1)), 1.0,
                               atol=1e-5)
    assert float(hc.res_stochastic_error(maps, 4)) < 0.05
    one = hc.hc_pre_reference(x, phi, alpha, 3.0 * bias,
                              **dict(KW, iters=1))[1]
    assert float(hc.res_stochastic_error(one, 4)) > float(
        hc.res_stochastic_error(maps, 4))
    # pre in (0, 1), post in (0, 2), nothing in the padding lanes.
    assert 0 < float(jnp.min(maps[:, :4])) and float(jnp.max(maps[:, :4])) < 1
    assert float(jnp.max(maps[:, 4:8])) < 2
    assert float(jnp.max(jnp.abs(maps[:, 24:]))) == 0


class TestTheKernelsAsAProgramSeesThem:

  def test_the_bytes_a_kernel_says_it_moves_a_token(self):
    """Each kernel's gauge: its operands and results a token, counted from
    the shapes the kernel takes and gives at the cell's size (less phi, its
    gradient and the scalars, which move once a call)."""
    from tensor2robot_tpu.observability import get_registry

    # The gauge is set when a kernel is TRACED: another test of this process
    # may have traced these shapes already.
    jax.clear_caches()
    rows, c = 4096, 3584
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims,
                                                                   dtype)
    x, f = shape(rows, 4 * c), shape(rows, c, dtype=jnp.bfloat16)
    maps, h = shape(rows, 32), shape(rows, c)
    phi = shape(4 * c, 24, dtype=jnp.bfloat16)
    alpha, bias = shape(3), shape(24)
    per_token = lambda *arrays: sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(arrays)
        if a.shape[0] == rows) / rows
    calls = {
        'hc_pre_fwd': ((x, phi, alpha, bias), lambda: jax.eval_shape(
            functools.partial(hc.hc_pre_fwd, **KW), x, phi, alpha, bias)),
        'hc_post_fwd': ((x, f, maps), lambda: jax.eval_shape(
            functools.partial(hc.hc_post_fwd, n=4), x, f, maps)),
        'hc_post_bwd': ((x, f, maps, x), lambda: jax.eval_shape(
            functools.partial(hc.hc_post_bwd, n=4), x, f, maps, x)),
        'hc_pre_bwd': ((x, phi, alpha, bias, h, x, maps),
                       lambda: jax.eval_shape(
                           functools.partial(hc.hc_pre_bwd, **KW), x, phi,
                           alpha, bias, h, x, maps)),
    }
    for kernel, (operands, results) in calls.items():
      out = results()
      assert hc.call_bytes(kernel, 4, c) == per_token(operands, out), kernel
      assert get_registry().gauge('hc/bytes_per_token/' + kernel).value == \
          hc.call_bytes(kernel, 4, c)
    assert sorted(calls) == sorted(hc.KERNELS)

  def test_a_program_of_two_sublayers_holds_one_copy_of_each_kernel(self):
    """The kernels are ``jax.jit`` functions: however many sublayers call
    them, the program has each once, under the name the trace finds."""

    def two_sublayers(x, phi, alpha, bias, f):
      for _ in range(2):
        _, _, x = _sublayer('pallas', 128, x, phi, alpha, bias, f)
      return jnp.sum(jnp.sin(x))

    args = [jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in _inputs(256, 128)]
    text = jax.jit(jax.grad(two_sublayers, (0, 1, 2, 3, 4))).lower(
        *args).as_text()
    assert sorted(re.findall(r'func\.func private @(hc_\w+)\(', text)) == \
        sorted(hc.KERNELS)
    for kernel in hc.KERNELS:
      assert len(re.findall(r'call @{}\('.format(kernel), text)) == 2, kernel
