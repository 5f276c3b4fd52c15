"""MoE layer (layers/moe.py) + expert parallelism (EP_RULES_MOE)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers.moe import MoEMlp


def _dense_oracle(variables, x, top_k):
  """Per-token expert MLP computed densely (no capacity, no dispatch)."""
  params = variables['params']
  w_r, b_r = params['router']['kernel'], params['router']['bias']
  w_in, w_out = params['w_in'], params['w_out']
  logits = x @ w_r + b_r
  probs = jax.nn.softmax(logits, axis=-1)
  topv, topi = jax.lax.top_k(probs, top_k)
  if top_k == 1:
    gates = topv  # Switch: raw router prob scales the expert output.
  else:
    gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
  out = jnp.zeros_like(x)
  for j in range(top_k):
    idx = topi[..., j]                         # [B, L]
    wi = w_in[idx]                             # [B, L, d, h]
    wo = w_out[idx]
    h = jax.nn.gelu(jnp.einsum('bld,bldh->blh', x, wi))
    out = out + gates[..., j:j + 1] * jnp.einsum('blh,blhd->bld', h, wo)
  return out


class TestMoEMlp:

  def _init(self, e=4, k=2, d=16, h=32, b=2, l=24, capacity_factor=None):
    # capacity_factor >= e/k guarantees no token is dropped, so the
    # dispatch path must reproduce the dense oracle exactly.
    cf = capacity_factor if capacity_factor is not None else float(e)
    layer = MoEMlp(num_experts=e, expert_dim=h, top_k=k,
                   capacity_factor=cf)
    rng = np.random.RandomState(0)
    x = rng.randn(b, l, d).astype(np.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)
    return layer, variables, jnp.asarray(x)

  def test_matches_dense_oracle_when_capacity_sufficient(self):
    layer, variables, x = self._init()
    out, aux = layer.apply(variables, x)
    ref = _dense_oracle(variables, x, top_k=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert np.isfinite(float(aux))

  def test_top1_matches_oracle(self):
    layer, variables, x = self._init(k=1)
    out, _ = layer.apply(variables, x)
    ref = _dense_oracle(variables, x, top_k=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

  def test_top1_router_gets_task_gradient(self):
    """Switch top-1: the gate is the raw router prob, so the router
    kernel must receive gradient from the task loss alone (no aux)."""
    layer, variables, x = self._init(k=1)

    def task_loss(params):
      out, _ = layer.apply({'params': params}, x)
      return jnp.sum(out ** 2)

    grads = jax.grad(task_loss)(variables['params'])
    g_router = np.asarray(grads['router']['kernel'])
    assert np.abs(g_router).max() > 0.0, (
        'top-1 router kernel got zero task-loss gradient — gate '
        'renormalization must not collapse to 1.0 at k=1')

  def test_overflow_drops_not_corrupts(self):
    """Tiny capacity: outputs are a mix of routed tokens and exact zeros
    (dropped -> residual passthrough upstream), never garbage."""
    layer, variables, x = self._init(capacity_factor=0.25)
    out, _ = layer.apply(variables, x)
    ref = _dense_oracle(variables, x, top_k=2)
    out, ref = np.asarray(out), np.asarray(ref)
    # Every token's output is either (close to) its oracle value with
    # gates renormalized over the surviving subset, or all-zero when all
    # its choices overflowed. Check the all-zero set is non-empty and
    # that non-zero rows are finite.
    token_norm = np.abs(out).sum(-1)
    assert (token_norm == 0).any(), 'tiny capacity should drop something'
    assert np.isfinite(out).all()
    assert (token_norm > 0).any()
    del ref

  def test_aux_loss_prefers_balance(self):
    """Uniform routing gives aux ~= 1 (its minimum); collapsed routing is
    larger."""
    e = 4
    layer, variables, x = self._init(e=e, k=1)
    # Force uniform router: zero kernel/bias -> equal probs.
    params = jax.tree.map(lambda p: jnp.zeros_like(p),
                          variables['params']['router'])
    vu = {'params': dict(variables['params'], router=params)}
    _, aux_uniform = layer.apply(vu, x)
    # Force collapse onto expert 0 via a large bias.
    bias = jnp.zeros((e,)).at[0].set(50.0)
    pc = dict(variables['params'],
              router={'kernel': jnp.zeros_like(
                  variables['params']['router']['kernel']), 'bias': bias})
    _, aux_collapsed = layer.apply({'params': pc}, x)
    assert float(aux_uniform) == pytest.approx(1.0, abs=1e-3)
    assert float(aux_collapsed) > 2.0

  def test_expert_count_divisibility_check(self):
    from tensor2robot_tpu import parallel

    mesh = parallel.create_mesh({'data': 1, 'expert': 8})
    layer = MoEMlp(num_experts=4, expert_dim=8, mesh=mesh, ep_axis='expert')
    with pytest.raises(ValueError, match='num_experts'):
      layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 16)))


class TestExpertParallel:
  """EP through the full seq2act train step on a data x expert mesh."""

  def _run(self, mesh, ep_axis, tp_rules):
    import tempfile

    from tensor2robot_tpu.research.seq2act import Seq2ActBCModel
    from tensor2robot_tpu.specs import SpecStruct
    from tensor2robot_tpu.trainer import Trainer

    # capacity_factor = E/k: no token drops in EITHER routing regime, so
    # the grouped EP dispatch must match the single-group DP dispatch
    # exactly (layers/moe.py MoEMlp docstring).
    model = Seq2ActBCModel(
        episode_length=4, action_size=2, vocab_size=8, img_res=(32, 32),
        src_img_res=(36, 36), tokens_per_frame=4, embed_dim=32,
        num_layers=2, num_heads=4, head_dim=8, mlp_dim=32,
        tokenizer_widths=(8, 8, 8, 16), attention_mode='xla',
        mesh=mesh, moe_experts=4, moe_top_k=2, moe_capacity_factor=2.0,
        ep_axis=ep_axis)
    rng = np.random.RandomState(0)
    frames = rng.randint(0, 255, (8, 4, 36, 36, 3), dtype=np.uint8)
    actions = rng.rand(8, 4, 2).astype(np.float32) * 2 - 1
    features = SpecStruct(image=frames)
    labels = SpecStruct(action=actions)
    with tempfile.TemporaryDirectory() as tmp:
      trainer = Trainer(model, tmp, mesh=mesh, tp_rules=tp_rules,
                        async_checkpoints=False,
                        save_checkpoints_steps=10**9)
      state = trainer.init_state(features, labels)
      step_fn = trainer._compile_train_step()
      rng_d = jax.device_put(
          jax.random.PRNGKey(3),
          jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
      batch = trainer._put_batch(
          {'features': features.to_dict(), 'labels': labels.to_dict()})
      state, metrics = step_fn(state, batch['features'], batch['labels'],
                               rng_d)
      shardings = {
          jax.tree_util.keystr(path): leaf.sharding
          for path, leaf in jax.tree_util.tree_flatten_with_path(
              state.params)[0]}
      trainer.close()
    return float(metrics['loss']), shardings

  def test_ep_step_matches_replicated(self):
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.parallel.sharding import EP_RULES_MOE

    mesh_ep = parallel.create_mesh({'data': 2, 'expert': 4})
    loss_ep, shardings = self._run(mesh_ep, 'expert', EP_RULES_MOE)

    mesh_dp = parallel.create_mesh({'data': 8})
    loss_dp, _ = self._run(mesh_dp, None, None)

    assert np.isfinite(loss_ep)
    np.testing.assert_allclose(loss_ep, loss_dp, rtol=2e-5)

    w_in = [s for path, s in shardings.items() if path.endswith("'w_in']")]
    assert w_in and all('expert' in str(s.spec) for s in w_in), shardings

  def test_ep_layer_matches_dense_path(self):
    """The shard_map all-to-all execution equals the single-group einsum
    path on the same weights (capacity_factor = E/k: no drops)."""
    from tensor2robot_tpu import parallel

    mesh = parallel.create_mesh({'data': 2, 'expert': 4})
    dense = MoEMlp(num_experts=8, expert_dim=32, top_k=2,
                   capacity_factor=4.0)
    ep = MoEMlp(num_experts=8, expert_dim=32, top_k=2, capacity_factor=4.0,
                mesh=mesh, ep_axis='expert')
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 16, 16), jnp.float32)
    variables = dense.init(jax.random.PRNGKey(0), x)
    out_dense, aux_dense = dense.apply(variables, x)
    out_ep, aux_ep = ep.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_dense),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux_ep), float(aux_dense), rtol=1e-6)

  def test_ep_lowers_to_all_to_all(self):
    """The compiled EP program contains the forward+reverse all-to-all
    pair — the GShard communication pattern the layer hand-codes
    (VERDICT r4 item 2's EP collective assertion, at the layer level)."""
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.parallel.hlo_analysis import (
        compiled_collective_stats,
    )

    mesh = parallel.create_mesh({'data': 2, 'expert': 4})
    layer = MoEMlp(num_experts=8, expert_dim=32, top_k=2,
                   capacity_factor=4.0, mesh=mesh, ep_axis='expert')
    x = jnp.asarray(np.random.RandomState(0).randn(4, 16, 16), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    fn = jax.jit(lambda v, x: layer.apply(v, x)[0])
    stats = compiled_collective_stats(fn, variables, x)
    assert stats.get('all-to-all', {}).get('count', 0) >= 2, stats

  def test_ep_rejects_indivisible_token_dim(self):
    from tensor2robot_tpu import parallel

    mesh = parallel.create_mesh({'data': 2, 'expert': 4})
    layer = MoEMlp(num_experts=8, expert_dim=8, mesh=mesh,
                   ep_axis='expert')
    with pytest.raises(ValueError, match='token dim'):
      layer.init(jax.random.PRNGKey(0), jnp.zeros((2, 6, 16)))


class TestMoEDtypes:

  def test_bfloat16_activations_finite_and_close(self):
    """The bf16 path (production compute dtype): the router still runs
    in f32 (on the bf16-rounded input, so statistics match to input
    precision) and outputs stay near the f32 oracle."""
    layer32 = MoEMlp(num_experts=4, expert_dim=32, top_k=2,
                     capacity_factor=4.0)
    layer16 = MoEMlp(num_experts=4, expert_dim=32, top_k=2,
                     capacity_factor=4.0, dtype=jnp.bfloat16)
    rng = np.random.RandomState(3)
    x = rng.randn(2, 16, 8).astype(np.float32)
    variables = layer32.init(jax.random.PRNGKey(0), jnp.asarray(x))
    out32, aux32 = layer32.apply(variables, jnp.asarray(x))
    out16, aux16 = layer16.apply(variables, jnp.asarray(x, jnp.bfloat16))
    assert out16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out16, np.float32),
                               np.asarray(out32), atol=0.05, rtol=0.05)
    # Router runs in f32 in both; the only drift is the bf16-rounded
    # input it sees.
    np.testing.assert_allclose(float(aux16), float(aux32), rtol=1e-3)


class TestDroplessGateAndShares:
  """The dropless layer's gate activation is a field, and one chip's share
  of an eight-chip deployment (16 of 128 experts, top 8) adds up with the
  seven others to the uncut layer."""

  def _inputs(self, experts, d=16, width=8, tokens=48):
    from tensor2robot_tpu.layers import moe as moe_lib

    u = jax.random.normal(jax.random.PRNGKey(4), (tokens, d))
    logits = jax.random.normal(jax.random.PRNGKey(5), (tokens, experts))
    layer = moe_lib.DroplessMoE(num_experts=experts,
                                experts_held=(0, experts), expert_dim=width,
                                gate_activation='silu', block_rows=8)
    params = jax.tree.map(
        lambda x: 20 * x,
        layer.init(jax.random.PRNGKey(6), u,
                   moe_lib.route_top_k(logits, 8))['params'])
    return moe_lib, u, logits, params

  @staticmethod
  def _dense(params, u, logits, first, held, top_k, activation):
    values, index = jax.lax.top_k(logits, top_k)
    weight = jax.nn.softmax(values, -1)
    y = 0
    for e in range(held):
      gate = ((index == first + e) * weight).sum(-1)
      y = y + gate[:, None] * (
          (activation(u @ params['w_gate'][e]) * (u @ params['w_up'][e]))
          @ params['w_down'][e])
    return y

  @pytest.mark.parametrize('name, activation', [('relu', jax.nn.relu),
                                                ('silu', jax.nn.silu)])
  def test_the_gate_is_activated_as_the_field_says(self, name, activation):
    moe_lib, u, logits, params = self._inputs(8)
    layer = moe_lib.DroplessMoE(num_experts=8, experts_held=(0, 8),
                                expert_dim=8, gate_activation=name,
                                block_rows=8)
    route = lambda logits: moe_lib.route_top_k(logits, 8)
    y, _ = layer.apply({'params': params}, u, route(logits))
    np.testing.assert_allclose(
        y, self._dense(params, u, logits, 0, 8, 8, activation), atol=2e-5)
    got = jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply({'params': p}, u, route(logits))[0])))(params)
    want = jax.grad(lambda p: jnp.sum(jnp.sin(
        self._dense(p, u, logits, 0, 8, 8, activation))))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
      np.testing.assert_allclose(g, w, atol=5e-5)

  def test_relu_is_the_default_and_the_two_differ(self):
    moe_lib, u, logits, params = self._inputs(8)
    kwargs = dict(num_experts=8, experts_held=(0, 8), expert_dim=8,
                  block_rows=8)
    routing = moe_lib.route_top_k(logits, 8)
    assert moe_lib.DroplessMoE(**kwargs).gate_activation == 'relu'
    relu, _ = moe_lib.DroplessMoE(**kwargs).apply({'params': params}, u,
                                                  routing)
    silu, _ = moe_lib.DroplessMoE(gate_activation='silu', **kwargs).apply(
        {'params': params}, u, routing)
    assert float(jnp.max(jnp.abs(relu - silu))) > 1e-3
    with pytest.raises(ValueError, match='gate_activation'):
      moe_lib.DroplessMoE(gate_activation='gelu', **kwargs).apply(
          {'params': params}, u, routing)

  def test_the_eight_shares_of_sixteen_add_up_to_the_uncut_layer(self):
    moe_lib, u, logits, params = self._inputs(128)
    whole = self._dense(params, u, logits, 0, 128, 8, jax.nn.silu)
    total, pairs = 0, 0
    for first in range(0, 128, 16):
      share = jax.tree.map(lambda x: x[first:first + 16], params)
      y, stats = moe_lib.DroplessMoE(
          num_experts=128, experts_held=(first, 16), expert_dim=8,
          gate_activation='silu', block_rows=8).apply(
              {'params': share}, u, moe_lib.route_top_k(logits, 8))
      total = total + y
      pairs += float(stats['pairs_held'])
      assert float(stats['dropped_pairs']) == 0
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert pairs == 48 * 8
