"""Parallel-layer tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tensor2robot_tpu import parallel
from tensor2robot_tpu.parallel import collectives


class TestMesh:

  def test_default_all_data(self):
    mesh = parallel.create_mesh()
    assert mesh.shape['data'] == 8
    assert mesh.shape['fsdp'] == 1 and mesh.shape['model'] == 1

  def test_explicit_axes(self):
    mesh = parallel.create_mesh({'data': 2, 'fsdp': 2, 'model': 2})
    shape = dict(mesh.shape)
    assert (shape['data'], shape['fsdp'], shape['model']) == (2, 2, 2)
    # Unrequested default axes (expert, pipe, future ones) exist at size 1.
    assert all(v == 1 for k, v in shape.items()
               if k not in ('data', 'fsdp', 'model'))

  def test_infer_axis(self):
    mesh = parallel.create_mesh({'data': -1, 'model': 2})
    assert mesh.shape['data'] == 4

  def test_bad_sizes_raise(self):
    with pytest.raises(ValueError, match='require'):
      parallel.create_mesh({'data': 3, 'model': 2})


class TestSharding:

  def test_shard_batch_and_replicate(self):
    mesh = parallel.create_mesh()
    batch = {'x': np.arange(16, dtype=np.float32).reshape(16, 1)}
    sharded = parallel.shard_batch(batch, mesh)
    assert sharded['x'].sharding.spec == P('data')

  def test_fsdp_spec_selection(self):
    mesh = parallel.create_mesh({'data': 2, 'fsdp': 4})
    big = jnp.zeros((1024, 64))
    spec = parallel.fsdp_param_spec(big, mesh, min_size_to_shard=1)
    assert spec == P('fsdp', None)
    small = jnp.zeros((3,))
    assert parallel.fsdp_param_spec(small, mesh) == P()
    indivisible = jnp.zeros((37, 33))
    assert parallel.fsdp_param_spec(indivisible, mesh,
                                    min_size_to_shard=1) == P()

  def test_gradient_psum_from_sharding(self):
    """Batch sharded over data + replicated params -> correct global grad."""
    mesh = parallel.create_mesh()
    w = jax.device_put(jnp.ones((1,)), parallel.replicated(mesh))
    x = jax.device_put(jnp.arange(8.0).reshape(8, 1),
                       parallel.batch_sharding(mesh))

    @jax.jit
    def grad_fn(w, x):
      return jax.grad(lambda w: jnp.mean(x * w))(w)

    g = grad_fn(w, x)
    np.testing.assert_allclose(np.asarray(g), [np.arange(8).mean()],
                               rtol=1e-6)


class TestCollectives:

  def test_psum_pmean_gather_scatter_ring(self):
    mesh = parallel.create_mesh()

    @collectives.sharded_fn(mesh, in_specs=P('data'), out_specs=P('data'))
    def roundtrip(x):
      total = collectives.psum(jnp.sum(x), 'data')
      mean = collectives.pmean(jnp.sum(x), 'data')
      gathered = collectives.all_gather(x, 'data')
      scattered = collectives.reduce_scatter(gathered, 'data')
      rung = collectives.ring_permute(jnp.sum(x), 'data')
      return x * 0 + total + mean + jnp.sum(scattered) - jnp.sum(x) * 8 + rung * 0

    x = jnp.arange(8.0)
    out = roundtrip(x)
    total = 28.0
    mean = total / 8
    np.testing.assert_allclose(np.asarray(out)[0], total + mean, rtol=1e-6)

  def test_cross_replica_mean(self):
    mesh = parallel.create_mesh()

    @collectives.sharded_fn(mesh, in_specs=P('data'), out_specs=P('data'))
    def mean_stats(x):
      stats = {'mu': jnp.mean(x)}
      synced = collectives.cross_replica_mean(stats, 'data')
      return jnp.broadcast_to(synced['mu'], x.shape)

    out = mean_stats(jnp.arange(8.0))
    np.testing.assert_allclose(np.asarray(out), np.full((8,), 3.5), rtol=1e-6)


class TestRingAttention:

  def _qkv(self, b=2, l=32, h=4, d=16, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, l, h, d).astype(np.float32) * 0.3,
                             dtype)
    return mk(), mk(), mk()

  def test_matches_reference_full(self):
    mesh = parallel.create_mesh()
    q, k, v = self._qkv()
    expected = parallel.reference_attention(q, k, v)
    got = parallel.ring_self_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-5)

  def test_matches_reference_causal(self):
    mesh = parallel.create_mesh()
    q, k, v = self._qkv(seed=3)
    expected = parallel.reference_attention(q, k, v, causal=True)
    got = parallel.ring_self_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-5)

  def test_bfloat16_inputs(self):
    mesh = parallel.create_mesh()
    q, k, v = self._qkv(dtype=jnp.bfloat16, seed=5)
    expected = parallel.reference_attention(q, k, v, causal=True)
    got = parallel.ring_self_attention(q, k, v, mesh, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expected, np.float32), atol=3e-2)

  def test_sequence_sharded_inputs_stay_sharded(self):
    mesh = parallel.create_mesh()
    q, k, v = self._qkv(l=64)
    seq_sharding = NamedSharding(mesh, P(None, 'data', None, None))
    q = jax.device_put(q, seq_sharding)
    k = jax.device_put(k, seq_sharding)
    v = jax.device_put(v, seq_sharding)

    @jax.jit
    def run(q, k, v):
      return parallel.ring_self_attention(q, k, v, mesh, causal=True)

    out = run(q, k, v)
    assert out.sharding.spec == P(None, 'data', None, None)
    expected = parallel.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)

  def test_long_sequence_memory_scales(self):
    """1024-long sequence over 8 devices: each shard sees 128 q rows."""
    mesh = parallel.create_mesh()
    q, k, v = self._qkv(b=1, l=1024, h=2, d=8, seed=9)
    got = parallel.ring_self_attention(q, k, v, mesh, causal=True)
    expected = parallel.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-4)

  @pytest.mark.parametrize('causal', [True, False])
  @pytest.mark.parametrize('use_pallas', [False, True])
  def test_gradients_match_reference(self, causal, use_pallas):
    """The memory-efficient ring backward (blockwise recompute + dk/dv
    accumulators riding the ring) matches the single-device oracle's
    gradients for q, k, AND v — pallas-forward path included."""
    mesh = parallel.create_mesh()
    # The ring machinery (rotating dk/dv accumulators, cross-hop causal
    # masks) only executes on a REAL multi-device mesh — guard against
    # this test passing vacuously on a single-device runtime.
    assert mesh.size >= 8, mesh
    q, k, v = self._qkv(b=2, l=64, h=2, d=16, seed=3)

    def loss_ring(q, k, v):
      return jnp.sum(jnp.sin(parallel.ring_self_attention(
          q, k, v, mesh, causal=causal, use_pallas=use_pallas)))

    def loss_ref(q, k, v):
      return jnp.sum(jnp.sin(parallel.reference_attention(
          q, k, v, causal=causal)))

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', g_ring, g_ref):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                 err_msg='d' + name)


class TestTensorParallel:
  """Megatron-style TP over the 'model' axis (TP_RULES_TRANSFORMER).

  Validated the way the multichip dryrun does: the SAME seq2act train step
  jitted over a data x model mesh with TP param shardings must (a) compile
  and run, (b) actually shard the matched params |model|-ways, and
  (c) reproduce the replicated step's numerics (GSPMD closes the partial
  sums with psums over 'model'; the math is identical).
  """

  def _model(self, mesh, tp_axis):
    from tensor2robot_tpu.research.seq2act import Seq2ActBCModel

    return Seq2ActBCModel(
        episode_length=4, action_size=2, vocab_size=8, img_res=(32, 32),
        src_img_res=(36, 36), tokens_per_frame=4, embed_dim=32,
        num_layers=2, num_heads=4, head_dim=8, mlp_dim=64,
        tokenizer_widths=(8, 8, 8, 16), attention_mode='xla',
        mesh=mesh, tp_axis=tp_axis)

  def _batch(self):
    rng = np.random.RandomState(0)
    frames = rng.randint(0, 255, (8, 4, 36, 36, 3), dtype=np.uint8)
    actions = rng.rand(8, 4, 2).astype(np.float32) * 2 - 1
    return frames, actions

  def _run_step(self, mesh, tp_axis, tp_rules):
    import tempfile

    from tensor2robot_tpu.data.input_generators import (
        DefaultRandomInputGenerator,
    )
    from tensor2robot_tpu.modes import ModeKeys
    from tensor2robot_tpu.specs import SpecStruct
    from tensor2robot_tpu.trainer import Trainer

    model = self._model(mesh, tp_axis)
    frames, actions = self._batch()
    # IN-spec (raw uint8) batch: the trainer preprocesses inside the step.
    features = SpecStruct(image=frames)
    labels = SpecStruct(action=actions)
    with tempfile.TemporaryDirectory() as tmp:
      trainer = Trainer(model, tmp, mesh=mesh, tp_rules=tp_rules,
                        async_checkpoints=False,
                        save_checkpoints_steps=10**9)
      state = trainer.init_state(features, labels)
      step_fn = trainer._compile_train_step()
      rng = jax.device_put(
          jax.random.PRNGKey(3),
          jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
      batch = trainer._put_batch(
          {'features': features.to_dict(), 'labels': labels.to_dict()})
      state, metrics = step_fn(state, batch['features'], batch['labels'],
                               rng)
      sharding_of = {
          '/'.join(str(getattr(k, 'key', k)) for k in path): leaf.sharding
          for path, leaf in jax.tree_util.tree_flatten_with_path(
              state.params)[0]}
      trainer.close()
    return float(metrics['loss']), sharding_of

  def test_tp_step_matches_replicated(self):
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.parallel.sharding import TP_RULES_TRANSFORMER

    mesh_tp = parallel.create_mesh({'data': 2, 'model': 4})
    loss_tp, shardings = self._run_step(mesh_tp, 'model',
                                        TP_RULES_TRANSFORMER)

    mesh_dp = parallel.create_mesh({'data': 8})
    loss_dp, _ = self._run_step(mesh_dp, None, None)

    assert np.isfinite(loss_tp)
    np.testing.assert_allclose(loss_tp, loss_dp, rtol=2e-5)

    # The qkv/mlp kernels really are split over 'model'.
    qkv = [s for path, s in shardings.items()
           if path.endswith('attn/qkv/kernel')]
    mlp_in = [s for path, s in shardings.items()
              if path.endswith('mlp_in/kernel')]
    assert qkv and mlp_in
    for s in qkv + mlp_in:
      assert 'model' in str(s.spec), s.spec
    # Non-matching params stay replicated.
    tok = [s for path, s in shardings.items() if 'tokenizer' in path]
    assert tok and all('model' not in str(s.spec) for s in tok)

  def test_tp_indivisible_kernel_falls_back_to_replication(self):
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.parallel.sharding import (
        TP_RULES_TRANSFORMER,
        tp_param_spec,
    )

    mesh = parallel.create_mesh({'data': 1, 'model': 8})

    class _P:
      shape = (32, 30)
      size = 32 * 30
    # 30 % 8 != 0: the rule declines and the param stays replicated.
    assert tp_param_spec('net/attn/qkv/kernel', _P, mesh,
                         TP_RULES_TRANSFORMER) is None

  def test_tp_head_indivisible_raises_at_trace(self):
    """The param rule can't see head boundaries (it checks the flat
    H*3*Dh dim), so MultiHeadAttention must reject head counts the model
    axis doesn't divide before anything gets mis-sharded."""
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.layers.transformer import MultiHeadAttention

    mesh = parallel.create_mesh({'data': 1, 'model': 8})
    mha = MultiHeadAttention(num_heads=4, head_dim=8, attention_mode='xla',
                             mesh=mesh, tp_axis='model')
    with pytest.raises(ValueError, match='num_heads'):
      mha.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)))


class TestPipelineParallel:
  """GPipe pipeline (parallel/pipeline.py) vs sequential oracle."""

  def _stages(self, s=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        'w': jnp.asarray(rng.randn(s, d, d).astype(np.float32) * 0.3),
        'b': jnp.asarray(rng.randn(s, d).astype(np.float32) * 0.1),
    }

  @staticmethod
  def _stage_fn(params, x):
    return jnp.tanh(x @ params['w'] + params['b'])

  def _oracle(self, params, x_mb):
    s = params['w'].shape[0]
    y = x_mb
    for i in range(s):
      y = self._stage_fn(jax.tree.map(lambda p: p[i], params), y)
    return y

  def test_matches_sequential(self):
    from tensor2robot_tpu.parallel import pipeline

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})
    params = self._stages()
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(6, 3, 16).astype(np.float32))  # M=6, mb=3
    got = pipeline.pipeline_apply(self._stage_fn, params, x, mesh,
                                  axis='pipe')
    ref = self._oracle(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

  def test_gradients_match_sequential(self):
    from tensor2robot_tpu.parallel import pipeline

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})
    params = self._stages(seed=2)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 2, 16).astype(np.float32))

    def loss_pipe(p):
      return jnp.sum(jnp.sin(
          pipeline.pipeline_apply(self._stage_fn, p, x, mesh, axis='pipe')))

    def loss_ref(p):
      return jnp.sum(jnp.sin(self._oracle(p, x)))

    g_pipe = jax.grad(loss_pipe)(params)
    g_ref = jax.grad(loss_ref)(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5), g_pipe, g_ref)

  def test_single_microbatch_and_helpers(self):
    from tensor2robot_tpu.parallel import pipeline

    mesh = parallel.create_mesh({'pipe': 8})
    params = self._stages(s=8, seed=4)
    rng = np.random.RandomState(5)
    full = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    x = pipeline.microbatch(full, 1)
    assert x.shape == (1, 8, 16)
    got = pipeline.unmicrobatch(
        pipeline.pipeline_apply(self._stage_fn, params, x, mesh,
                                axis='pipe'))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(self._oracle(params, x))[0],
                               atol=1e-5)

  def test_remat_matches_no_remat_gradients(self):
    from tensor2robot_tpu.parallel import pipeline

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})
    params = self._stages(seed=7)
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(4, 2, 16).astype(np.float32))

    def loss(p, remat):
      return jnp.sum(jnp.sin(pipeline.pipeline_apply(
          self._stage_fn, p, x, mesh, axis='pipe', remat=remat)))

    g_plain = jax.grad(lambda p: loss(p, False))(params)
    g_remat = jax.grad(lambda p: loss(p, True))(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-6), g_plain, g_remat)

  def test_bad_configs_raise(self):
    from tensor2robot_tpu.parallel import pipeline

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})
    params = self._stages(s=3)  # wrong stage count
    with pytest.raises(ValueError, match='stage count'):
      pipeline.pipeline_apply(self._stage_fn, params, jnp.zeros((2, 2, 16)),
                              mesh, axis='pipe')
    with pytest.raises(ValueError, match='no .* axis'):
      # A hand-built mesh without the pipe axis (create_mesh always adds
      # a size-1 'pipe', which fails the stage-count check instead).
      bare = jax.sharding.Mesh(np.array(jax.devices()), ('data',))
      pipeline.pipeline_apply(self._stage_fn, self._stages(),
                              jnp.zeros((2, 2, 16)), bare, axis='pipe')
    with pytest.raises(ValueError, match='microbatches'):
      pipeline.microbatch(jnp.zeros((7, 4)), 2)

  def test_pipelined_transformer_matches_sequential(self):
    """CausalTransformer(pipe_axis=...) == the same stack run serially.

    Same stacked params evaluated both ways: pipelined over pipe(4) and
    as a plain loop via the block template.
    """
    from tensor2robot_tpu.layers import transformer as transformer_lib

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})
    model = transformer_lib.CausalTransformer(
        num_layers=4, num_heads=2, head_dim=8, mlp_dim=32, max_length=16,
        attention_mode='xla', mesh=mesh, pipe_axis='pipe',
        pipeline_microbatches=2)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 12, 16).astype(np.float32))
    variables = model.init(jax.random.PRNGKey(0), x)
    got, aux = model.apply(variables, x)
    assert float(aux) == 0.0

    # Oracle: run the same stacked block params sequentially (leading
    # dims [S, k] — stage-major, k blocks per stage).
    ref = self._sequential_oracle(variables, x, stages=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

  @staticmethod
  def _sequential_oracle(variables, x, stages):
    import flax.linen as nn

    from tensor2robot_tpu.layers import transformer as transformer_lib

    block = transformer_lib.TransformerBlock(
        num_heads=2, head_dim=8, mlp_dim=32, attention_mode='xla',
        causal=True)
    stacked = variables['params']['pipe_blocks']
    pos = variables['params']['pos_embedding']
    h = x + jnp.asarray(pos)[None, :x.shape[1]]
    k = jax.tree_util.tree_leaves(stacked)[0].shape[1]
    for i in range(stages):
      for j in range(k):
        h, _ = block.apply(
            {'params': jax.tree.map(lambda p: p[i][j], stacked)}, h)
    ln = variables['params']['ln_final']
    return nn.LayerNorm().apply({'params': ln}, h)

  def test_pipelined_virtual_stages_match_sequential(self):
    """8 layers on 4 stages: each stage applies 2 consecutive blocks."""
    from tensor2robot_tpu.layers import transformer as transformer_lib

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})
    model = transformer_lib.CausalTransformer(
        num_layers=8, num_heads=2, head_dim=8, mlp_dim=32, max_length=16,
        attention_mode='xla', mesh=mesh, pipe_axis='pipe',
        pipeline_microbatches=2)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 12, 16).astype(np.float32))
    variables = model.init(jax.random.PRNGKey(1), x)
    got, _ = model.apply(variables, x)
    ref = self._sequential_oracle(variables, x, stages=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

  def test_pipelined_indivisible_layers_raise(self):
    from tensor2robot_tpu.layers import transformer as transformer_lib

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})
    model = transformer_lib.CausalTransformer(
        num_layers=6, num_heads=2, head_dim=8, mlp_dim=32, max_length=16,
        attention_mode='xla', mesh=mesh, pipe_axis='pipe')
    with pytest.raises(ValueError, match='divisible'):
      model.init(jax.random.PRNGKey(0), jnp.zeros((2, 12, 16)))

  def test_pipelined_transformer_param_rule(self):
    from tensor2robot_tpu.parallel.sharding import (
        PP_RULES_TRANSFORMER,
        tp_param_spec,
    )

    mesh = parallel.create_mesh({'pipe': 4, 'data': 2})

    class _Leaf:
      shape = (4, 32, 96)
      size = 4 * 32 * 96
    spec = tp_param_spec(
        'params/transformer/pipe_blocks/attn/qkv/kernel', _Leaf, mesh,
        PP_RULES_TRANSFORMER)
    assert spec == P('pipe')


class TestShardedCheckpoint:
  """Orbax save/restore round-trip of a TP-sharded train state."""

  def _make_trainer(self, mesh, d, tokenizer_widths=(8, 8, 8, 16),
                    use_fsdp=False, save_steps=2):
    from tensor2robot_tpu.parallel.sharding import TP_RULES_TRANSFORMER
    from tensor2robot_tpu.research.seq2act import Seq2ActBCModel
    from tensor2robot_tpu.trainer import Trainer

    model = Seq2ActBCModel(
        episode_length=4, action_size=2, vocab_size=8, img_res=(32, 32),
        src_img_res=(36, 36), tokens_per_frame=4, embed_dim=32,
        num_layers=2, num_heads=4, head_dim=8, mlp_dim=64,
        tokenizer_widths=tokenizer_widths, attention_mode='xla',
        mesh=mesh, tp_axis='model')
    return Trainer(model, d, mesh=mesh, tp_rules=TP_RULES_TRANSFORMER,
                   use_fsdp=use_fsdp, async_checkpoints=False,
                   save_checkpoints_steps=save_steps)

  def test_tp_checkpoint_roundtrip(self, tmp_path):
    """A fresh Trainer restores the sharded checkpoint into its
    NamedSharding template, keeps the 'model' placement, and resumes the
    step count — the restore path itself runs on sharded leaves."""
    from tensor2robot_tpu.data.input_generators import (
        DefaultRandomInputGenerator,
    )

    mesh = parallel.create_mesh({'data': 2, 'model': 4})
    gen = DefaultRandomInputGenerator(batch_size=8)
    d = str(tmp_path / 'run')

    trainer = self._make_trainer(mesh, d)
    state = trainer.train(gen, max_train_steps=2)
    assert int(jax.device_get(state.step)) == 2
    trainer.close()

    trainer2 = self._make_trainer(mesh, d)
    state2 = trainer2.train(gen, max_train_steps=4)  # must resume at 2
    assert int(jax.device_get(state2.step)) == 4
    qkv = [l for p, l in jax.tree_util.tree_flatten_with_path(
               state2.params)[0]
           if jax.tree_util.keystr(p).endswith("qkv']['kernel']")]
    assert qkv and all('model' in str(l.sharding.spec) for l in qkv)
    trainer2.close()

  def test_tp_composes_with_fsdp(self, tmp_path):
    """data x fsdp x model: TP params shard over 'model', everything else
    falls back to FSDP ('fsdp') or replication — the composition
    docs/parallelism.md promises."""
    from tensor2robot_tpu.data.input_generators import (
        DefaultRandomInputGenerator,
    )

    mesh = parallel.create_mesh({'data': 2, 'fsdp': 2, 'model': 2})
    # The widened last tokenizer width makes its conv3 kernel
    # [3, 3, 8, 256] (18,432 elems) cross fsdp_param_spec's
    # min_size_to_shard (2**14), so the FSDP fallback actually engages
    # in this tiny config.
    gen = DefaultRandomInputGenerator(batch_size=8)
    trainer = self._make_trainer(mesh, str(tmp_path),
                                 tokenizer_widths=(8, 8, 8, 256),
                                 use_fsdp=True, save_steps=10**9)
    state = trainer.train(gen, max_train_steps=1)
    assert int(jax.device_get(state.step)) == 1
    shardings = {
        jax.tree_util.keystr(path): str(leaf.sharding.spec)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            state.params)[0]}
    qkv = {p: s for p, s in shardings.items()
           if p.endswith("qkv']['kernel']")}
    assert qkv and all('model' in s for s in qkv.values()), qkv
    # The large non-TP param (tokenizer conv3 kernel) takes the FSDP path.
    fsdp_leaves = [p for p, s in shardings.items() if 'fsdp' in s]
    assert any('conv3' in p for p in fsdp_leaves), shardings
    trainer.close()
