"""Layer library tests: shapes + numerics (ref layers/*_test.py style)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import mdn, resnet, snail, tec, vision_layers
from tensor2robot_tpu.layers.spatial_softmax import spatial_softmax


class TestSpatialSoftmax:

  def test_shapes(self):
    features = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16, 5))
    points, maps = spatial_softmax(features)
    assert points.shape == (2, 10)
    assert maps.shape == (2, 12, 16, 5)
    np.testing.assert_allclose(
        np.sum(maps, axis=(1, 2)), np.ones((2, 5)), rtol=1e-5)

  def test_peaked_feature_localizes(self):
    """A single hot pixel recovers its own (x, y) position."""
    features = np.full((1, 9, 9, 1), -1e9, np.float32)
    features[0, 2, 6, 0] = 1e9  # row 2, col 6
    points, _ = spatial_softmax(jnp.asarray(features))
    x, y = float(points[0, 0]), float(points[0, 1])
    assert abs(x - (2.0 * 6 / 8 - 1.0)) < 1e-4
    assert abs(y - (2.0 * 2 / 8 - 1.0)) < 1e-4

  def test_gumbel_variant_runs(self):
    features = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 3))
    points, _ = spatial_softmax(features,
                                gumbel_rng=jax.random.PRNGKey(1))
    assert points.shape == (2, 6)


class TestMDN:

  def _gm(self, batch=4, k=3, d=2, seed=0):
    params = jax.random.normal(jax.random.PRNGKey(seed),
                               (batch, k + 2 * k * d))
    return mdn.get_mixture_distribution(params, k, d)

  def test_param_split_shapes(self):
    gm = self._gm()
    assert gm.alphas.shape == (4, 3)
    assert gm.mus.shape == (4, 3, 2)
    assert gm.sigmas.shape == (4, 3, 2)
    assert bool(jnp.all(gm.sigmas > 0))

  def test_bad_param_size_raises(self):
    with pytest.raises(ValueError, match='unexpected'):
      mdn.get_mixture_distribution(jnp.zeros((4, 7)), 3, 2)

  def test_log_prob_matches_single_gaussian(self):
    """K=1 mixture log-prob equals the analytic diagonal-normal one."""
    mu = np.array([0.5, -1.0], np.float32)
    raw_sigma = np.array([0.3, 0.7], np.float32)
    params = jnp.asarray(
        np.concatenate([[0.0], mu, raw_sigma])[None], jnp.float32)
    gm = mdn.get_mixture_distribution(params, 1, 2)
    x = jnp.asarray([[0.1, 0.2]], jnp.float32)
    sigma = np.log1p(np.exp(raw_sigma))
    expected = -0.5 * np.sum(((np.array([0.1, 0.2]) - mu) / sigma) ** 2)
    expected -= np.sum(np.log(sigma)) + np.log(2 * np.pi)
    np.testing.assert_allclose(
        float(mdn.mixture_log_prob(gm, x)[0]), expected, rtol=1e-5)

  def test_approximate_mode_picks_top_component(self):
    alphas = jnp.asarray([[0.1, 5.0]])
    mus = jnp.asarray([[[1.0, 1.0], [2.0, -2.0]]])
    sigmas = jnp.ones((1, 2, 2))
    gm = mdn.MixtureParams(alphas, mus, sigmas)
    mode = mdn.gaussian_mixture_approximate_mode(gm)
    np.testing.assert_allclose(np.asarray(mode), [[2.0, -2.0]])

  def test_decoder_end_to_end(self):
    decoder = mdn.MDNDecoder(num_mixture_components=4, output_size=3)
    inputs = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    variables = decoder.init(jax.random.PRNGKey(1), inputs)
    (action, gm), _ = decoder.apply(variables, inputs, mutable=[])
    assert action.shape == (8, 3)
    target = jax.random.normal(jax.random.PRNGKey(2), (8, 3))
    loss = mdn.mdn_loss(gm, target)
    assert np.isfinite(float(loss))

  def test_sample_shape(self):
    gm = self._gm(batch=6, k=2, d=4)
    sample = mdn.mixture_sample(gm, jax.random.PRNGKey(3))
    assert sample.shape == (6, 4)


class TestSnail:

  def test_causal_conv_is_causal(self):
    """Perturbing a late timestep can't change earlier outputs."""
    module = snail.CausalConv(filters=7, dilation_rate=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 3))
    variables = module.init(jax.random.PRNGKey(1), x)
    y1 = module.apply(variables, x)
    x2 = x.at[0, 9, :].set(100.0)
    y2 = module.apply(variables, x2)
    assert y1.shape == (1, 10, 7)
    np.testing.assert_allclose(y1[0, :9], y2[0, :9], atol=1e-5)
    assert not np.allclose(y1[0, 9], y2[0, 9])

  def test_dense_block_concats(self):
    module = snail.DenseBlock(filters=5, dilation_rate=1)
    x = jnp.ones((2, 6, 3))
    variables = module.init(jax.random.PRNGKey(0), x)
    y = module.apply(variables, x)
    assert y.shape == (2, 6, 8)
    np.testing.assert_allclose(y[..., :3], x)

  def test_tc_block_output_channels(self):
    module = snail.TCBlock(sequence_length=8, filters=4)
    x = jnp.ones((2, 8, 3))
    variables = module.init(jax.random.PRNGKey(0), x)
    y = module.apply(variables, x)
    assert y.shape == (2, 8, 3 + 4 * 3)  # ceil(log2(8)) == 3 blocks

  def test_causally_masked_softmax(self):
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 5))
    probs = snail.causally_masked_softmax(logits)
    probs = np.asarray(probs)
    assert np.allclose(np.triu(probs, k=1), 0.0)
    np.testing.assert_allclose(probs.sum(-1), np.ones((2, 5)), rtol=1e-5)

  def test_attention_block(self):
    module = snail.AttentionBlock(key_size=8, value_size=6)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3))
    variables = module.init(jax.random.PRNGKey(1), x)
    y, end_points = module.apply(variables, x)
    assert y.shape == (2, 5, 9)
    assert end_points['attn_prob'].shape == (2, 5, 5)
    # Causality: output at t=0 only attends to t=0.
    probs = np.asarray(end_points['attn_prob'])
    np.testing.assert_allclose(probs[:, 0, 0], 1.0, rtol=1e-5)


class TestVisionLayers:

  def test_images_to_features(self):
    module = vision_layers.ImagesToFeaturesNet()
    images = jax.random.uniform(jax.random.PRNGKey(0), (2, 64, 64, 3))
    variables = module.init(jax.random.PRNGKey(1), images)
    points, aux = module.apply(variables, images)
    assert points.shape == (2, 64)
    assert aux['softmax'].shape[0] == 2

  def test_film_conditioning_changes_output(self):
    module = vision_layers.ImagesToFeaturesNet()
    images = jax.random.uniform(jax.random.PRNGKey(0), (2, 64, 64, 3))
    film = jax.random.normal(jax.random.PRNGKey(2), (2, 2 * 5 * 32))
    variables = module.init(jax.random.PRNGKey(1), images, film)
    with_film, _ = module.apply(variables, images, film)
    without, _ = module.apply(variables, images, jnp.zeros_like(film))
    assert not np.allclose(with_film, without)

  def test_bad_film_shape_raises(self):
    module = vision_layers.ImagesToFeaturesNet()
    images = jnp.ones((2, 64, 64, 3))
    with pytest.raises(ValueError, match='FiLM'):
      module.init(jax.random.PRNGKey(0), images, jnp.ones((2, 7)))

  def test_film_params_head(self):
    module = vision_layers.FilmParams(film_output_size=320)
    emb = jnp.ones((4, 12))
    variables = module.init(jax.random.PRNGKey(0), emb)
    out = module.apply(variables, emb)
    assert out.shape == (4, 320)

  def test_pose_net(self):
    module = vision_layers.ImageFeaturesToPoseNet(num_outputs=7)
    feats = jnp.ones((3, 64))
    aux = jnp.ones((3, 5))
    variables = module.init(jax.random.PRNGKey(0), feats, aux)
    pose = module.apply(variables, feats, aux)
    assert pose.shape == (3, 7)

  def test_pose_net_aux_output(self):
    module = vision_layers.ImageFeaturesToPoseNet(
        num_outputs=7, aux_output_dim=3)
    feats = jnp.ones((3, 64))
    variables = module.init(jax.random.PRNGKey(0), feats)
    pose, aux_pred = module.apply(variables, feats)
    assert pose.shape == (3, 7)
    assert aux_pred.shape == (3, 3)

  def test_high_res_multi_resolution_sum(self):
    module = vision_layers.ImagesToFeaturesHighResNet(
        num_blocks=3, use_batch_norm=False)
    images = jax.random.uniform(jax.random.PRNGKey(0), (2, 128, 128, 3))
    variables = module.init(jax.random.PRNGKey(1), images)
    points, aux = module.apply(variables, images)
    assert points.shape == (2, 64)
    # Softmax runs at the first tap's (highest) resolution.
    assert aux['softmax'].shape[1] >= 28


class TestResNet:

  def test_resnet18_shapes_and_endpoints(self):
    model = resnet.ResNet(resnet_size=18, num_classes=10)
    images = jax.random.uniform(jax.random.PRNGKey(0), (2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(1), images)
    (logits, endpoints), _ = model.apply(variables, images, mutable=[])
    assert logits.shape == (2, 10)
    for key in ('initial_conv', 'initial_max_pool', 'block_layer1',
                'block_layer4', 'pre_final_pool', 'final_reduce_mean',
                'final_dense'):
      assert key in endpoints, key
    assert endpoints['final_reduce_mean'].shape == (2, 512)

  def test_resnet50_bottleneck_channels(self):
    model = resnet.ResNet(resnet_size=50, num_classes=4)
    images = jnp.ones((1, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), images)
    (_, endpoints), _ = model.apply(variables, images, mutable=[])
    assert endpoints['block_layer4'].shape[-1] == 2048

  def test_film_generator_contract_and_effect(self):
    model = resnet.ResNet(resnet_size=18, num_classes=4)
    gen = resnet.LinearFilmGenerator(
        block_sizes=model.block_sizes, filter_sizes=model.filter_sizes)
    emb = jax.random.normal(jax.random.PRNGKey(0), (2, 16))
    gen_vars = gen.init(jax.random.PRNGKey(1), emb)
    films = gen.apply(gen_vars, emb)
    assert len(films) == 4 and len(films[0]) == model.block_sizes[0]
    assert films[0][0].shape == (2, 2 * 64)

    images = jax.random.uniform(jax.random.PRNGKey(2), (2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(3), images,
                           film_gamma_betas=films)
    (with_film, _), _ = model.apply(variables, images,
                                    film_gamma_betas=films, mutable=[])
    (without, _), _ = model.apply(variables, images, mutable=[])
    assert not np.allclose(with_film, without)

  def test_enabled_block_layers_disables_film(self):
    gen = resnet.LinearFilmGenerator(
        block_sizes=[2, 2, 2, 2], filter_sizes=[64, 128, 256, 512],
        enabled_block_layers=[True, False, False, False])
    emb = jnp.ones((1, 8))
    variables = gen.init(jax.random.PRNGKey(0), emb)
    films = gen.apply(variables, emb)
    assert films[0][0] is not None
    assert all(f is None for f in films[1])

  def test_bad_resnet_size_raises(self):
    with pytest.raises(ValueError, match='resnet_size'):
      resnet.get_block_sizes(42)

  def test_functional_wrapper_train_mode_updates_batch_stats(self):
    images = jax.random.uniform(jax.random.PRNGKey(0), (2, 32, 32, 3))
    model = resnet.ResNet(resnet_size=18, num_classes=4)
    variables = model.init(jax.random.PRNGKey(1), images)
    logits, endpoints, new_state = resnet.resnet_model(
        images, variables, train=True, num_classes=4, resnet_size=18)
    assert logits.shape == (2, 4)
    assert 'batch_stats' in new_state


class TestTec:

  def test_embed_fullstate(self):
    module = tec.EmbedFullstate(embed_size=20)
    state = jnp.ones((4, 10))
    variables = module.init(jax.random.PRNGKey(0), state)
    emb = module.apply(variables, state)
    assert emb.shape == (4, 20)

  def test_embed_condition_images(self):
    module = tec.EmbedConditionImages(fc_layers=(32, 16))
    images = jax.random.uniform(jax.random.PRNGKey(0), (2, 64, 64, 3))
    variables = module.init(jax.random.PRNGKey(1), images)
    emb = module.apply(variables, images)
    assert emb.shape == (2, 16)

  def test_embed_condition_images_bad_rank(self):
    module = tec.EmbedConditionImages()
    with pytest.raises(ValueError, match='unexpected shape'):
      module.init(jax.random.PRNGKey(0), jnp.ones((2, 64, 64)))

  def test_reduce_temporal_embeddings(self):
    module = tec.ReduceTemporalEmbeddings(output_size=12)
    temporal = jnp.ones((3, 40, 8))
    variables = module.init(jax.random.PRNGKey(0), temporal)
    out = module.apply(variables, temporal)
    assert out.shape == (3, 12)

  def test_contrastive_loss_prefers_matching_pairs(self):
    rng = np.random.RandomState(0)
    anchor_dir = rng.randn(8).astype(np.float32)
    anchor_dir /= np.linalg.norm(anchor_dir)
    inf_emb = jnp.asarray(np.tile(anchor_dir, (3, 2, 1)))
    # Task 0's condition embedding matches; others are far away.
    con = np.tile(-anchor_dir, (3, 2, 1)).astype(np.float32)
    con[0] = anchor_dir
    loss_aligned = tec.compute_embedding_contrastive_loss(
        inf_emb, jnp.asarray(con))
    con_bad = np.tile(anchor_dir, (3, 2, 1)).astype(np.float32)
    con_bad[0] = -anchor_dir
    loss_misaligned = tec.compute_embedding_contrastive_loss(
        inf_emb, jnp.asarray(con_bad))
    assert float(loss_aligned) < float(loss_misaligned)


class TestFastMaxPool:
  """pooling.max_pool == nn.max_pool in value AND gradient.

  The custom-VJP path replaces XLA select-and-scatter (measured 10x
  slower than the surrounding convs on TPU) for non-overlapping pools;
  parity with the reference semantics (reduce-window max + first-match
  scatter, ref slim max_pool2d usage networks.py:333) is what these
  tests pin down.
  """

  CASES = [
      ((2, 236, 236, 3), (3, 3), 'SAME'),
      ((2, 79, 79, 4), (3, 3), 'SAME'),
      ((2, 27, 27, 4), (2, 2), 'SAME'),
      ((2, 28, 28, 4), (2, 2), 'VALID'),
      ((2, 29, 29, 4), (3, 3), 'VALID'),  # non-divisible: tail cropped
      ((1, 8, 10, 2), (2, 2), 'VALID'),
  ]

  @pytest.mark.parametrize('shape,window,padding', CASES)
  def test_value_and_grad_match_reference(self, shape, window, padding):
    from tensor2robot_tpu.layers import pooling
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))

    import flax.linen as nn
    want = nn.max_pool(x, window, strides=window, padding=padding)
    got = pooling.max_pool(x, window, strides=window, padding=padding)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    def loss_ref(x):
      return jnp.sum(jnp.sin(
          nn.max_pool(x, window, strides=window, padding=padding)))

    def loss_fast(x):
      return jnp.sum(jnp.sin(
          pooling.max_pool(x, window, strides=window, padding=padding)))

    g_want = jax.grad(loss_ref)(x)
    g_got = jax.grad(loss_fast)(x)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               atol=1e-6)

  def test_tie_break_first_match(self):
    """Equal window elements: gradient goes to the FIRST (row-major)."""
    from tensor2robot_tpu.layers import pooling
    import flax.linen as nn
    x = jnp.ones((1, 4, 4, 1), jnp.float32)
    g_ref = jax.grad(lambda x: jnp.sum(
        nn.max_pool(x, (2, 2), strides=(2, 2), padding='VALID')))(x)
    g_fast = jax.grad(lambda x: jnp.sum(
        pooling.max_pool(x, (2, 2), strides=(2, 2), padding='VALID')))(x)
    np.testing.assert_array_equal(np.asarray(g_fast), np.asarray(g_ref))

  def test_overlapping_falls_back(self):
    from tensor2robot_tpu.layers import pooling
    import flax.linen as nn
    x = jnp.asarray(np.random.RandomState(1).randn(2, 9, 9, 3),
                    jnp.float32)
    want = nn.max_pool(x, (3, 3), strides=(2, 2), padding='SAME')
    got = pooling.max_pool(x, (3, 3), strides=(2, 2), padding='SAME')
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))

  def test_strides_none_matches_flax_default(self):
    """flax's strides=None (stride 1) must not crash the fast-path gate
    (ADVICE r2: it used to TypeError at tuple(strides))."""
    from tensor2robot_tpu.layers import pooling
    import flax.linen as nn
    x = jnp.asarray(np.random.RandomState(2).randn(2, 6, 6, 3), jnp.float32)
    want = nn.max_pool(x, (2, 2), strides=None, padding='VALID')
    got = pooling.max_pool(x, (2, 2), strides=None, padding='VALID')
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))

  def test_3d_window_falls_back(self):
    """A 3-dim window (5D input) must take the nn.max_pool path, not crash
    inside the 2D fast path (ADVICE r2)."""
    from tensor2robot_tpu.layers import pooling
    import flax.linen as nn
    x = jnp.asarray(np.random.RandomState(3).randn(1, 4, 4, 4, 2),
                    jnp.float32)
    want = nn.max_pool(x, (2, 2, 2), strides=(2, 2, 2), padding='VALID')
    got = pooling.max_pool(x, (2, 2, 2), strides=(2, 2, 2),
                           padding='VALID')
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
