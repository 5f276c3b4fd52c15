"""Preprocessor tests: protocol, spec wrappers, jittable image transforms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.preprocessors import (
    AbstractPreprocessor,
    Bfloat16PreprocessorWrapper,
    NoOpPreprocessor,
    SpecTransformationPreprocessor,
    image_transformations,
)
from tensor2robot_tpu.specs import SpecStruct, TensorSpec, bfloat16


def _model_feature_spec(mode):
  del mode
  s = SpecStruct()
  s['image'] = TensorSpec((16, 16, 3), np.float32, name='image')
  s['state'] = TensorSpec((4,), np.float32, name='state')
  return s


def _model_label_spec(mode):
  del mode
  return SpecStruct(target=TensorSpec((2,), np.float32, name='target'))


class TestNoOpPreprocessor:

  def test_identity_with_validation(self):
    p = NoOpPreprocessor(_model_feature_spec, _model_label_spec)
    features = specs_lib.make_random_numpy(
        p.get_in_feature_specification(ModeKeys.TRAIN), batch_size=2)
    labels = specs_lib.make_random_numpy(
        p.get_in_label_specification(ModeKeys.TRAIN), batch_size=2)
    f, l = p.preprocess(features, labels, ModeKeys.TRAIN)
    np.testing.assert_array_equal(f['image'], features['image'])
    np.testing.assert_array_equal(l['target'], labels['target'])

  def test_rejects_bad_input(self):
    p = NoOpPreprocessor(_model_feature_spec, _model_label_spec)
    with pytest.raises(ValueError, match='Required'):
      p.preprocess(SpecStruct(), None, ModeKeys.PREDICT)


class TestSpecTransformationPreprocessor:

  class _JpegOnDisk(SpecTransformationPreprocessor):
    def update_spec_transform(self, key, spec, mode):
      if 'image' in key:
        return TensorSpec(spec.shape, np.uint8, name=spec.name,
                          data_format='jpeg')
      return spec

    def _preprocess_fn(self, features, labels, mode, rng=None):
      features['image'] = features['image'].astype(np.float32) / 255.0
      return features, labels

  def test_in_spec_transformed_out_matches_model(self):
    p = self._JpegOnDisk(_model_feature_spec, _model_label_spec)
    in_spec = p.get_in_feature_specification(ModeKeys.TRAIN)
    assert in_spec['image'].dtype == np.uint8
    assert in_spec['image'].data_format == 'jpeg'
    out_spec = p.get_out_feature_specification(ModeKeys.TRAIN)
    assert out_spec['image'].dtype == np.float32
    features = specs_lib.make_random_numpy(in_spec, batch_size=2)
    labels = specs_lib.make_random_numpy(
        p.get_in_label_specification(ModeKeys.TRAIN), batch_size=2)
    f, _ = p.preprocess(features, labels, ModeKeys.TRAIN)
    assert f['image'].dtype == np.float32


class TestBfloat16Wrapper:

  def test_spec_retyping_and_cast(self):
    base = NoOpPreprocessor(_model_feature_spec, _model_label_spec)
    wrapped = Bfloat16PreprocessorWrapper(base)
    in_spec = wrapped.get_in_feature_specification(ModeKeys.TRAIN)
    assert in_spec['image'].dtype == np.float32
    out_spec = wrapped.get_out_feature_specification(ModeKeys.TRAIN)
    assert out_spec['image'].dtype == bfloat16
    features = specs_lib.make_random_numpy(in_spec, batch_size=2)
    labels = specs_lib.make_random_numpy(
        wrapped.get_in_label_specification(ModeKeys.TRAIN), batch_size=2)
    f, l = wrapped.preprocess(features, labels, ModeKeys.TRAIN)
    assert f['image'].dtype == bfloat16
    assert l['target'].dtype == bfloat16

  def test_optional_stripped(self):
    def fs(mode):
      s = _model_feature_spec(mode)
      s['extra'] = TensorSpec((1,), np.float32, name='extra', is_optional=True)
      return s
    wrapped = Bfloat16PreprocessorWrapper(NoOpPreprocessor(fs, _model_label_spec))
    out_spec = wrapped.get_out_feature_specification(ModeKeys.TRAIN)
    assert 'extra' not in out_spec


class TestImageTransformations:

  def _images(self, n=2, h=16, w=16):
    rng = np.random.RandomState(0)
    return jnp.asarray(rng.rand(n, h, w, 3).astype(np.float32))

  def test_center_crop(self):
    img = self._images()
    (out,) = image_transformations.center_crop_images([img], (8, 8))
    assert out.shape == (2, 8, 8, 3)
    np.testing.assert_allclose(out, img[:, 4:12, 4:12, :])

  def test_random_crop_aligned_across_views(self):
    img = self._images()
    key = jax.random.PRNGKey(0)
    a, b = image_transformations.random_crop_images(key, [img, img], (8, 8))
    np.testing.assert_allclose(a, b)  # identical offsets per example
    assert a.shape == (2, 8, 8, 3)

  def test_random_crop_bounds(self):
    img = self._images()
    with pytest.raises(ValueError, match='exceeds'):
      image_transformations.random_crop_images(
          jax.random.PRNGKey(0), [img], (32, 32))

  def test_random_crop_content_is_a_window(self):
    img = self._images(n=1, h=6, w=6)
    key = jax.random.PRNGKey(3)
    (out,) = image_transformations.random_crop_images(key, [img], (3, 3))
    # The crop must appear somewhere in the source image.
    found = False
    for y in range(4):
      for x in range(4):
        if np.allclose(out[0], img[0, y:y + 3, x:x + 3]):
          found = True
    assert found

  def test_photometric_jittable_and_bounded(self):
    img = self._images()
    key = jax.random.PRNGKey(1)

    @jax.jit
    def distort(key, img):
      return image_transformations.apply_photometric_image_distortions(
          key, [img], random_brightness=True, random_saturation=True,
          random_hue=True, random_contrast=True, random_noise_level=0.05,
          random_channel_swap=True)[0]

    out = distort(key, img)
    assert out.shape == img.shape
    assert float(out.min()) >= 0.0 and float(out.max()) <= 1.0
    assert not np.allclose(out, img)
    # Deterministic per key.
    np.testing.assert_allclose(distort(key, img), out)

  def test_hue_identity_at_zero(self):
    img = self._images()
    out = image_transformations.adjust_hue(img, 0.0)
    np.testing.assert_allclose(out, img, atol=1e-5)

  def test_hue_matches_tf(self):
    tf = pytest.importorskip('tensorflow')
    img = self._images(n=1)
    for delta in (0.07, -0.2, 0.45):
      ours = image_transformations.adjust_hue(img, delta)
      theirs = tf.image.adjust_hue(tf.constant(np.asarray(img)), delta).numpy()
      assert np.max(np.abs(np.asarray(ours) - theirs)) < 1e-4, delta

  def test_depth_distortions(self):
    depth = jnp.ones((2, 8, 8, 1), jnp.float32)
    (out,) = image_transformations.apply_depth_image_distortions(
        jax.random.PRNGKey(0), [depth], random_noise_level=0.1,
        scale_noise=True)
    assert out.shape == depth.shape
    assert not np.allclose(out, depth)

  def test_preprocess_inside_jit_with_rng(self):
    """The whole preprocessor protocol composes under jit (device-side)."""

    class CropPreprocessor(AbstractPreprocessor):
      def get_in_feature_specification(self, mode):
        return SpecStruct(image=TensorSpec((16, 16, 3), np.float32,
                                           name='image'))

      def get_in_label_specification(self, mode):
        return SpecStruct()

      def get_out_feature_specification(self, mode):
        return SpecStruct(image=TensorSpec((8, 8, 3), np.float32,
                                           name='image'))

      def get_out_label_specification(self, mode):
        return SpecStruct()

      def _preprocess_fn(self, features, labels, mode, rng=None):
        out = SpecStruct()
        (out['image'],) = image_transformations.random_crop_images(
            rng, [features['image']], (8, 8))
        return out, labels

    p = CropPreprocessor()

    @jax.jit
    def step(features, rng):
      f, _ = p.preprocess(features, None, ModeKeys.TRAIN, rng)
      return jnp.mean(f['image'])

    features = specs_lib.make_random_numpy(
        p.get_in_feature_specification(ModeKeys.TRAIN), batch_size=4)
    value = step(features, jax.random.PRNGKey(0))
    assert np.isfinite(float(value))


class TestDeviceDecodePreprocessor:
  """Split-decode training path: coef records in, decoded pixels inside
  the jitted step (preprocessors/device_decode.py)."""

  def _image_model(self):
    import flax.linen as nn
    from tensor2robot_tpu.models.abstract_model import AbstractT2RModel

    class _Net(nn.Module):

      @nn.compact
      def __call__(self, features, mode='train', train=False):
        img = jnp.asarray(features['image'], jnp.float32) / 255.0
        pooled = img.mean(axis=(1, 2))
        return {'logits': nn.Dense(1, name='head')(pooled)}

    class _ImageModel(AbstractT2RModel):

      def __init__(self):
        super().__init__(device_type='cpu')

      def get_feature_specification(self, mode):
        return SpecStruct(image=TensorSpec(
            (64, 64, 3), np.uint8, name='frame', data_format='jpeg'))

      def get_label_specification(self, mode):
        return SpecStruct(target=TensorSpec((1,), np.float32,
                                            name='target'))

      def create_network(self):
        return _Net()

      def model_train_fn(self, variables, features, labels,
                         inference_outputs, mode):
        loss = jnp.mean(
            (inference_outputs['logits'] -
             jnp.asarray(labels['target'], jnp.float32)) ** 2)
        return loss, SpecStruct(loss=loss)

    return _ImageModel()

  def _write_records(self, path, n=12):
    from tensor2robot_tpu.data import tfrecord, wire
    from tensor2robot_tpu.utils.image import numpy_to_image_string
    rng = np.random.RandomState(0)
    frames, records = [], []
    for i in range(n):
      img = np.tile(rng.randint(0, 255, (64, 64, 1), np.uint8), (1, 1, 3))
      frames.append(img)
      records.append(wire.build_example({
          'frame': numpy_to_image_string(img),
          'target': np.asarray([float(i % 2)], np.float32)}))
    tfrecord.write_records(path, records)
    return frames

  def test_specs_and_parity_with_host_decode(self, tmp_path):
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    model = self._image_model()
    path = str(tmp_path / 'imgs.tfrecord')
    frames = self._write_records(path)
    model.set_preprocessor(DeviceDecodePreprocessor(model.preprocessor))
    in_spec = model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN)
    assert 'image/y' in dict(in_spec) and 'image/qt' in dict(in_spec)
    assert tuple(in_spec['image/y'].shape) == (8, 8, 64)

    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    generator.set_specification_from_model(model, ModeKeys.TRAIN)
    features, labels = next(generator.create_dataset_iterator(
        mode=ModeKeys.EVAL, num_epochs=1))
    # Finish the decode exactly as the jitted step would.
    decoded, _ = model.preprocessor.preprocess(features, labels,
                                               ModeKeys.EVAL)
    img = np.asarray(decoded['image'])
    assert img.shape == (4, 64, 64, 3) and img.dtype == np.uint8
    # Pixel parity vs a host decode of the same JPEG bytes (first record
    # of the unshuffled EVAL stream).
    from tensor2robot_tpu.utils.image import (
        image_string_to_numpy,
        numpy_to_image_string,
    )
    host = image_string_to_numpy(numpy_to_image_string(frames[0]))
    diff = img[0].astype(int) - host.astype(int)
    assert np.abs(diff).max() <= 4

  def test_trains_from_coef_records(self, tmp_path):
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    from tensor2robot_tpu.trainer import Trainer
    model = self._image_model()
    path = str(tmp_path / 'imgs.tfrecord')
    self._write_records(path)
    model.set_preprocessor(DeviceDecodePreprocessor(model.preprocessor))
    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    trainer = Trainer(model, str(tmp_path / 'run'),
                      mesh=parallel.create_mesh(
                          {'data': 1}, devices=jax.devices()[:1]),
                      async_checkpoints=False,
                      save_checkpoints_steps=10**9)
    try:
      state = trainer.train(generator, max_train_steps=2,
                            shard_index=0, num_shards=1)
      assert int(jax.device_get(state.step)) == 2
    finally:
      trainer.close()

  def test_sparse_specs_and_pixel_parity(self, tmp_path):
    """sparse=True ships delta/value streams; preprocess() unpacks them to
    the same pixels as the dense coef path (host convenience route)."""
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    model = self._image_model()
    path = str(tmp_path / 'imgs.tfrecord')
    frames = self._write_records(path)
    model.set_preprocessor(
        DeviceDecodePreprocessor(model.preprocessor, sparse=True))
    in_spec = model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN)
    assert 'image/sd' in dict(in_spec) and 'image/qt' in dict(in_spec)

    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    generator.set_specification_from_model(model, ModeKeys.TRAIN)
    features, labels = next(generator.create_dataset_iterator(
        mode=ModeKeys.EVAL, num_epochs=1))
    assert 'image/sd' in features and 'image/y' not in features
    decoded, _ = model.preprocessor.preprocess(features, labels,
                                               ModeKeys.EVAL)
    img = np.asarray(decoded['image'])
    assert img.shape == (4, 64, 64, 3) and img.dtype == np.uint8
    from tensor2robot_tpu.utils.image import (
        image_string_to_numpy,
        numpy_to_image_string,
    )
    host = image_string_to_numpy(numpy_to_image_string(frames[0]))
    diff = img[0].astype(int) - host.astype(int)
    assert np.abs(diff).max() <= 4

  def test_trains_from_sparse_records(self, tmp_path):
    """Full Trainer loop over sparse streams: the SparseCoefFeed unpacks
    between transfer and the (shape-stable) jitted step."""
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    from tensor2robot_tpu.trainer import Trainer
    model = self._image_model()
    path = str(tmp_path / 'imgs.tfrecord')
    self._write_records(path)
    model.set_preprocessor(
        DeviceDecodePreprocessor(model.preprocessor, sparse=True))
    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    trainer = Trainer(model, str(tmp_path / 'run'),
                      mesh=parallel.create_mesh(
                          {'data': 1}, devices=jax.devices()[:1]),
                      async_checkpoints=False,
                      save_checkpoints_steps=10**9)
    try:
      state = trainer.train(generator, max_train_steps=2,
                            shard_index=0, num_shards=1)
      assert int(jax.device_get(state.step)) == 2
    finally:
      trainer.close()

  def test_packed_specs_and_pixel_parity(self, tmp_path):
    """wire_format='packed' ships the bit-packed streams with a hoisted
    [1, 3, 64] quant table; preprocess() unpacks them to the same pixels
    as the dense coef path (host convenience route)."""
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    model = self._image_model()
    path = str(tmp_path / 'imgs.tfrecord')
    frames = self._write_records(path)
    model.set_preprocessor(
        DeviceDecodePreprocessor(model.preprocessor, wire_format='packed'))
    in_spec = model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN)
    assert 'image/pw' in dict(in_spec) and 'image/dcn' in dict(in_spec)
    assert 'image/se' in dict(in_spec) and 'image/qt' in dict(in_spec)

    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    generator.set_specification_from_model(model, ModeKeys.TRAIN)
    features, labels = next(generator.create_dataset_iterator(
        mode=ModeKeys.EVAL, num_epochs=1))
    assert 'image/pw' in features and 'image/y' not in features
    # The quant-table hoist actually happened on the wire.
    assert np.asarray(features['image/qt']).shape == (1, 3, 64)
    decoded, _ = model.preprocessor.preprocess(features, labels,
                                               ModeKeys.EVAL)
    img = np.asarray(decoded['image'])
    assert img.shape == (4, 64, 64, 3) and img.dtype == np.uint8
    from tensor2robot_tpu.utils.image import (
        image_string_to_numpy,
        numpy_to_image_string,
    )
    host = image_string_to_numpy(numpy_to_image_string(frames[0]))
    diff = img[0].astype(int) - host.astype(int)
    assert np.abs(diff).max() <= 4

  def test_trains_from_packed_records(self, tmp_path):
    """Full Trainer loop over the packed wire: SparseCoefFeed ships the
    hoisted table replicated, unpacks between transfer and the
    (shape-stable) jitted step, and the step sees the SAME dense
    key/{y,cb,cr,qt} signature as the sparse path."""
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.observability import get_registry
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    from tensor2robot_tpu.trainer import Trainer
    model = self._image_model()
    path = str(tmp_path / 'imgs.tfrecord')
    self._write_records(path)
    model.set_preprocessor(
        DeviceDecodePreprocessor(model.preprocessor, wire_format='packed'))
    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    trainer = Trainer(model, str(tmp_path / 'run'),
                      mesh=parallel.create_mesh(
                          {'data': 1}, devices=jax.devices()[:1]),
                      async_checkpoints=False,
                      save_checkpoints_steps=10**9)
    try:
      state = trainer.train(generator, max_train_steps=2,
                            shard_index=0, num_shards=1)
      assert int(jax.device_get(state.step)) == 2
    finally:
      trainer.close()
    # The train-channel shape-stability contract held across batches.
    gauges = get_registry().snapshot()['gauges']
    assert gauges.get('data/feed_shape_signatures', 0.0) <= 1.0

  def test_trains_with_pipelined_feed_depth(self, tmp_path):
    """feed_depth > 1: the train loop consumes device batches from the
    N-deep PipelinedFeed (producer thread owns decode + transfer) and
    completes the same steps."""
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    from tensor2robot_tpu.trainer import Trainer
    model = self._image_model()
    path = str(tmp_path / 'imgs.tfrecord')
    self._write_records(path)
    model.set_preprocessor(
        DeviceDecodePreprocessor(model.preprocessor, wire_format='packed'))
    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    trainer = Trainer(model, str(tmp_path / 'run'),
                      mesh=parallel.create_mesh(
                          {'data': 1}, devices=jax.devices()[:1]),
                      async_checkpoints=False,
                      save_checkpoints_steps=10**9,
                      feed_depth=3)
    try:
      state = trainer.train(generator, max_train_steps=3,
                            shard_index=0, num_shards=1)
      assert int(jax.device_get(state.step)) == 3
    finally:
      trainer.close()

  def test_requires_eligible_image_spec(self):
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    from tensor2robot_tpu.preprocessors.noop_preprocessor import (
        NoOpPreprocessor,
    )
    pre = NoOpPreprocessor(
        lambda mode: SpecStruct(x=TensorSpec((4,), np.float32, name='x')),
        lambda mode: SpecStruct())
    with pytest.raises(ValueError, match='no coef-eligible'):
      DeviceDecodePreprocessor(pre)

  def test_wire_format_validated(self):
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    model = self._image_model()
    with pytest.raises(ValueError, match="wire_format"):
      DeviceDecodePreprocessor(model.preprocessor, wire_format='zstd')

  def test_train_eval_model_wraps_bf16_outside_sparse(self, tmp_path):
    """The production config path: train_eval_model on a TPU-typed model
    installs Bfloat16PreprocessorWrapper OUTSIDE the device-decode
    wrapper. The bf16 decorator must forward the device-decode surface
    (raw specs / sparse flag) so the generator still plans the native
    sparse stream, and must delegate preprocess() wholesale (round-4
    regression: this configuration silently fell back to the Python
    parser and crashed on the sparse in-specs)."""
    from tensor2robot_tpu import parallel
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    from tensor2robot_tpu.trainer import train_eval_model

    model = self._image_model()
    model._device_type = 'tpu'  # force the bf16 wrap on the CPU backend
    path = str(tmp_path / 'imgs.tfrecord')
    self._write_records(path)
    model.set_preprocessor(
        DeviceDecodePreprocessor(model.preprocessor, sparse=True))
    generator = DefaultRecordInputGenerator(file_patterns=path,
                                            batch_size=4)
    results = train_eval_model(
        t2r_model=model,
        model_dir=str(tmp_path / 'run'),
        input_generator_train=generator,
        max_train_steps=2,
        mesh=parallel.create_mesh({'data': 1}, devices=jax.devices()[:1]),
        async_checkpoints=False)
    assert int(jax.device_get(results['state'].step)) == 2


class TestFusedCropConvert:
  """preprocessors/pallas_crop.py vs the XLA dynamic-slice path.

  Runs the kernel in interpret mode on CPU (on the chip it is 1 ulp from
  the XLA path — the in-kernel divide compiles to a reciprocal
  multiply).
  """

  def _ref(self, imgs, offs, target):
    cropped = image_transformations.crop_images(
        [jnp.asarray(imgs)], jnp.asarray(offs), target)[0]
    return np.asarray(cropped, np.float32) / 255.0

  def test_parity_random_offsets(self):
    from tensor2robot_tpu.preprocessors import pallas_crop

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (4, 64, 128, 3), dtype=np.uint8)
    offs = np.stack([rng.randint(0, 64 - 40 + 1, 4),
                     rng.randint(0, 128 - 56 + 1, 4)], -1).astype(np.int32)
    got = np.asarray(pallas_crop.fused_crop_convert(
        jnp.asarray(imgs), offs, (40, 56), interpret=True))
    np.testing.assert_allclose(got, self._ref(imgs, offs, (40, 56)),
                               atol=1e-7)

  def test_extreme_offsets_match_dynamic_slice_clamp(self):
    from tensor2robot_tpu.preprocessors import pallas_crop

    rng = np.random.RandomState(1)
    imgs = rng.randint(0, 256, (3, 16, 128, 1), dtype=np.uint8)
    # Zero, max-valid, and out-of-range (must clamp like dynamic_slice).
    offs = np.array([[0, 0], [8, 64], [100, 1000]], np.int32)
    got = np.asarray(pallas_crop.fused_crop_convert(
        jnp.asarray(imgs), offs, (8, 64), interpret=True))
    np.testing.assert_allclose(got, self._ref(imgs, offs, (8, 64)),
                               atol=1e-7)

  def test_unsupported_shapes_raise(self):
    from tensor2robot_tpu.preprocessors import pallas_crop

    assert not pallas_crop.supported((2, 63, 128, 3))   # H % 8
    assert not pallas_crop.supported((2, 64, 100, 3))   # W*C % 128
    with pytest.raises(ValueError, match='Unsupported image shape'):
      pallas_crop.fused_crop_convert(
          jnp.zeros((2, 64, 100, 3), jnp.uint8), np.zeros((2, 2), np.int32),
          (32, 50), interpret=True)
    with pytest.raises(ValueError, match='uint8'):
      pallas_crop.fused_crop_convert(
          jnp.zeros((2, 64, 128, 3), jnp.float32), np.zeros((2, 2), np.int32),
          (32, 64), interpret=True)

  def test_grasping_preprocessor_fused_matches_xla(self):
    """Same rng => same offsets => same pixels through the full TRAIN path."""
    from tensor2robot_tpu.research.qtopt import t2r_models

    rng = np.random.RandomState(2)
    # Full-size frames so the shape qualifies for the fused path.
    image = rng.randint(0, 256, (2, 512, 640, 3), dtype=np.uint8)
    key = jax.random.PRNGKey(7)
    outs = {}
    for fused in (False, True):
      pre = t2r_models.DefaultGrasping44ImagePreprocessor(
          model_feature_specification_fn=lambda mode: SpecStruct(),
          model_label_specification_fn=lambda mode: SpecStruct(),
          use_fused_crop=fused)
      features = SpecStruct()
      features['state/image'] = jnp.asarray(image)
      got, _ = pre._preprocess_fn(features, None, ModeKeys.TRAIN, rng=key)
      outs[fused] = np.asarray(got['state/image'])
    assert outs[True].shape == (2, 472, 472, 3)
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-7)
