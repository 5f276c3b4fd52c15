"""Tests for the native C++ record loader (data/native/record_loader.cc).

Strategy: the pure-Python ExampleParser pipeline is the semantic oracle —
the native path must produce byte-identical batches on the same records
(both decode through libjpeg-turbo, so even JPEG pixels match exactly).
"""

import gc
import hashlib
import time

import numpy as np
import pytest

from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.data import tfrecord
from tensor2robot_tpu.data.input_generators import DefaultRecordInputGenerator
from tensor2robot_tpu.data.parser import ExampleParser, build_example_for_specs
from tensor2robot_tpu.data.wire import build_example
from tensor2robot_tpu.data import native_loader
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import spans
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec, bfloat16
from tensor2robot_tpu.utils.image import numpy_to_image_string


def _specs():
  features = SpecStruct(
      image=TensorSpec((48, 64, 3), np.uint8, name='img/encoded',
                       data_format='jpeg'),
      vec=TensorSpec((3,), np.float32, name='vec'),
      scalar=TensorSpec((1,), np.float32, name='scalar'),
      idx=TensorSpec((2,), np.int64, name='idx'),
  )
  labels = SpecStruct(
      target=TensorSpec((1,), np.float32, name='target'))
  return features, labels


def _write_records(path, n, seed=0):
  rng = np.random.RandomState(seed)
  records = []
  raw = []
  for i in range(n):
    img = rng.randint(0, 255, (48, 64, 3), dtype=np.uint8)
    example = {
        'img/encoded': numpy_to_image_string(img),
        'vec': rng.rand(3).astype(np.float32),
        'scalar': np.asarray([i], np.float32),
        'idx': np.asarray([i, i * 2], np.int64),
        'target': np.asarray([i * 0.5], np.float32),
    }
    raw.append(example)
    records.append(build_example(example))
  tfrecord.write_records(path, records)
  return records, raw


@pytest.fixture(scope='module')
def record_file(tmp_path_factory):
  path = str(tmp_path_factory.mktemp('native') / 'data.tfrecord')
  records, raw = _write_records(path, 10)
  return path, records, raw


class TestPlan:

  def test_eligible(self):
    features, labels = _specs()
    assert native_loader.plan_for_specs(features, labels) is not None

  def test_sequence_ineligible_without_max_len(self):
    features, labels = _specs()
    features.seq = TensorSpec((4,), np.float32, name='seq', is_sequence=True)
    assert native_loader.plan_for_specs(features, labels) is None
    # With a step capacity the fast path takes sequence specs.
    assert native_loader.plan_for_specs(features, labels,
                                        sequence_max_len=8) is not None

  def test_optional_eligible(self):
    features, labels = _specs()
    features.opt = TensorSpec((4,), np.float32, name='opt', is_optional=True)
    assert native_loader.plan_for_specs(features, labels) is not None

  def test_png_ineligible(self):
    # PNG is the ONE remaining image fallback to the Python parser.
    features, labels = _specs()
    features.image = TensorSpec((48, 64, 3), np.uint8, name='img/encoded',
                                data_format='png')
    assert native_loader.plan_for_specs(features, labels) is None

  def test_varlen_eligible(self):
    # Rank-1 numeric varlen (TensorSpec enforces rank-1 for non-image
    # varlen) and rank-4 varlen frame lists are both native now.
    features, labels = _specs()
    features.v = TensorSpec((4,), np.float32, name='v',
                            varlen_default_value=0.0)
    assert native_loader.plan_for_specs(features, labels) is not None
    features.clips = TensorSpec((3, 48, 64, 3), np.uint8, name='clips',
                                data_format='jpeg',
                                varlen_default_value=0.0)
    assert native_loader.plan_for_specs(features, labels) is not None

  def test_dataset_zip_eligible(self):
    features, labels = _specs()
    features.other = TensorSpec((2,), np.float32, name='other',
                                dataset_key='aux')
    plan = native_loader.plan_for_specs(features, labels)
    assert plan is not None
    assert plan.dataset_keys == ['', 'aux']

  def test_optional_ineligible_in_coef_mode(self):
    features, labels = _specs()
    features.image = TensorSpec((48, 64, 3), np.uint8, name='img/encoded',
                                data_format='jpeg', is_optional=True)
    assert native_loader.plan_for_specs(
        features, labels, image_mode='coef') is None

  def test_coef_requires_mcu_aligned_dims(self):
    features, labels = _specs()
    plan = native_loader.plan_for_specs(features, labels, image_mode='coef')
    assert plan is not None  # 48x64 is 16-aligned
    features.image = TensorSpec((40, 64, 3), np.uint8, name='img/encoded',
                                data_format='jpeg')
    assert native_loader.plan_for_specs(
        features, labels, image_mode='coef') is None


class TestNativeStream:

  def _native_batches(self, path, batch_size, **kwargs):
    features, labels = _specs()
    plan = native_loader.plan_for_specs(features, labels)
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=batch_size, **kwargs)
    try:
      return list(stream)
    finally:
      stream.close()

  def test_matches_python_parser(self, record_file):
    path, records, _ = record_file
    features_spec, labels_spec = _specs()
    batches = self._native_batches(path, 4, num_epochs=1)
    assert len(batches) == 2  # 10 records, batch 4, remainder dropped
    parser = ExampleParser(features_spec, labels_spec)
    for i, (feats, labs) in enumerate(batches):
      ref_feats, ref_labs = parser.parse_batch(records[i * 4:(i + 1) * 4])
      for key in ref_feats:
        np.testing.assert_array_equal(
            np.asarray(feats[key]), np.asarray(ref_feats[key]), err_msg=key)
      for key in ref_labs:
        np.testing.assert_array_equal(
            np.asarray(labs[key]), np.asarray(ref_labs[key]), err_msg=key)

  def test_epochs(self, record_file):
    path, _, _ = record_file
    assert len(self._native_batches(path, 4, num_epochs=2)) == 5

  def test_shuffle_reproducible(self, record_file):
    path, _, _ = record_file
    a = self._native_batches(path, 4, num_epochs=1, shuffle=True, seed=7,
                             shuffle_buffer=8)
    b = self._native_batches(path, 4, num_epochs=1, shuffle=True, seed=7,
                             shuffle_buffer=8)
    c = self._native_batches(path, 4, num_epochs=1)
    for (fa, _), (fb, _) in zip(a, b):
      np.testing.assert_array_equal(fa['scalar'], fb['scalar'])
    assert not all(
        np.array_equal(fa['scalar'], fc['scalar'])
        for (fa, _), (fc, _) in zip(a, c))

  def test_shuffle_buffer_zero_degrades_to_pass_through(self, record_file):
    # shuffle on with shuffle_buffer <= 0 must clamp to 1 (pass-through),
    # not silently end the stream empty before a single record is
    # admitted to the reservoir.
    path, _, _ = record_file
    batches = self._native_batches(path, 4, num_epochs=1, shuffle=True,
                                   shuffle_buffer=0)
    assert len(batches) == 2

  def test_zero_copy_views_valid_for_one_step(self, record_file):
    path, _, _ = record_file
    features, labels = _specs()
    plan = native_loader.plan_for_specs(features, labels)
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=2, num_epochs=1)
    try:
      it = iter(stream)
      feats, _ = next(it)
      first = np.asarray(feats['scalar']).copy()
      np.testing.assert_array_equal(first.ravel(), [0.0, 1.0])
      next(it)  # the ring slot is recycled; the batch in hand is not
      np.testing.assert_array_equal(feats['scalar'], first)
    finally:
      stream.close()

  def test_missing_feature_raises(self, tmp_path):
    path = str(tmp_path / 'bad.tfrecord')
    tfrecord.write_records(
        path, [build_example({'vec': np.zeros(3, np.float32)})])
    with pytest.raises(RuntimeError, match='missing'):
      self._native_batches(path, 1, num_epochs=1)

  def test_wrong_image_dims_raises(self, tmp_path):
    path = str(tmp_path / 'dims.tfrecord')
    img = np.zeros((32, 32, 3), np.uint8)
    tfrecord.write_records(path, [build_example({
        'img/encoded': numpy_to_image_string(img),
        'vec': np.zeros(3, np.float32),
        'scalar': np.zeros(1, np.float32),
        'idx': np.zeros(2, np.int64),
        'target': np.zeros(1, np.float32),
    })])
    with pytest.raises(RuntimeError, match='dims'):
      self._native_batches(path, 1, num_epochs=1)

  def test_empty_image_is_zeros(self, tmp_path):
    path = str(tmp_path / 'empty.tfrecord')
    tfrecord.write_records(path, [build_example({
        'img/encoded': b'',
        'vec': np.zeros(3, np.float32),
        'scalar': np.zeros(1, np.float32),
        'idx': np.zeros(2, np.int64),
        'target': np.zeros(1, np.float32),
    })])
    (feats, _), = self._native_batches(path, 1, num_epochs=1)
    assert np.all(np.asarray(feats['image']) == 0)

  def test_episode_frame_list(self, tmp_path):
    """Rank-4 [T, H, W, C] image specs (a bytes list of T JPEGs — the
    seq2act episode layout) decode on the native path and match the
    Python parser."""
    path = str(tmp_path / 'episodes.tfrecord')
    features = SpecStruct(
        frames=TensorSpec((3, 32, 48, 3), np.uint8, name='ep/frames',
                          data_format='jpeg'),
        pose=TensorSpec((4,), np.float32, name='pose'))
    rng = np.random.RandomState(0)
    records = []
    for _ in range(5):
      jpegs = [numpy_to_image_string(
          rng.randint(0, 255, (32, 48, 3), dtype=np.uint8))
          for _ in range(3)]
      records.append(build_example(
          {'ep/frames': jpegs, 'pose': rng.rand(4).astype(np.float32)}))
    tfrecord.write_records(path, records)
    plan = native_loader.plan_for_specs(features, SpecStruct())
    assert plan is not None
    stream = native_loader.NativeBatchedStream(plan, [path], batch_size=2,
                                               num_epochs=1)
    try:
      batches = list(stream)
    finally:
      stream.close()
    assert len(batches) == 2
    parser = ExampleParser(features, SpecStruct())
    ref, _ = parser.parse_batch(records[:2])
    np.testing.assert_array_equal(np.asarray(batches[0][0]['frames']),
                                  np.asarray(ref['frames']))
    assert np.asarray(batches[0][0]['frames']).shape == (2, 3, 32, 48, 3)

  def test_episode_frame_count_mismatch_raises(self, tmp_path):
    path = str(tmp_path / 'short.tfrecord')
    features = SpecStruct(
        frames=TensorSpec((3, 32, 48, 3), np.uint8, name='ep/frames',
                          data_format='jpeg'))
    img = numpy_to_image_string(np.zeros((32, 48, 3), np.uint8))
    tfrecord.write_records(path, [build_example({'ep/frames': [img, img]})])
    plan = native_loader.plan_for_specs(features, SpecStruct())
    stream = native_loader.NativeBatchedStream(plan, [path], batch_size=1,
                                               num_epochs=1)
    try:
      with pytest.raises(RuntimeError, match='frames'):
        list(stream)
    finally:
      stream.close()

  def test_bfloat16_field(self, tmp_path):
    path = str(tmp_path / 'bf16.tfrecord')
    features = SpecStruct(x=TensorSpec((3,), bfloat16, name='x'))
    tfrecord.write_records(path, [build_example(
        {'x': np.asarray([1., 2., 3.], np.float32)})])
    plan = native_loader.plan_for_specs(features, SpecStruct())
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=1, num_epochs=1)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    assert np.asarray(feats['x']).dtype == bfloat16


class TestVarlenOptionalZip:
  """Wire parity for the round-6 fast paths: varlen pad/clip, optional
  presence (dense-batch drop), and multi-dataset zip — the Python
  ExampleParser is the semantic oracle, byte-for-byte."""

  def test_varlen_rank1_pad_clip_parity(self, tmp_path):
    path = str(tmp_path / 'varlen.tfrecord')
    features = SpecStruct(
        v=TensorSpec((4,), np.float32, name='v', varlen_default_value=7.0),
        i=TensorSpec((3,), np.int64, name='i', varlen_default_value=-1))
    rng = np.random.RandomState(0)
    records = []
    for count_v, count_i in [(2, 3), (4, 1), (6, 5), (0, 0)]:
      records.append(build_example({
          'v': rng.rand(count_v).astype(np.float32),
          'i': np.arange(count_i, dtype=np.int64)}))
    tfrecord.write_records(path, records)
    plan = native_loader.plan_for_specs(features, SpecStruct())
    assert plan is not None
    stream = native_loader.NativeBatchedStream(plan, [path], batch_size=4,
                                               num_epochs=1)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    ref, _ = ExampleParser(features, SpecStruct()).parse_batch(records)
    for key in ('v', 'i'):
      np.testing.assert_array_equal(np.asarray(feats[key]),
                                    np.asarray(ref[key]), err_msg=key)
      assert feats[key].dtype == ref[key].dtype, key

  def test_varlen_frame_list_pad_clip_parity(self, tmp_path):
    path = str(tmp_path / 'clips.tfrecord')
    features = SpecStruct(
        clips=TensorSpec((3, 32, 48, 3), np.uint8, name='clips',
                         data_format='jpeg', varlen_default_value=0.0))
    rng = np.random.RandomState(1)
    records = []
    for n_frames in (2, 3, 5):  # short (pad), exact, long (clip)
      jpegs = [numpy_to_image_string(
          rng.randint(0, 255, (32, 48, 3), dtype=np.uint8))
          for _ in range(n_frames)]
      records.append(build_example({'clips': jpegs}))
    tfrecord.write_records(path, records)
    plan = native_loader.plan_for_specs(features, SpecStruct())
    assert plan is not None
    stream = native_loader.NativeBatchedStream(plan, [path], batch_size=3,
                                               num_epochs=1)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    ref, _ = ExampleParser(features, SpecStruct()).parse_batch(records)
    np.testing.assert_array_equal(np.asarray(feats['clips']),
                                  np.asarray(ref['clips']))
    assert np.asarray(feats['clips']).shape == (3, 3, 32, 48, 3)

  def _optional_records(self, present):
    rng = np.random.RandomState(2)
    records = []
    for has_opt in present:
      example = {'vec': rng.rand(3).astype(np.float32)}
      if has_opt:
        example['opt'] = rng.rand(2).astype(np.float32)
      records.append(build_example(example))
    return records

  def _optional_specs(self):
    return SpecStruct(
        vec=TensorSpec((3,), np.float32, name='vec'),
        opt=TensorSpec((2,), np.float32, name='opt', is_optional=True))

  def test_optional_fully_present_batch_keeps_key(self, tmp_path):
    path = str(tmp_path / 'opt_full.tfrecord')
    records = self._optional_records([True, True, True, True])
    tfrecord.write_records(path, records)
    features = self._optional_specs()
    plan = native_loader.plan_for_specs(features, SpecStruct())
    stream = native_loader.NativeBatchedStream(plan, [path], batch_size=4,
                                               num_epochs=1)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    ref, _ = ExampleParser(features, SpecStruct()).parse_batch(records)
    assert 'opt' in ref and 'opt' in feats
    np.testing.assert_array_equal(np.asarray(feats['opt']),
                                  np.asarray(ref['opt']))

  def test_optional_partial_batch_drops_key(self, tmp_path):
    path = str(tmp_path / 'opt_part.tfrecord')
    records = self._optional_records([True, False, True, True])
    tfrecord.write_records(path, records)
    features = self._optional_specs()
    plan = native_loader.plan_for_specs(features, SpecStruct())
    stream = native_loader.NativeBatchedStream(plan, [path], batch_size=4,
                                               num_epochs=1)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    ref, _ = ExampleParser(features, SpecStruct()).parse_batch(records)
    assert 'opt' not in ref  # the oracle's dense-batch semantics
    assert 'opt' not in feats
    np.testing.assert_array_equal(np.asarray(feats['vec']),
                                  np.asarray(ref['vec']))

  def test_multi_dataset_zip_parity(self, tmp_path):
    from tensor2robot_tpu.data.pipeline import (
        BatchedExampleStream,
        RecordDataset,
    )

    main_path = str(tmp_path / 'main.tfrecord')
    aux_path = str(tmp_path / 'aux.tfrecord')
    rng = np.random.RandomState(3)
    main_records = [build_example({
        'img/encoded': numpy_to_image_string(
            rng.randint(0, 255, (16, 16, 3), dtype=np.uint8)),
        'vec': rng.rand(3).astype(np.float32)}) for _ in range(6)]
    # The aux dataset is LONGER: zip must end with the shortest.
    aux_records = [build_example({'aux_v': rng.rand(2).astype(np.float32)})
                   for _ in range(9)]
    tfrecord.write_records(main_path, main_records)
    tfrecord.write_records(aux_path, aux_records)
    features = SpecStruct(
        image=TensorSpec((16, 16, 3), np.uint8, name='img/encoded',
                         data_format='jpeg'),
        vec=TensorSpec((3,), np.float32, name='vec'),
        aux_v=TensorSpec((2,), np.float32, name='aux_v',
                         dataset_key='aux'))
    plan = native_loader.plan_for_specs(features, SpecStruct())
    assert plan is not None and plan.dataset_keys == ['', 'aux']
    stream = native_loader.NativeBatchedStream(
        plan, {'': [main_path], 'aux': [aux_path]}, batch_size=2,
        num_epochs=1)
    try:
      native_batches = list(stream)
    finally:
      stream.close()
    py_batches = list(iter(BatchedExampleStream(
        {'': RecordDataset(main_path),
         'aux': RecordDataset(aux_path, dataset_key='aux')},
        ExampleParser(features, SpecStruct()),
        batch_size=2, shuffle=False, num_epochs=1)))
    assert len(native_batches) == len(py_batches) == 3
    for (nf, _), (pf, _) in zip(native_batches, py_batches):
      for key in pf:
        np.testing.assert_array_equal(np.asarray(nf[key]),
                                      np.asarray(pf[key]), err_msg=key)

  def test_empty_file_list_rejected_at_create(self):
    # An empty group would spin the zip reader on nothing; it must fail
    # at CREATE (a config error), like the pre-zip 'files 0' contract.
    features = SpecStruct(x=TensorSpec((2,), np.float32, name='x'))
    plan = native_loader.plan_for_specs(features, SpecStruct())
    with pytest.raises(RuntimeError, match='empty file group'):
      native_loader.NativeBatchedStream(plan, [], batch_size=2)

  def test_zip_generator_takes_native_path(self, tmp_path):
    """dataset_map datasets route through the native loader now
    (use_native=True raised 'only supported by the Python pipeline'
    before round 6)."""
    rng = np.random.RandomState(4)
    main_path = str(tmp_path / 'm.tfrecord')
    aux_path = str(tmp_path / 'a.tfrecord')
    tfrecord.write_records(main_path, [
        build_example({'vec': rng.rand(3).astype(np.float32)})
        for _ in range(8)])
    tfrecord.write_records(aux_path, [
        build_example({'aux_v': rng.rand(2).astype(np.float32)})
        for _ in range(8)])
    features = SpecStruct(
        vec=TensorSpec((3,), np.float32, name='vec'),
        aux_v=TensorSpec((2,), np.float32, name='aux_v',
                         dataset_key='aux'))
    gen = DefaultRecordInputGenerator(
        dataset_map={'': main_path, 'aux': aux_path}, batch_size=4,
        use_native=True)
    gen.set_specification(features, SpecStruct())
    it = gen.create_dataset_iterator(mode=ModeKeys.EVAL, num_epochs=1)
    feats, _ = next(it)
    assert np.asarray(feats['vec']).shape == (4, 3)
    assert np.asarray(feats['aux_v']).shape == (4, 2)


def _sequence_specs():
  """Metareacher-style episode specs (episode_to_transitions.py:63)."""
  features = SpecStruct(
      obs=TensorSpec((2,), np.float32, name='pose_t', is_sequence=True),
      act=TensorSpec((3,), np.float32, name='action', is_sequence=True),
      done=TensorSpec((1,), np.int64, name='done', is_sequence=True),
      is_demo=TensorSpec((1,), np.int64, name='is_demo'))
  labels = SpecStruct(
      reward=TensorSpec((1,), np.float32, name='reward', is_sequence=True))
  return features, labels


def _write_sequence_records(path, n, max_steps=6, seed=0):
  from tensor2robot_tpu.data.wire import build_sequence_example

  rng = np.random.RandomState(seed)
  records = []
  for i in range(n):
    t = int(rng.randint(2, max_steps + 1))
    context = {'is_demo': np.asarray([i % 2], np.int64)}
    lists = {
        'pose_t': [rng.randn(2).astype(np.float32) for _ in range(t)],
        'action': [rng.randn(3).astype(np.float32) for _ in range(t)],
        'done': [np.asarray([int(s == t - 1)], np.int64) for s in range(t)],
        'reward': [np.asarray([rng.rand()], np.float32) for _ in range(t)],
    }
    records.append(build_sequence_example(context, lists))
  tfrecord.write_records(path, records)


class TestSequenceRecords:
  """SequenceExample fast path (VERDICT r4 item 5): wire parity with the
  Python parser on feature_lists records — batch-max padding, int64
  <key>_length outputs, context features, strict capacity."""

  def test_matches_python_parser(self, tmp_path):
    from tensor2robot_tpu.data.pipeline import (
        BatchedExampleStream,
        RecordDataset,
    )

    path = str(tmp_path / 'seq.tfrecord')
    _write_sequence_records(path, 8)
    features, labels = _sequence_specs()
    plan = native_loader.plan_for_specs(
        specs_lib.add_sequence_length_specs(features), labels,
        sequence_max_len=8)
    assert plan is not None
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=4, shuffle=False, num_epochs=1)
    native_batches = list(iter(stream))
    stream.close()
    py_batches = list(iter(BatchedExampleStream(
        RecordDataset(path), ExampleParser(features, labels),
        batch_size=4, shuffle=False, num_epochs=1)))
    assert len(native_batches) == len(py_batches) == 2
    for (nf, nl), (pf, pl) in zip(native_batches, py_batches):
      for key in pf:
        np.testing.assert_array_equal(np.asarray(nf[key]),
                                      np.asarray(pf[key]), err_msg=key)
        assert nf[key].dtype == pf[key].dtype, key
      for key in pl:
        np.testing.assert_array_equal(np.asarray(nl[key]),
                                      np.asarray(pl[key]), err_msg=key)

  def test_over_capacity_raises(self, tmp_path):
    path = str(tmp_path / 'seq.tfrecord')
    _write_sequence_records(path, 4, max_steps=6)
    features, labels = _sequence_specs()
    plan = native_loader.plan_for_specs(features, labels,
                                        sequence_max_len=3)
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=4, shuffle=False, num_epochs=1)
    with pytest.raises(RuntimeError, match='sequence_max_len'):
      list(iter(stream))
    stream.close()

  def test_generator_takes_native_path(self, tmp_path):
    """DefaultRecordInputGenerator(sequence_max_len=...) routes sequence
    datasets through the native loader (use_native=True would raise on
    fallback, so success proves the fast path)."""
    from tensor2robot_tpu.models.abstract_model import AbstractT2RModel

    path = str(tmp_path / 'seq.tfrecord')
    _write_sequence_records(path, 8)
    features, labels = _sequence_specs()

    class _Model(AbstractT2RModel):

      def get_feature_specification(self, mode):
        return features

      def get_label_specification(self, mode):
        return labels

    generator = DefaultRecordInputGenerator(
        file_patterns=path, batch_size=4, use_native=True,
        sequence_max_len=8)
    generator.set_specification_from_model(_Model(), ModeKeys.TRAIN)
    it = generator.create_dataset_iterator(mode=ModeKeys.EVAL, num_epochs=1)
    batch_features, batch_labels = next(it)
    assert batch_features['obs'].shape[0] == 4
    assert batch_features['obs'].shape[-1] == 2
    assert batch_features['obs_length'].dtype == np.int64
    assert batch_labels['reward'].shape[:2] == batch_features['obs'].shape[:2]


def _ownership_stream(kind, tmp_path):
  """A looping shuffled stream, and a key of it whose array has one shape
  in every batch (the sequence stream's other fields are cut to the longest
  episode of the batch)."""
  path = str(tmp_path / (kind + '.tfrecord'))
  if kind == 'dense':
    _write_records(path, 24)
    plan = native_loader.plan_for_specs(*_specs())
    key = 'image'
  else:
    _write_sequence_records(path, 24)
    features, labels = _sequence_specs()
    plan = native_loader.plan_for_specs(
        specs_lib.add_sequence_length_specs(features), labels,
        sequence_max_len=8)
    key = 'is_demo'
  stream = native_loader.NativeBatchedStream(
      plan, [path], batch_size=4, shuffle=True, seed=3, shuffle_buffer=24,
      num_threads=2)
  return stream, key


def _digest(batch):
  digest = hashlib.sha1()
  for side in batch:
    for key in sorted(side):
      digest.update(key.encode())
      digest.update(np.ascontiguousarray(side[key]).tobytes())
  return digest.hexdigest()


def _address(array):
  return array.__array_interface__['data'][0]


def _ring_mark():
  spans.event('test.mark')
  return max(r.id for r in spans.records())


def _packs_since(mark):
  return [r.attrs for r in spans.records(since_id=mark)
          if r.name == 'data.pack']


@pytest.mark.parametrize('kind', ['dense', 'sequence'])
class TestBatchOwnership:
  """A batch is its holder's for as long as it is held, and its memory is
  the stream's to write again as soon as it is not. A pool that recycles on
  a fixed round fails every test here."""

  @pytest.fixture
  def mark(self):
    return _ring_mark()

  def test_kept_batch_keeps_its_bytes(self, kind, tmp_path):
    stream, _ = _ownership_stream(kind, tmp_path)
    try:
      it = iter(stream)
      kept = next(it)
      drawn = _digest(kept)
      others = set()
      for _ in range(10):
        others.add(_digest(next(it)))  # drawn and dropped
      assert len(others) > 1  # other bytes did go by
      assert _digest(kept) == drawn
    finally:
      stream.close()

  def test_dropped_batch_gives_its_memory_to_the_next(self, kind, tmp_path,
                                                      mark):
    stream, key = _ownership_stream(kind, tmp_path)
    try:
      it = iter(stream)
      batch = next(it)
      address = _address(batch[0][key])
      pooled = _packs_since(mark)[0]['allocated']
      assert pooled > 0 and _packs_since(mark)[0]['reused'] == 0
      for _ in range(5):
        del batch
        batch = next(it)
        assert _address(batch[0][key]) == address
      for pack in _packs_since(mark)[1:]:
        assert (pack['reused'], pack['allocated']) == (pooled, 0)
    finally:
      stream.close()

  def test_kept_batches_cost_fresh_memory_and_none_is_overwritten(
      self, kind, tmp_path, mark):
    stream, key = _ownership_stream(kind, tmp_path)
    try:
      kept = []
      for batch in stream:
        kept.append((batch, _digest(batch)))
        if len(kept) == 8:
          break
      del batch
      packs = _packs_since(mark)
      assert all(pack['allocated'] > 0 for pack in packs)
      assert all(pack['reused'] == 0 for pack in packs)
      assert len({_address(batch[0][key]) for batch, _ in kept}) == 8
      for batch, drawn in kept:
        assert _digest(batch) == drawn
    finally:
      stream.close()

  def test_device_arrays_behind_a_prefetch_queue_keep_their_bytes(
      self, kind, tmp_path):
    import jax

    from tensor2robot_tpu.data.input_generators import prefetch_iterator

    # What the seed gives, drawn one at a time and copied at the draw.
    stream, _ = _ownership_stream(kind, tmp_path)
    truth = [{k: np.array(v) for k, v in features.items()}
             for (features, _), _ in zip(stream, range(12))]
    stream.close()
    mark = _ring_mark()
    stream, _ = _ownership_stream(kind, tmp_path)
    it = prefetch_iterator(iter(stream), depth=2, label='test')
    try:
      put = []
      for n in range(12):
        features, _ = next(it)
        # A slow consumer: the producer gets as far ahead as the queue
        # lets it (two queued, one in its hand) before batch n is put.
        deadline = time.monotonic() + 5.0
        while (len(_packs_since(mark)) < n + 4
               and time.monotonic() < deadline):
          time.sleep(0.001)
        assert len(_packs_since(mark)) >= n + 4
        put.append(jax.device_put(features.to_dict()))
        del features
      # Batch n + 5 has been packed for every n checked here.
      for device, expected in zip(put[:9], truth):
        assert sorted(device) == sorted(expected)
        for k, v in expected.items():
          np.testing.assert_array_equal(np.asarray(device[k]), v, err_msg=k)
    finally:
      it.close()
      stream.close()

  def test_batches_outlive_a_closed_stream(self, kind, tmp_path):
    stream, _ = _ownership_stream(kind, tmp_path)
    it = iter(stream)
    kept, drawn = [], []
    for _ in range(8):
      kept.append(next(it))
      drawn.append(_digest(kept[-1]))
    it.close()
    stream.close()
    del it, stream
    gc.collect()
    assert [_digest(batch) for batch in kept] == drawn
    # Slices of them die later still, each keeping its buffer alive.
    views = [leaf[:1] for batch in kept for side in batch
             for leaf in side.values()]
    copies = [view.copy() for view in views]
    del kept
    gc.collect()
    for view, copy in zip(views, copies):
      np.testing.assert_array_equal(view, copy)
    del views
    gc.collect()


class TestSoak:

  def test_epoch_coverage_under_parallel_decode(self, tmp_path):
    """Every record appears EXACTLY once per epoch across shuffled,
    multi-file, multi-threaded, ring-buffered iteration — the invariant
    that would break first under a slot-recycling or shuffle race."""
    features = SpecStruct(
        image=TensorSpec((16, 16, 3), np.uint8, name='im',
                         data_format='jpeg'),
        uid=TensorSpec((1,), np.float32, name='uid'))
    rng = np.random.RandomState(0)
    n_files, per_file = 4, 32
    uid = 0
    for fi in range(n_files):
      records = []
      for _ in range(per_file):
        records.append(build_example({
            'im': numpy_to_image_string(
                rng.randint(0, 255, (16, 16, 3), dtype=np.uint8)),
            'uid': np.asarray([float(uid)], np.float32)}))
        uid += 1
      tfrecord.write_records(str(tmp_path / 'f{}.tfrecord'.format(fi)),
                             records)
    total = n_files * per_file
    epochs = 3
    batch = 16
    plan = native_loader.plan_for_specs(features, SpecStruct())
    stream = native_loader.NativeBatchedStream(
        plan, [str(tmp_path / 'f{}.tfrecord'.format(i))
               for i in range(n_files)],
        batch_size=batch, shuffle=True, seed=11, shuffle_buffer=50,
        num_epochs=epochs, num_threads=4)
    seen = []
    try:
      for feats, _ in stream:
        seen.extend(np.asarray(feats['uid']).ravel().astype(int).tolist())
    finally:
      stream.close()
    assert len(seen) == total * epochs
    counts = np.bincount(np.asarray(seen), minlength=total)
    np.testing.assert_array_equal(counts, np.full(total, epochs))

  def test_non_tfrecord_file_is_clear_error(self, tmp_path):
    path = str(tmp_path / 'not_a_record.bin')
    with open(path, 'wb') as f:
      f.write(b'\xff' * 4096)  # garbage length field
    features = SpecStruct(uid=TensorSpec((1,), np.float32, name='uid'))
    plan = native_loader.plan_for_specs(features, SpecStruct())
    # The reader fails fast; depending on thread timing the error surfaces
    # at construction or on the first batch — both must carry the cause.
    with pytest.raises(RuntimeError, match='corrupt or non-TFRecord'):
      stream = native_loader.NativeBatchedStream(plan, [path], batch_size=1,
                                                 num_epochs=1)
      try:
        list(stream)
      finally:
        stream.close()


class TestDeviceDecode:
  """DCT-coefficient split decode: native coef mode + jpeg_device finish."""

  def _coef_decode(self, jpeg_bytes, h, w):
    from tensor2robot_tpu.data import jpeg_device
    features = SpecStruct(image=TensorSpec((h, w, 3), np.uint8, name='im',
                                           data_format='jpeg'))
    plan = native_loader.plan_for_specs(features, SpecStruct(),
                                        image_mode='coef')
    import tempfile, os
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, 'coef.tfrecord')
    tfrecord.write_records(path, [build_example({'im': jpeg_bytes})])
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=1, num_epochs=1, validate=False)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    return np.asarray(jpeg_device.decode_jpeg_coefficients(
        np.asarray(feats['image/y']), np.asarray(feats['image/cb']),
        np.asarray(feats['image/cr']), np.asarray(feats['image/qt'])))[0]

  def test_matches_host_decode(self):
    from tensor2robot_tpu.utils.image import image_string_to_numpy
    rng = np.random.RandomState(0)
    x = np.linspace(0, 1, 64)
    yy = np.linspace(0, 1, 48)
    img = (np.outer(yy, x)[..., None] * [220, 160, 90]).astype(np.float32)
    img[10:30, 20:50] = [250, 30, 60]  # sharp chroma edge
    img = np.clip(img + rng.randn(48, 64, 1) * 4, 0, 255).astype(np.uint8)
    jpeg_bytes = numpy_to_image_string(img)
    ref = image_string_to_numpy(jpeg_bytes)
    out = self._coef_decode(jpeg_bytes, 48, 64)
    diff = out.astype(int) - ref.astype(int)
    # Float triangle upsample + float color convert vs libjpeg fixed-point:
    # within +/-4 everywhere, sub-pixel on average.
    assert np.abs(diff).max() <= 4
    assert np.abs(diff).mean() < 0.6
    assert (np.abs(diff) <= 1).mean() > 0.95

  def test_decode_coef_features_helper(self):
    from tensor2robot_tpu.data import jpeg_device
    img = np.full((32, 32, 3), 128, np.uint8)
    jpeg_bytes = numpy_to_image_string(img)
    features = SpecStruct(image=TensorSpec((32, 32, 3), np.uint8, name='im',
                                           data_format='jpeg'))
    plan = native_loader.plan_for_specs(features, SpecStruct(),
                                        image_mode='coef')
    import tempfile, os
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, 'h.tfrecord')
    tfrecord.write_records(path, [build_example({'im': jpeg_bytes})])
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=1, num_epochs=1, validate=False)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    out = jpeg_device.decode_coef_features(feats, ['image'])
    assert 'image/y' not in out
    arr = np.asarray(out['image'])
    assert arr.shape == (1, 32, 32, 3)
    assert np.abs(arr.astype(int) - 128).max() <= 4


class TestGeneratorIntegration:

  def test_record_generator_uses_native(self, record_file):
    path, records, _ = record_file
    features_spec, labels_spec = _specs()
    gen = DefaultRecordInputGenerator(file_patterns=path, batch_size=4)
    gen.set_specification(features_spec, labels_spec)
    native = gen._native_iterator(ModeKeys.EVAL, 1, 0, 1, None)
    assert native is not None
    parser = ExampleParser(features_spec, labels_spec)
    ref_feats, _ = parser.parse_batch(records[:4])
    feats, labs = next(native)
    np.testing.assert_array_equal(
        np.asarray(feats['image']), np.asarray(ref_feats['image']))
    assert np.asarray(labs['target']).shape == (4, 1)

  def test_generator_full_iteration(self, record_file):
    path, _, _ = record_file
    features_spec, labels_spec = _specs()
    gen = DefaultRecordInputGenerator(file_patterns=path, batch_size=4)
    gen.set_specification(features_spec, labels_spec)
    batches = list(gen.create_dataset_iterator(
        mode=ModeKeys.TRAIN, num_epochs=2, seed=3))
    assert len(batches) == 5
    for feats, labs in batches:
      assert np.asarray(feats['image']).shape == (4, 48, 64, 3)

  def test_use_native_true_raises_on_unsupported(self, record_file):
    path, _, _ = record_file
    features_spec, labels_spec = _specs()
    features_spec.seq = TensorSpec((4,), np.float32, name='s',
                                   is_sequence=True)
    gen = DefaultRecordInputGenerator(file_patterns=path, batch_size=4,
                                      use_native=True)
    gen.set_specification(features_spec, labels_spec)
    with pytest.raises(ValueError, match='not supported'):
      gen._native_iterator(ModeKeys.TRAIN, 1, 0, 1, None)

  def test_use_native_false(self, record_file):
    path, _, _ = record_file
    features_spec, labels_spec = _specs()
    gen = DefaultRecordInputGenerator(file_patterns=path, batch_size=4,
                                      use_native=False)
    gen.set_specification(features_spec, labels_spec)
    assert gen._native_iterator(ModeKeys.TRAIN, 1, 0, 1, None) is None


def _gray_with_dots():
  img = np.full((64, 96, 3), 128, np.uint8)
  img[0:8, 0:8] = 200       # first block row
  img[56:64, 88:96] = 60    # last block — >255 empty coef slots between
  return img


class TestSparseCoef:
  """Sparse DCT entry streams: 'coef_sparse' mode round-trips exactly to
  the dense 'coef' mode tensors through the device unpack
  (record_loader.cc decode_jpeg_coef_sparse <-> jpeg_device
  unpack_sparse_coefficients)."""

  def _streams(self, images, h, w, density=0.5, batch_size=None,
               quality=95):
    import os
    import tempfile

    from tensor2robot_tpu.utils.image import jpeg_string
    from PIL import Image

    batch_size = batch_size or len(images)
    features = SpecStruct(image=TensorSpec((h, w, 3), np.uint8, name='im',
                                           data_format='jpeg'))
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, 's.tfrecord')
    # The default quality=95 shrinks quant steps so bright DCs exceed int8
    # and exercise the value-continuation entries.
    tfrecord.write_records(path, [
        build_example({'im': jpeg_string(Image.fromarray(im), quality)})
        for im in images])
    out = []
    for mode in ('coef', 'coef_sparse'):
      plan = native_loader.plan_for_specs(features, SpecStruct(),
                                          image_mode=mode,
                                          sparse_density=density)
      stream = native_loader.NativeBatchedStream(
          plan, [path], batch_size=batch_size, num_epochs=1, validate=False)
      try:
        (feats, _), = list(stream)
      finally:
        stream.close()
      out.append(feats)
    return out

  def _images(self):
    rng = np.random.RandomState(3)
    imgs = [
        # bright uniform: large positive DCs -> continuation entries
        np.full((64, 96, 3), 250, np.uint8),
        # mid-gray with two far-apart features: the all-zero blocks
        # between them make a gap longer than 255 -> skip entries
        _gray_with_dots(),
        # noisy: dense-ish coefficients
        np.clip(rng.randn(64, 96, 3) * 50 + 128, 0, 255).astype(np.uint8),
        # gradient scene
        (np.outer(np.linspace(0, 1, 64), np.linspace(0, 1, 96))[..., None]
         * [255, 180, 90]).astype(np.uint8),
    ]
    return imgs

  def test_exact_coefficient_parity(self):
    from tensor2robot_tpu.data import jpeg_device
    dense, sparse = self._streams(self._images(), 64, 96)
    sd, sv = np.asarray(sparse['image/sd']), np.asarray(sparse['image/sv'])
    y, cb, cr = jpeg_device.unpack_sparse_coefficients(sd, sv, 64, 96)
    assert np.array_equal(np.asarray(y), np.asarray(dense['image/y']))
    assert np.array_equal(np.asarray(cb), np.asarray(dense['image/cb']))
    assert np.array_equal(np.asarray(cr), np.asarray(dense['image/cr']))
    assert np.array_equal(np.asarray(sparse['image/qt']),
                          np.asarray(dense['image/qt']))
    # Both escape entry kinds were actually exercised.
    n = np.asarray(sparse['image/n'])
    assert (sd[0][:n[0]] == 0).any()  # delta-0 continuation (bright DCs)
    assert (sd[1][:n[1]] == 255).any()  # long-gap skip (empty gray blocks)

  def test_bucketed_stream_shape(self):
    _, sparse = self._streams(self._images(), 64, 96)
    sd = np.asarray(sparse['image/sd'])
    n = np.asarray(sparse['image/n'])
    assert sd.shape[1] % native_loader.SPARSE_BUCKET == 0
    assert sd.shape[1] >= int(n.max())
    assert sd.shape[1] - int(n.max()) < native_loader.SPARSE_BUCKET
    # Owned copies, not ring-buffer views (use-after-free guard).
    assert sd.base is None

  def test_all_zero_rows_unpack_to_zero(self):
    from tensor2robot_tpu.data import jpeg_device
    sd = np.zeros((2, native_loader.SPARSE_BUCKET), np.uint8)
    sv = np.zeros((2, native_loader.SPARSE_BUCKET), np.int8)
    y, cb, cr = jpeg_device.unpack_sparse_coefficients(sd, sv, 32, 32)
    assert not np.asarray(y).any()
    assert not np.asarray(cb).any() and not np.asarray(cr).any()

  def test_capacity_overflow_is_a_clear_error(self):
    rng = np.random.RandomState(0)
    noisy = [np.clip(rng.randn(128, 160, 3) * 60 + 128, 0, 255)
             .astype(np.uint8)]
    with pytest.raises(RuntimeError, match='capacity .* exceeded'):
      self._streams(noisy, 128, 160, density=0.01)

  def test_sparse_bytes_shrink_vs_dense(self):
    # Camera-like content (the workload the format exists for): gradient +
    # objects + mild sensor noise at 512x640, >= 5x fewer bytes than the
    # dense coefficient tensors (VERDICT r3 item 1 acceptance bar).
    rng = np.random.RandomState(0)
    x = np.linspace(0, 1, 640)
    yy = np.linspace(0, 1, 512)
    img = (np.outer(yy, x)[..., None] * [200, 160, 240]).astype(np.float32)
    img[100:180, 200:300] = [250, 40, 10]
    img += rng.randn(512, 640, 1) * 6
    img = np.clip(img, 0, 255).astype(np.uint8)
    # quality=75: what numpy_to_image_string (PIL default) writes — the
    # replay writer / bench record content this path actually serves.
    dense, sparse = self._streams([img], 512, 640, quality=75)
    dense_bytes = sum(np.asarray(dense['image/' + k]).nbytes
                      for k in ('y', 'cb', 'cr'))
    sparse_bytes = (np.asarray(sparse['image/sd']).nbytes +
                    np.asarray(sparse['image/sv']).nbytes)
    assert dense_bytes / sparse_bytes >= 5.0


class TestPackedCoef:
  """Packed wire ('coef_packed'): nibble AC stream + nibble DC-delta
  plane + int16 escapes + batch-hoisted quant table must round-trip
  BIT-EXACT to the dense 'coef' tensors and to the loose 'coef_sparse'
  path (record_loader.cc decode_jpeg_coef_packed <-> jpeg_device
  unpack_packed_coefficients), at ~1.8x fewer wire bytes."""

  def _streams(self, images, h, w, density=0.5, batch_size=None,
               quality=95, modes=('coef', 'coef_sparse', 'coef_packed')):
    import os
    import tempfile

    from tensor2robot_tpu.utils.image import jpeg_string
    from PIL import Image

    batch_size = batch_size or len(images)
    features = SpecStruct(image=TensorSpec((h, w, 3), np.uint8, name='im',
                                           data_format='jpeg'))
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, 'p.tfrecord')
    tfrecord.write_records(path, [
        build_example({'im': jpeg_string(Image.fromarray(im), quality)})
        for im in images])
    out = []
    for mode in modes:
      plan = native_loader.plan_for_specs(features, SpecStruct(),
                                          image_mode=mode,
                                          sparse_density=density)
      stream = native_loader.NativeBatchedStream(
          plan, [path], batch_size=batch_size, num_epochs=1, validate=False)
      try:
        (feats, _), = list(stream)
      finally:
        stream.close()
      out.append(feats)
    return out

  def _images(self):
    rng = np.random.RandomState(3)
    return [
        # bright uniform: large DC values -> DC escape entries
        np.full((64, 96, 3), 250, np.uint8),
        # far-apart features: >255-coef gaps -> multiple skip bytes
        _gray_with_dots(),
        # noisy: dense-ish coefficients, AC values beyond +/-7 -> escapes
        np.clip(rng.randn(64, 96, 3) * 50 + 128, 0, 255).astype(np.uint8),
        # gradient scene (the camera-like common case)
        (np.outer(np.linspace(0, 1, 64), np.linspace(0, 1, 96))[..., None]
         * [255, 180, 90]).astype(np.uint8),
    ]

  def test_bit_exact_vs_dense_and_loose_sparse(self):
    from tensor2robot_tpu.data import jpeg_device
    dense, sparse, packed = self._streams(self._images(), 64, 96)
    y, cb, cr = jpeg_device.unpack_packed_coefficients(
        np.asarray(packed['image/pw']), np.asarray(packed['image/se']),
        np.asarray(packed['image/dcn']), 64, 96)
    # Bit-exact vs the dense coef mode...
    assert np.array_equal(np.asarray(y), np.asarray(dense['image/y']))
    assert np.array_equal(np.asarray(cb), np.asarray(dense['image/cb']))
    assert np.array_equal(np.asarray(cr), np.asarray(dense['image/cr']))
    # ...and therefore vs the loose sparse path's unpack too.
    ys, cbs, crs = jpeg_device.unpack_sparse_coefficients(
        np.asarray(sparse['image/sd']), np.asarray(sparse['image/sv']),
        64, 96)
    assert np.array_equal(np.asarray(y), np.asarray(ys))
    assert np.array_equal(np.asarray(cb), np.asarray(cbs))
    assert np.array_equal(np.asarray(cr), np.asarray(crs))
    # Every wire mechanism was actually exercised by this image set.
    pw = np.asarray(packed['image/pw'])
    d4, v4 = pw >> 4, pw & 15
    assert ((v4 == 0) & (d4 > 0)).any()      # skip bytes (long gaps)
    assert (v4 == 8).any()                   # AC escapes
    codes = np.stack([np.asarray(packed['image/dcn']) & 15,
                      np.asarray(packed['image/dcn']) >> 4], axis=2)
    assert (codes == 8).any()                # DC escapes (bright frame)
    assert np.asarray(packed['image/se']).any()

  def test_quant_table_hoisted_to_one_row(self):
    dense, _, packed = self._streams(self._images(), 64, 96)
    qt = np.asarray(packed['image/qt'])
    assert qt.shape == (1, 3, 64)
    assert np.array_equal(qt[0], np.asarray(dense['image/qt'])[0])

  def test_unpack_packed_features_broadcasts_qt(self):
    from tensor2robot_tpu.data import jpeg_device
    _, _, packed = self._streams(self._images(), 64, 96)
    out = jpeg_device.unpack_packed_features(
        dict(packed), {'image': (64, 96)})
    assert 'image/pw' not in out and 'image/dcn' not in out
    assert np.asarray(out['image/qt']).shape == (4, 3, 64)
    assert np.asarray(out['image/y']).shape == (4, 8, 12, 64)

  def test_bucketed_stream_shapes(self):
    _, _, packed = self._streams(self._images(), 64, 96)
    pw = np.asarray(packed['image/pw'])
    se = np.asarray(packed['image/se'])
    assert pw.shape[1] % native_loader.PACKED_BUCKET == 0
    assert se.shape[1] % native_loader.ESCAPE_BUCKET == 0
    # Owned copies, not ring-buffer views (use-after-free guard).
    assert pw.base is None and se.base is None

  def test_mixed_quality_batch_is_a_clear_error(self):
    # Two encode qualities -> two quant tables -> the hoist must refuse
    # loudly, naming the loose format as the remedy.
    import os
    import tempfile

    from tensor2robot_tpu.utils.image import jpeg_string
    from PIL import Image

    img = self._images()[3]
    features = SpecStruct(image=TensorSpec((64, 96, 3), np.uint8,
                                           name='im', data_format='jpeg'))
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, 'mixed.tfrecord')
    tfrecord.write_records(path, [
        build_example({'im': jpeg_string(Image.fromarray(img), 95)}),
        build_example({'im': jpeg_string(Image.fromarray(img), 40)})])
    plan = native_loader.plan_for_specs(features, SpecStruct(),
                                        image_mode='coef_packed')
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=2, num_epochs=1, validate=False)
    try:
      with pytest.raises(RuntimeError, match='batch-uniform.*coef_sparse'):
        list(stream)
    finally:
      stream.close()

  def test_empty_payload_rides_along_as_zero_image(self):
    # An empty bytes payload decodes to an all-zero image (tfdata parity)
    # and its all-zero "no table" sentinel must not trip the uniformity
    # check against the batch's real rows.
    import os
    import tempfile

    from tensor2robot_tpu.data import jpeg_device
    from tensor2robot_tpu.utils.image import jpeg_string
    from PIL import Image

    img = self._images()[3]
    features = SpecStruct(image=TensorSpec((64, 96, 3), np.uint8,
                                           name='im', data_format='jpeg'))
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, 'empty.tfrecord')
    tfrecord.write_records(path, [
        build_example({'im': jpeg_string(Image.fromarray(img), 95)}),
        build_example({'im': b''})])
    plan = native_loader.plan_for_specs(features, SpecStruct(),
                                        image_mode='coef_packed')
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=2, num_epochs=1, validate=False)
    try:
      (feats, _), = list(stream)
    finally:
      stream.close()
    y, cb, cr = jpeg_device.unpack_packed_coefficients(
        np.asarray(feats['image/pw']), np.asarray(feats['image/se']),
        np.asarray(feats['image/dcn']), 64, 96)
    assert np.asarray(y)[0].any()            # real frame decoded
    assert not np.asarray(y)[1].any()        # empty payload -> zeros
    assert not np.asarray(cb)[1].any() and not np.asarray(cr)[1].any()
    assert np.asarray(feats['image/qt']).shape == (1, 3, 64)
    assert np.asarray(feats['image/qt']).any()  # the REAL row's table

  def test_capacity_overflow_is_a_clear_error(self):
    rng = np.random.RandomState(0)
    noisy = [np.clip(rng.randn(128, 160, 3) * 60 + 128, 0, 255)
             .astype(np.uint8)]
    with pytest.raises(RuntimeError, match='capacity .* exceeded'):
      self._streams(noisy, 128, 160, density=0.01,
                    modes=('coef_packed',))

  def test_packed_bytes_shrink_vs_loose_sparse(self):
    # The round-10 acceptance shape: on the camera-like 512x640 frame
    # the packed wire must carry >= 1.4x fewer bytes than the loose
    # sparse wire (measured ~1.76x on the bench content incl. padding).
    rng = np.random.RandomState(0)
    x = np.linspace(0, 1, 640)
    yy = np.linspace(0, 1, 512)
    img = (np.outer(yy, x)[..., None] * [200, 160, 240]).astype(np.float32)
    img[100:180, 200:300] = [250, 40, 10]
    img += rng.randn(512, 640, 1) * 6
    img = np.clip(img, 0, 255).astype(np.uint8)
    sparse, packed = self._streams([img], 512, 640, quality=75,
                                   modes=('coef_sparse', 'coef_packed'))
    sparse_bytes = sum(np.asarray(sparse['image/' + k]).nbytes
                       for k in ('sd', 'sv', 'qt', 'n'))
    packed_bytes = sum(np.asarray(packed['image/' + k]).nbytes
                       for k in ('pw', 'se', 'dcn', 'qt'))
    assert sparse_bytes / packed_bytes >= 1.4

  def test_full_qtopt_feature_set_round_trips(self, tmp_path):
    """The full QT-Opt off-policy shape on one packed plan: BOTH image
    features (state + next-state frame), the action/status floats, a
    varlen float rider and an optional float rider — images bit-exact
    through the packed wire and pixel-close to the Python parser's full
    decode, non-image features byte-identical (incl. the round-5 varlen
    pad/clip and optional dense-batch semantics)."""
    from tensor2robot_tpu.data import jpeg_device
    from tensor2robot_tpu.utils.image import (
        image_string_to_numpy,
        numpy_to_image_string,
    )

    h, w = 64, 96
    rng = np.random.RandomState(7)
    features = SpecStruct(
        image=TensorSpec((h, w, 3), np.uint8, name='image_1',
                         data_format='jpeg'),
        next_image=TensorSpec((h, w, 3), np.uint8, name='next/image_1',
                              data_format='jpeg'),
        close=TensorSpec((1,), np.float32, name='gripper_closed'),
        tags=TensorSpec((5,), np.float32, name='tags',
                        varlen_default_value=-1.0),
        aux=TensorSpec((2,), np.float32, name='aux', is_optional=True),
    )
    labels = SpecStruct(
        reward=TensorSpec((1,), np.float32, name='grasp_success'))
    frames, records = [], []
    for i in range(6):
      img = (np.outer(np.linspace(0, 1, h), np.linspace(0, 1, w))[..., None]
             * rng.randint(120, 255, 3)).astype(np.uint8)
      nxt = np.clip(img.astype(np.int16) + 12, 0, 255).astype(np.uint8)
      frames.append((img, nxt))
      records.append(build_example({
          'image_1': numpy_to_image_string(img),
          'next/image_1': numpy_to_image_string(nxt),
          'gripper_closed': np.asarray([float(i % 2)], np.float32),
          'tags': rng.rand(3 + i % 4).astype(np.float32),  # varlen: 3..6
          'aux': rng.rand(2).astype(np.float32),
          'grasp_success': np.asarray([0.5 * i], np.float32),
      }))
    path = str(tmp_path / 'qtopt.tfrecord')
    tfrecord.write_records(path, records)

    plan = native_loader.plan_for_specs(features, labels,
                                        image_mode='coef_packed')
    assert plan is not None  # varlen/optional riders must not kill it
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=6, num_epochs=1, validate=False)
    try:
      (feats, labs), = list(stream)
    finally:
      stream.close()

    # Non-image features: byte-identical to the Python parser.
    parser = ExampleParser(features, labels)
    ref_feats, ref_labs = parser.parse_batch(records)
    for key in ('close', 'tags', 'aux'):
      assert np.array_equal(np.asarray(feats[key]),
                            np.asarray(ref_feats[key])), key
    assert np.array_equal(np.asarray(labs['reward']),
                          np.asarray(ref_labs['reward']))

    # BOTH image features ship packed, unpack bit-consistently, and
    # decode pixel-close to a host decode (existing +/-4 tolerance).
    for key, frame_index in (('image', 0), ('next_image', 1)):
      assert key + '/pw' in feats and key + '/dcn' in feats
      unpacked = jpeg_device.unpack_packed_features(
          {k: np.asarray(v) for k, v in feats.items()
           if k.startswith(key + '/')}, {key: (h, w)})
      decoded = np.asarray(jpeg_device.decode_jpeg_coefficients(
          unpacked[key + '/y'], unpacked[key + '/cb'],
          unpacked[key + '/cr'], np.asarray(unpacked[key + '/qt'])))
      for row in range(6):
        host = image_string_to_numpy(
            numpy_to_image_string(frames[row][frame_index]))
        diff = decoded[row].astype(int) - host.astype(int)
        assert np.abs(diff).max() <= 4, (key, row)


class TestDroppedRemainderErrors:

  def test_corrupt_record_in_dropped_partial_batch_is_swallowed(
      self, tmp_path):
    """drop_remainder semantics: a decode error on a record that falls in
    the discarded EOF partial batch must not error the stream. The
    fail/swallow decision is deferred to batch completion in the C++
    worker, so this holds deterministically (not just when the reader
    wins the race to EOF)."""
    features = SpecStruct(image=TensorSpec((16, 16, 3), np.uint8,
                                           name='im', data_format='jpeg'))
    rng = np.random.RandomState(0)
    records = [build_example({'im': numpy_to_image_string(
        rng.randint(0, 255, (16, 16, 3), dtype=np.uint8))})
        for _ in range(4)]
    # Record 5 of 5 is garbage; batch_size=4 drops it as the remainder.
    records.append(build_example({'im': b'not a jpeg'}))
    path = str(tmp_path / 'tail.tfrecord')
    tfrecord.write_records(path, records)
    plan = native_loader.plan_for_specs(features, SpecStruct())
    for _ in range(10):  # the old behavior was a thread-timing race
      stream = native_loader.NativeBatchedStream(
          plan, [path], batch_size=4, num_epochs=1)
      try:
        batches = list(stream)
      finally:
        stream.close()
      assert len(batches) == 1
      assert np.asarray(batches[0][0]['image']).shape == (4, 16, 16, 3)

  def test_corrupt_record_in_delivered_batch_still_fails(self, tmp_path):
    features = SpecStruct(image=TensorSpec((16, 16, 3), np.uint8,
                                           name='im', data_format='jpeg'))
    records = [build_example({'im': b'not a jpeg'})
               for _ in range(4)]
    path = str(tmp_path / 'bad.tfrecord')
    tfrecord.write_records(path, records)
    plan = native_loader.plan_for_specs(features, SpecStruct())
    stream = native_loader.NativeBatchedStream(
        plan, [path], batch_size=4, num_epochs=1)
    with pytest.raises(RuntimeError, match='jpeg'):
      try:
        list(stream)
      finally:
        stream.close()
