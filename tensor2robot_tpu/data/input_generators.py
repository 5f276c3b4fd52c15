"""Input generators: bind model specs to data sources, yield numpy batches.

Parity targets:
  * AbstractInputGenerator     ref input_generators/abstract_input_generator.py:38
  * DefaultRecordInputGenerator / FractionalRecordInputGenerator /
    MultiEvalRecordInputGenerator  ref input_generators/default_input_generator.py:54,118,141
  * GeneratorInputGenerator / DefaultRandomInputGenerator /
    DefaultConstantInputGenerator  ref default_input_generator.py:156,210,223

Redesign note: the reference returns Estimator ``input_fn``s; here a generator
yields ``(features, labels)`` numpy batches sized for the *global* batch. The
trainer shards each batch over the mesh data axis and runs the preprocessor
inside the jitted train step (device-side, XLA-fused) — so generators stay
pure host-side decode.
"""

from __future__ import annotations

import abc
import json
import os
from typing import Callable, Dict, Iterator, Optional, Sequence, Union

import numpy as np

from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.data.parser import ExampleParser
from tensor2robot_tpu.data.pipeline import (
    BatchedExampleStream,
    RecordDataset,
    parse_file_patterns,
)
from tensor2robot_tpu.modes import ModeKeys, assert_valid_mode


def prefetch_iterator(iterator: Iterator, depth: int,
                      label: str = 'default') -> Iterator:
  """Wraps an iterator with a ``depth``-deep background prefetch queue.

  Producer uses timed puts against a stop event (same discipline as
  BatchedExampleStream, data/pipeline.py): when the consumer abandons or
  closes the generator, the worker thread exits instead of blocking in
  q.put forever holding decoded batches and open readers.

  ``label`` names this queue's telemetry series (the generators pass the
  mode), so a train and an eval queue in one process report separately.
  """
  import queue
  import threading

  from tensor2robot_tpu.observability import get_registry, span
  from tensor2robot_tpu.observability.pipeline_xray import StageMeter

  q: 'queue.Queue' = queue.Queue(maxsize=depth)
  sentinel = object()
  error: list = []
  stop = threading.Event()
  # Resolved once per iterator; the per-batch path only bumps them. The
  # gauge reads near zero when the trainer outruns the pipeline (data-
  # starved — matches a high goodput 'data' fraction) and near ``depth``
  # when decode comfortably leads the device.
  registry = get_registry()
  prefetched = registry.counter_family(
      'data/batches_prefetched', ('queue',)).series(label)
  queue_depth = registry.gauge_family(
      'data/prefetch_queue_depth', ('queue',)).series(label)
  # Pipeline X-ray 'batch' stage: this producer is the ONE batch-handoff
  # point every generator path (native, Python parser, synthetic) runs
  # through, so it owns the stage's example count — the flow meter.
  # No busy time is charged here: the handoff is a queue put whose only
  # real cost is downstream backpressure (queue-full waits), which must
  # NOT be attributed to this stage; the stage's health signals are the
  # flow count and the prefetch-depth gauge, and it never competes in
  # the capacity argmin (native pack cost is the span data.pack). The
  # wait itself is the span data.handoff_wait: near the whole of this
  # thread's time when input is hidden behind the device.
  batch_meter = StageMeter('batch')

  def _put(item) -> bool:
    while not stop.is_set():
      try:
        q.put(item, timeout=0.1)
        queue_depth.set(q.qsize())
        return True
      except queue.Full:
        continue
    return False

  def _batch_examples(item) -> int:
    """Leading dim of a (features, labels) item's array leaves.

    A leading dim of 1 only wins when every leaf agrees: the packed
    coef wire ships its batch-hoisted quant table as [1, 3, 64], which
    must not masquerade as the batch size.
    """
    features = item[0] if isinstance(item, tuple) else item
    examples = 0
    try:
      for key in features:
        shape = getattr(features[key], 'shape', None)
        if shape and (not examples or examples == 1):
          examples = int(shape[0])
          if examples > 1:
            break
    except TypeError:
      pass
    return examples

  def _producer():
    try:
      for index, item in enumerate(iterator):
        prefetched.inc()
        batch_meter.add(examples=_batch_examples(item))
        # The queue is full: the consumer is slower (healthy back-pressure).
        with span('data.handoff_wait', batch=index):
          handed = _put(item)
        if not handed:
          return
    except BaseException as e:  # surfaced on the consumer side
      error.append(e)
    finally:
      _put(sentinel)
      # A finished/abandoned queue must not advertise its last depth
      # forever: stale nonzero depth reads as a healthy full pipeline.
      queue_depth.set(0)

  thread = threading.Thread(target=_producer, daemon=True,
                            name='t2r-prefetch')
  thread.start()

  def _consume():
    try:
      while True:
        item = q.get()
        if item is sentinel:
          if error:
            raise error[0]
          return
        yield item
    finally:
      stop.set()

  return _consume()


class AbstractInputGenerator(abc.ABC):
  """Binds a model's (preprocessor's) in-specs to a batch source."""

  def __init__(self, batch_size: int = 32, prefetch: int = 2):
    self._batch_size = int(batch_size)
    self._prefetch = int(prefetch)
    self._feature_spec = None
    self._label_spec = None
    self._raw_feature_spec = None  # device-decode: on-disk JPEG specs
    self._device_decode_preprocessor = None
    self._preprocess_fn = None

  @property
  def batch_size(self) -> int:
    return self._batch_size

  @batch_size.setter
  def batch_size(self, value: int) -> None:
    self._batch_size = int(value)

  def set_specification_from_model(self, model, mode: str) -> None:
    """Pulls the in-feature/in-label specs from the model's preprocessor.

    ref: abstract_input_generator.py:80 — the input pipeline produces what the
    preprocessor consumes, not what the model consumes.

    A DeviceDecodePreprocessor wrapper is recognized: the generator then
    plans the native loader in COEF mode against the raw (on-disk JPEG)
    specs and ships DCT coefficient tensors the wrapper finishes decoding
    on device.
    """
    assert_valid_mode(mode)
    preprocessor = model.preprocessor
    self._feature_spec = preprocessor.get_in_feature_specification(mode)
    self._label_spec = preprocessor.get_in_label_specification(mode)
    specs_lib.assert_valid_spec_structure(self._feature_spec)
    specs_lib.assert_valid_spec_structure(self._label_spec)
    self._raw_feature_spec = None
    self._device_decode_preprocessor = None
    if hasattr(preprocessor, 'raw_in_feature_specification'):
      self._raw_feature_spec = preprocessor.raw_in_feature_specification(
          mode)
      self._device_decode_preprocessor = preprocessor

  def set_specification(self, feature_spec, label_spec) -> None:
    self._feature_spec = specs_lib.flatten_spec_structure(feature_spec)
    self._label_spec = specs_lib.flatten_spec_structure(label_spec)
    # Plain specs: clear any device-decode plan a previous
    # set_specification_from_model(wrapped_model) installed.
    self._raw_feature_spec = None
    self._device_decode_preprocessor = None

  @property
  def feature_spec(self):
    return self._feature_spec

  @property
  def label_spec(self):
    return self._label_spec

  def create_dataset_iterator(
      self, mode: str,
      num_epochs: Optional[int] = None,
      shard_index: int = 0, num_shards: int = 1,
      seed: Optional[int] = None,
      prefetch: Optional[int] = None) -> Iterator:
    """Yields (features, labels) numpy batch SpecStructs.

    ``prefetch``: batches decoded ahead in a background thread so host
    parsing overlaps the device step (the reference's
    prefetch(AUTOTUNE), utils/tfdata.py:575). None uses the generator's
    default; 0 disables.
    """
    assert_valid_mode(mode)
    if self._feature_spec is None:
      raise ValueError(
          'set_specification(_from_model) must be called before creating '
          'a dataset iterator.')
    iterator = self._create_iterator(mode=mode, num_epochs=num_epochs,
                                     shard_index=shard_index,
                                     num_shards=num_shards, seed=seed)
    depth = self._prefetch if prefetch is None else prefetch
    if depth and depth > 0:
      iterator = prefetch_iterator(iterator, depth, label=mode)
    return iterator

  @abc.abstractmethod
  def _create_iterator(self, mode: str, num_epochs, shard_index, num_shards,
                       seed) -> Iterator:
    ...


class DefaultRecordInputGenerator(AbstractInputGenerator):
  """TFRecord-backed input generator, optionally joining multiple datasets.

  ``file_patterns``: 'path/a*' or 'tfrecord:path/a*,path/b*'.
  ``dataset_map``: {dataset_key: file_patterns} for multi-dataset zip driven
  by the specs' ``dataset_key`` attributes.

  When the specs qualify (plain tf.Example, fixed shapes, JPEG images), the
  hot path runs on the native C++ loader (data/native/record_loader.cc):
  multithreaded record read + proto parse + JPEG decode outside the GIL,
  the analog of the reference's C++ tf.data pipeline
  (utils/tfdata.py:527-575). ``use_native=False`` (or T2R_NATIVE_LOADER=0)
  forces the pure-Python pipeline; 'auto' takes it only when the specs
  are unsupported — a library that fails to build raises.
  """

  def __init__(self, file_patterns: Optional[str] = None,
               dataset_map: Optional[Dict[str, str]] = None,
               batch_size: int = 32,
               shuffle_buffer_size: int = 500,
               prefetch: int = 2,
               use_native: Union[bool, str] = 'auto',
               num_native_threads: Optional[int] = None,
               sequence_max_len: Optional[int] = None,
               skip_corrupt_records: bool = False,
               max_corrupt_records: int = 100,
               max_corrupt_records_per_file: int = 10):
    """``sequence_max_len``: step capacity bound for SequenceExample
    (is_sequence) specs on the native fast path — e.g. the workload's
    episode-length bound. Without it sequence datasets read through the
    Python parser (native_loader.plan_for_specs).

    ``skip_corrupt_records``: quarantine corrupt/truncated records instead
    of raising, up to ``max_corrupt_records`` across the run and
    ``max_corrupt_records_per_file`` in any one file; exhausting either
    budget raises CorruptionBudgetExceeded naming the offending file
    (docs/reliability.md). Counters surface in train metrics. Only the
    Python pipeline can skip, so this disables the native fast path.
    """
    super().__init__(batch_size=batch_size)
    if not file_patterns and not dataset_map:
      raise ValueError('file_patterns or dataset_map is required.')
    if file_patterns and dataset_map:
      raise ValueError('file_patterns and dataset_map are mutually exclusive.')
    if skip_corrupt_records and use_native is True:
      raise ValueError(
          'use_native=True is incompatible with skip_corrupt_records: '
          'only the Python pipeline can quarantine corrupt records.')
    self._file_patterns = file_patterns
    self._dataset_map = dataset_map
    self._shuffle_buffer_size = shuffle_buffer_size
    self._prefetch = prefetch
    self._use_native = use_native
    self._num_native_threads = num_native_threads
    self._sequence_max_len = sequence_max_len
    self._skip_corrupt_records = skip_corrupt_records
    self._quarantine = None
    if skip_corrupt_records:
      from tensor2robot_tpu.reliability.quarantine import RecordQuarantine
      self._quarantine = RecordQuarantine(
          max_corrupt_records=max_corrupt_records,
          max_corrupt_records_per_file=max_corrupt_records_per_file)

  @property
  def quarantine(self):
    """The RecordQuarantine counting this generator's skips (or None)."""
    return self._quarantine

  def _dataset_files(self) -> Dict[str, str]:
    if self._dataset_map is not None:
      return dict(self._dataset_map)
    return {'': self._file_patterns}

  def _native_iterator(self, mode, num_epochs, shard_index, num_shards, seed):
    """Returns a native-loader batch iterator, or None to fall back."""
    from tensor2robot_tpu.data import native_loader

    if self._skip_corrupt_records and self._raw_feature_spec is None:
      # Corrupt-record quarantine only exists in the Python reader; the
      # native loader hard-fails on bad CRCs. (use_native=True was
      # already rejected in __init__; device-decode streams have no
      # Python fallback, so they cannot combine with skip mode either.)
      return None
    if self._raw_feature_spec is not None:
      if self._skip_corrupt_records:
        raise ValueError(
            'skip_corrupt_records is not supported with a '
            'DeviceDecodePreprocessor (native-only stream).')
      # Device-decode wrapper in play: plan against the on-disk JPEG specs
      # in coef mode; the stream's key/{y,cb,cr,qt} outputs match the
      # wrapper's in-specs. No Python fallback exists for coef shipping —
      # every unavailability is a hard error, never a silent fallthrough
      # to a parser that cannot produce coefficient tensors.
      if self._use_native is False or not native_loader.native_loader_enabled():
        raise ValueError(
            'DeviceDecodePreprocessor requires the native loader '
            '(use_native must not be False; T2R_NATIVE_LOADER must not '
            'disable it).')
      if self._dataset_map is not None:
        raise ValueError(
            'DeviceDecodePreprocessor does not support multi-dataset zip.')
      wire_format = getattr(self._device_decode_preprocessor,
                            'wire_format', None)
      if wire_format is None:  # pre-wire_format wrappers: sparse bool
        wire_format = 'sparse' if getattr(
            self._device_decode_preprocessor, 'sparse', False) else 'dense'
      image_mode = {'packed': 'coef_packed', 'sparse': 'coef_sparse',
                    'dense': 'coef'}[wire_format]
      plan = native_loader.plan_for_specs(
          self._raw_feature_spec, self._label_spec,
          image_mode=image_mode,
          sparse_density=float(getattr(self._device_decode_preprocessor,
                                       'sparse_density', 0.5)))
      if plan is None:
        raise ValueError(
            'DeviceDecodePreprocessor requires the native loader fast path '
            '(plain Example, fixed shapes, 4:2:0-eligible JPEG specs).')
      _, files = parse_file_patterns(self._dataset_files()[''])
      files = files[shard_index::num_shards]
      if not files:
        raise ValueError(
            'Host {} of {} has no record files for the device-decode '
            'stream; provide at least num_shards files.'.format(
                shard_index, num_shards))
      import jax

      stream = native_loader.NativeBatchedStream(
          plan, files, batch_size=self._batch_size,
          shuffle=(mode == ModeKeys.TRAIN),
          shuffle_buffer=self._shuffle_buffer_size,
          num_epochs=num_epochs, seed=seed,
          num_threads=self._num_native_threads, validate=False,
          # Per-host buckets diverge across processes; multi-host SPMD
          # needs the host-invariant full-capacity shape.
          bucket_sparse=jax.process_count() == 1)
      return iter(stream)
    if self._use_native is False or not native_loader.native_loader_enabled():
      return None
    plan = native_loader.plan_for_specs(
        self._feature_spec, self._label_spec,
        sequence_max_len=self._sequence_max_len)
    if plan is None:
      if self._use_native is True:
        raise ValueError(
            'use_native=True but the specs are not supported by the native '
            'loader (sequences without sequence_max_len, PNG images, '
            'duplicate or unnamed feature names).')
      return None
    # Through _dataset_files() so subclass overrides (e.g. Fractional's
    # file_fraction truncation) apply to the native path too. One file
    # list per dataset key: the native loader zips multi-dataset plans
    # itself (record_loader.cc file groups).
    files_by_key = {}
    for key, patterns in self._dataset_files().items():
      _, files = parse_file_patterns(patterns)
      files = files[shard_index::num_shards]
      if not files:
        return None
      files_by_key[key] = files
    if set(plan.dataset_keys) != set(files_by_key):
      # Specs reference dataset keys with no configured files (the
      # Python path raises the clear error), OR the dataset_map names
      # datasets no spec reads — the Python pipeline still ZIPS those
      # (epoch ends at the shortest dataset), so the native path must
      # not silently change epoch length/pairing by ignoring them.
      return None
    stream_files = (files_by_key[''] if plan.dataset_keys == ['']
                    else files_by_key)
    # A library that does not build, or a stream the loader rejects, is
    # an error under 'auto' too: the Python parser is several times
    # slower, and a run that silently took it reports a host rate that
    # is not the system's.
    stream = native_loader.NativeBatchedStream(
        plan, stream_files, batch_size=self._batch_size,
        shuffle=(mode == ModeKeys.TRAIN),
        shuffle_buffer=self._shuffle_buffer_size,
        num_epochs=num_epochs, seed=seed,
        num_threads=self._num_native_threads)
    return iter(stream)

  def _create_iterator(self, mode, num_epochs, shard_index, num_shards, seed):
    native = self._native_iterator(mode, num_epochs, shard_index,
                                   num_shards, seed)
    if native is not None:
      return native
    parser = ExampleParser(self._feature_spec, self._label_spec)
    datasets = {
        key: RecordDataset(patterns, dataset_key=key,
                           shard_index=shard_index, num_shards=num_shards,
                           skip_corrupt_records=self._skip_corrupt_records,
                           quarantine=self._quarantine)
        for key, patterns in self._dataset_files().items()
    }
    missing = set(parser.dataset_keys) - set(datasets)
    if missing:
      raise ValueError(
          'Specs reference dataset keys {} with no configured files; have {}.'
          .format(sorted(missing), sorted(datasets)))
    # prefetch=0: the base class's prefetch_iterator wrapper is the ONE
    # background-decode mechanism (stacking the stream's own worker on top
    # would double the threads and the buffered-batch memory).
    stream = BatchedExampleStream(
        datasets, parser, batch_size=self._batch_size,
        shuffle=(mode == ModeKeys.TRAIN),
        shuffle_buffer=self._shuffle_buffer_size,
        num_epochs=num_epochs, seed=seed, prefetch=0)
    return iter(stream)


class FractionalRecordInputGenerator(DefaultRecordInputGenerator):
  """Uses only a fraction of the matched files (data ablations, ref :118)."""

  def __init__(self, file_fraction: float = 1.0, **kwargs):
    super().__init__(**kwargs)
    if not 0.0 < file_fraction <= 1.0:
      raise ValueError('file_fraction must be in (0, 1].')
    self._file_fraction = file_fraction

  def _dataset_files(self) -> Dict[str, str]:
    out = {}
    for key, patterns in super()._dataset_files().items():
      if self._file_fraction < 1.0:
        _, files = parse_file_patterns(patterns)
        n = max(1, int(self._file_fraction * len(files)))
        patterns = ','.join(files[:n])
      out[key] = patterns
    return out


def get_multi_eval_name(default: Optional[str] = None) -> Optional[str]:
  """Reads the eval-dataset selector from TF_CONFIG (ref :42-50)."""
  tf_config = os.environ.get('TF_CONFIG')
  if not tf_config:
    return default
  try:
    return json.loads(tf_config).get('multi_eval_name', default)
  except (ValueError, AttributeError):
    return default


class MultiEvalRecordInputGenerator(DefaultRecordInputGenerator):
  """Picks the eval dataset named by TF_CONFIG.multi_eval_name (ref :141)."""

  def __init__(self, eval_map: Dict[str, str], **kwargs):
    multi_eval_name = get_multi_eval_name()
    if multi_eval_name is None:
      raise ValueError('TF_CONFIG.multi_eval_name must be set for '
                       'MultiEvalRecordInputGenerator.')
    if multi_eval_name not in eval_map:
      raise ValueError('multi_eval_name {!r} not in eval_map {}.'.format(
          multi_eval_name, sorted(eval_map)))
    self.multi_eval_name = multi_eval_name
    super().__init__(file_patterns=eval_map[multi_eval_name], **kwargs)


class GeneratorInputGenerator(AbstractInputGenerator):
  """Wraps a python generator of spec-conforming numpy batches (ref :156)."""

  def __init__(self, batch_generator_fn: Optional[Callable] = None,
               batch_size: int = 32, sequence_length: Optional[int] = None):
    super().__init__(batch_size=batch_size)
    self._batch_generator_fn = batch_generator_fn
    self._sequence_length = sequence_length

  def _generate_batch(self, seed: Optional[int]):
    if self._batch_generator_fn is None:
      raise NotImplementedError(
          'Provide batch_generator_fn or override _generate_batch.')
    return self._batch_generator_fn(self._batch_size)

  def _create_iterator(self, mode, num_epochs, shard_index, num_shards, seed):
    def _iter():
      step = 0
      while num_epochs is None or step < num_epochs:
        batch = self._generate_batch(None if seed is None else seed + step)
        if isinstance(batch, tuple):
          features, labels = batch
        else:
          features, labels = batch, None
        features = specs_lib.validate_and_pack(
            self._feature_spec, features, ignore_batch=True)
        if labels is not None and len(self._label_spec):
          labels = specs_lib.validate_and_pack(
              self._label_spec, labels, ignore_batch=True)
        yield features, labels
        step += 1
    return _iter()


class DefaultRandomInputGenerator(GeneratorInputGenerator):
  """Spec-conforming random batches — the test-data backbone (ref :210)."""

  def _generate_batch(self, seed: Optional[int]):
    features = specs_lib.make_random_numpy(
        self._feature_spec, batch_size=self._batch_size,
        sequence_length=self._sequence_length or 3, seed=seed)
    labels = specs_lib.make_random_numpy(
        self._label_spec, batch_size=self._batch_size,
        sequence_length=self._sequence_length or 3,
        seed=None if seed is None else seed + 977)
    return features, labels


class DefaultConstantInputGenerator(GeneratorInputGenerator):
  """Spec-conforming constant batches (ref :223)."""

  def __init__(self, constant_value: float, **kwargs):
    super().__init__(**kwargs)
    self._constant_value = constant_value

  def _generate_batch(self, seed: Optional[int]):
    features = specs_lib.make_constant_numpy(
        self._feature_spec, self._constant_value, batch_size=self._batch_size,
        sequence_length=self._sequence_length or 3)
    labels = specs_lib.make_constant_numpy(
        self._label_spec, self._constant_value, batch_size=self._batch_size,
        sequence_length=self._sequence_length or 3)
    return features, labels
