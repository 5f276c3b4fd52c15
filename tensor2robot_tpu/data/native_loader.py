"""ctypes front-end for the native C++ record loader.

The C++ side (``data/native/record_loader.cc``) is the framework's native
data-loader runtime: TFRecord framing, tf.Example wire parsing, libjpeg
decode and batch assembly on a worker thread pool, with batches landing in a
ring of preallocated buffers. This module:

  * builds the shared library on first use (g++, cached by a hash of
    the source and the flags);
  * decides, from a feature/label spec pair, whether the fast path supports
    the dataset (``plan_for_specs``). Since round 6 the fast path covers
    sequences (given ``sequence_max_len``), varlen pad/clip, optional
    features, and multi-dataset zip; the Python-parser fallback list is
    PNG images only (plus structurally unparseable specs: unnamed or
    duplicate feature names, object dtype);
  * exposes :class:`NativeBatchedStream`, an iterator of ``(features,
    labels)`` SpecStruct batches matching BatchedExampleStream's contract.

Error delivery contract: creating a stream validates CONFIG only; the
C++ reader/worker threads start on the first ``next()``, so every
data-dependent error (missing file, corrupt record, decode failure,
frame-count mismatch) surfaces at iteration — deterministically, never
racing the constructor.

Parity target: the reference's input hot path is TF's C++ tf.data runtime
(/root/reference/utils/tfdata.py:527-575 — parallel_interleave + map with
num_parallel_calls + prefetch(AUTOTUNE)); this is the equivalent component,
sized to host cores via the ``threads`` knob.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.specs.tensor_spec import TensorSpec, bfloat16

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), 'native')
_SOURCE = os.path.join(_NATIVE_DIR, 'record_loader.cc')
_BUILD_LOCK = threading.Lock()
_LIB = None

# Field kinds, mirroring record_loader.cc's FieldKind.
_KIND_FLOAT = 0
_KIND_INT = 1
_KIND_IMAGE_FULL = 2
_KIND_IMAGE_COEF = 3
_KIND_IMAGE_COEF_SPARSE = 4
_KIND_IMAGE_COEF_PACKED = 5

# Bucket granularity (entries) for sparse coefficient streams: per-batch
# max entry counts are rounded up to a multiple of this before slicing, so
# the device-side unpack sees few distinct shapes (bounded jit cache) while
# transfer padding stays under ~7% at realistic densities.
SPARSE_BUCKET = 4096

# Bucket granularities for the PACKED wire ('coef_packed'): the nibble
# stream averages ~1 byte per AC nonzero (vs 2 for loose sparse), so a
# finer bucket keeps the padding share comparable; the escape stream is
# two orders of magnitude smaller and buckets finer still.
PACKED_BUCKET = 2048
ESCAPE_BUCKET = 256


_COMPILE_CMD = ('g++', '-O2', '-fPIC', '-shared', '-std=c++17', '-msse4.2')
_LINK_LIBS = ('-ljpeg', '-lpthread')


def _so_path() -> str:
  """The library's path, NAMED by a hash of the source and the flags.

  A file time says nothing once a tree has been copied or checked out,
  so a stale library is one whose name no longer matches.
  """
  digest = hashlib.sha1(' '.join(_COMPILE_CMD + _LINK_LIBS).encode())
  with open(_SOURCE, 'rb') as f:
    digest.update(f.read())
  return os.path.join(
      _NATIVE_DIR, '_record_loader.{}.so'.format(digest.hexdigest()[:16]))


def build_native(force: bool = False) -> str:
  """Compiles record_loader.cc into a shared library (cached by content
  hash; libraries built from other sources or flags are removed)."""
  so = _so_path()
  with _BUILD_LOCK:
    if not force and os.path.exists(so):
      return so
    tmp = so + '.build.{}'.format(os.getpid())
    cmd = list(_COMPILE_CMD) + ['-o', tmp, _SOURCE] + list(_LINK_LIBS)
    try:
      subprocess.run(cmd, check=True, capture_output=True, text=True)
    except (OSError, subprocess.CalledProcessError) as e:
      raise RuntimeError('native loader build failed ({}):\n{}'.format(
          ' '.join(cmd), getattr(e, 'stderr', None) or e)) from e
    os.replace(tmp, so)  # atomic: concurrent builders race benignly
    for stale in glob.glob(os.path.join(_NATIVE_DIR, '_record_loader*.so')):
      if stale != so:
        with contextlib.suppress(FileNotFoundError):  # a racing builder
          os.unlink(stale)
  return so


def _lib():
  global _LIB
  if _LIB is None:
    lib = ctypes.CDLL(build_native())
    lib.t2r_loader_create.restype = ctypes.c_void_p
    lib.t2r_loader_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.t2r_loader_last_error.restype = ctypes.c_char_p
    lib.t2r_loader_last_error.argtypes = [ctypes.c_void_p]
    lib.t2r_loader_num_buffers.restype = ctypes.c_int
    lib.t2r_loader_num_buffers.argtypes = [ctypes.c_void_p]
    lib.t2r_loader_buffer_size.restype = ctypes.c_longlong
    lib.t2r_loader_buffer_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.t2r_loader_buffer_ptr.restype = ctypes.c_void_p
    lib.t2r_loader_buffer_ptr.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                          ctypes.c_int]
    lib.t2r_loader_ring_size.restype = ctypes.c_int
    lib.t2r_loader_ring_size.argtypes = [ctypes.c_void_p]
    lib.t2r_loader_next.restype = ctypes.c_int
    lib.t2r_loader_next.argtypes = [ctypes.c_void_p]
    lib.t2r_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.t2r_loader_destroy.argtypes = [ctypes.c_void_p]
    lib.t2r_loader_stats.restype = ctypes.c_longlong
    lib.t2r_loader_stats.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_longlong),
                                     ctypes.c_int]
    _LIB = lib
  return _LIB


# t2r_loader_stats slot order (record_loader.cc stats_snapshot).
_STAT_NAMES = ('records_read', 'bytes_read', 'reader_busy_us',
               'reader_wait_us', 'rows_parsed', 'parse_bytes',
               'worker_busy_us', 'worker_idle_us', 'n_workers',
               'completed_batches', 'min_worker_busy_us',
               'max_worker_busy_us', 'reader_wait_slot_us',
               'reader_wait_space_us')


class _Field:
  """One parsed field: config line + numpy view metadata."""

  def __init__(self, key: str, spec: TensorSpec, kind: int,
               dtype_size: int, shape: Tuple[int, ...],
               view_dtype, count: int = 0, seq_cap: int = 0,
               varlen: bool = False, optional: bool = False,
               dsi: int = 0, pad_value: float = 0.0):
    self.key = key            # flat spec key ('state/image')
    self.spec = spec
    self.kind = kind
    self.dtype_size = dtype_size
    self.shape = shape        # per-row output shape (per STEP for seqs)
    self.view_dtype = view_dtype
    self.count = count
    # > 0: SequenceExample feature_lists field with this step capacity;
    # rows come back [seq_cap, *shape] zero-padded + a per-row length.
    self.seq_cap = seq_cap
    # Varlen: on-disk value count may differ from the spec; the C++ side
    # clips extras / pads shortfalls with ``pad_value`` (parser.py
    # pad_or_clip semantics).
    self.varlen = varlen
    # Optional: records may omit the feature; a per-row presence buffer
    # rides along and _pack drops the key from any batch that is not
    # fully present (the Python parser's dense-batch semantics).
    self.optional = optional
    # Dataset index (multi-dataset zip): which zipped record this field
    # parses from.
    self.dsi = dsi
    self.pad_value = pad_value
    # Images: last three dims are H, W, C (rank-4 specs carry a leading
    # frame count, which travels in ``count``).
    h, w, c = shape[-3:] if kind in (
        _KIND_IMAGE_FULL, _KIND_IMAGE_COEF,
        _KIND_IMAGE_COEF_SPARSE, _KIND_IMAGE_COEF_PACKED) else (0, 0, 0)
    self.h, self.w, self.c = h, w, c

  def config_line(self) -> str:
    name = self.spec.name.encode('utf-8')
    return '{} {} {} {} {} {} {} {} {} {} {} {:.17g} {}'.format(
        len(name), self.kind, self.dtype_size, self.h, self.w, self.c,
        self.count, self.seq_cap, int(self.varlen), int(self.optional),
        self.dsi, float(self.pad_value), self.spec.name)


class NativeLoaderPlan:
  """Eligibility + field layout for a (feature_spec, label_spec) pair.

  ``dataset_keys`` orders the zip groups: field ``dsi`` indexes into it,
  and a stream built from this plan must provide one file list per key
  (a plain list when the only key is '').
  """

  def __init__(self, fields: List[_Field], feature_spec, label_spec,
               dataset_keys: Optional[List[str]] = None):
    self.fields = fields
    self.feature_spec = feature_spec
    self.label_spec = label_spec
    self.dataset_keys = list(dataset_keys or [''])


def coef_eligible(spec: TensorSpec) -> bool:
  """Can this image spec ship as DCT coefficients (split decode)?

  Baseline 4:2:0 constraints: rank-3 uint8 3-channel JPEG with both
  spatial dims divisible by 16. The ONE authority for coef eligibility —
  plan_for_specs and DeviceDecodePreprocessor both consult it.
  """
  shape = tuple(spec.shape or ())
  return (spec.is_encoded_image
          and spec.data_format in (None, 'jpeg', 'JPEG', 'jpg')
          and len(shape) == 3 and shape[-1] == 3
          and spec.dtype == np.uint8
          and shape[0] % 16 == 0 and shape[1] % 16 == 0)


def total_coefficients(spec: TensorSpec) -> int:
  """Flat DCT coefficient count of one 4:2:0 frame (y + cb + cr blocks)."""
  h, w = spec.shape[0], spec.shape[1]
  return ((h // 8) * (w // 8) + 2 * (h // 16) * (w // 16)) * 64


def sparse_capacity(spec: TensorSpec, density: float) -> int:
  """Entry capacity for a sparse coef stream at the given density budget."""
  total = total_coefficients(spec)
  cap = int(np.ceil(total * density / SPARSE_BUCKET)) * SPARSE_BUCKET
  return max(cap, SPARSE_BUCKET)


def packed_capacity(spec: TensorSpec, density: float) -> int:
  """Byte capacity of one packed nibble stream at the density budget.

  The packed wire spends ~1 byte per AC nonzero plus skip bytes, i.e.
  strictly less than the loose format's 1 delta byte per entry — so the
  same entry-count budget, taken as BYTES, over-provisions by design
  (the stream errors with a clear message on pathological overflow).
  Multiple of 8 so the C++ side's derived escape capacity (bytes / 8
  int16 entries) is exact.
  """
  return sparse_capacity(spec, density)


def packed_dc_count(spec: TensorSpec) -> int:
  """Blocks (= DC coefficients) of one 4:2:0 frame; always even."""
  h, w = spec.shape[0], spec.shape[1]
  return (h // 8) * (w // 8) + 2 * (h // 16) * (w // 16)


def plan_for_specs(feature_spec, label_spec,
                   image_mode: str = 'full',
                   sparse_density: float = 0.5,
                   sequence_max_len: Optional[int] = None
                   ) -> Optional[NativeLoaderPlan]:
  """Returns a plan if the native fast path supports these specs, else None.

  ``image_mode``: 'full' (decode to uint8 pixels), 'coef' (entropy-only
  decode; device finishes via data/jpeg_device.py — requires 4:2:0 JPEGs
  with dims divisible by 16), 'coef_sparse' (entropy decode + sparse
  delta/value packing of the ~88%-zero quantized coefficients — same
  device finish after a cumsum + scatter-add unpack, ~8x fewer bytes over
  the host->device link; see record_loader.cc decode_jpeg_coef_sparse),
  or 'coef_packed' (the bit-packed wire: nibble-coded AC entries, a
  nibble DC-delta plane, an int16 escape stream, and batch-hoisted quant
  tables — ~1.8x fewer bytes again vs 'coef_sparse', bit-exact the same
  coefficients; record_loader.cc decode_jpeg_coef_packed).

  ``sparse_density``: coef_sparse only — per-image entry capacity as a
  fraction of the total coefficient count. Realistic camera frames run
  ~12-14% nonzero; the 0.5 default leaves 3-4x headroom (the stream
  errors with a clear message if a pathological image overflows it).

  ``sequence_max_len``: step CAPACITY for SequenceExample feature_lists
  specs (``is_sequence``), e.g. the workload's episode length bound.
  Without it sequence specs fall back to the Python parser (the batch
  buffers are preallocated, so an upper bound is required); records with
  more steps fail with a clear error. Numeric (float/int) sequences only
  — bytes/JPEG steps fall back; derived ``<key>_length`` specs are
  produced by the stream, not read from disk.

  Varlen specs (``varlen_default_value`` set) are native for rank-1
  numeric tensors and rank-4 'full'-mode frame lists (clip/pad with the
  default value — parser.py pad_or_clip parity); optional specs
  (``is_optional``) are native everywhere except coef image modes, with
  the Python parser's dense-batch semantics (a batch where ANY record
  omits the feature drops the key). Specs with ``dataset_key`` plan as a
  multi-dataset zip: the stream then takes one file list per key. The
  remaining Python-parser fallbacks are PNG images and structurally
  unparseable specs (unnamed/duplicate names, object dtype).
  """
  feature_spec = specs_lib.flatten_spec_structure(feature_spec)
  label_spec = specs_lib.flatten_spec_structure(label_spec)
  fields: List[_Field] = []
  seen_names = set()
  sides = (('features', feature_spec), ('labels', label_spec))
  dataset_keys = sorted({(struct[key].dataset_key or '')
                         for _, struct in sides for key in struct
                         if struct[key].name is not None})
  if not dataset_keys:
    return None
  key_to_dsi = {k: i for i, k in enumerate(dataset_keys)}
  for side, struct in sides:
    for key in struct:
      spec = struct[key]
      if (key.endswith('_length') and key[:-len('_length')] in struct
          and struct[key[:-len('_length')]].is_sequence):
        # Derived length spec (algebra.add_sequence_length_specs): the
        # stream emits it from the parsed step counts.
        continue
      if spec.name is None or spec.name in seen_names:
        # The Python parser supports unnamed specs (skipped) and the same
        # on-disk feature bound under several spec keys (fanned out at pack
        # time, parser.py _pack_side); the native pack stage does neither,
        # and validate_and_pack would then raise on the missing keys every
        # batch. Fall back rather than fail downstream.
        return None
      optional = bool(spec.is_optional)
      varlen = spec.varlen_default_value is not None
      pad_value = float(spec.varlen_default_value or 0.0)
      dsi = key_to_dsi[spec.dataset_key or '']
      shape = tuple(spec.shape or ())
      if any(s is None for s in shape):
        return None
      full_key = side + '/' + key
      if spec.is_sequence:
        if not sequence_max_len or spec.is_encoded_image or varlen:
          # Varlen sequences pad the BATCH dim with the default value in
          # the Python parser — different semantics; keep them there.
          return None
        seen_names.add(spec.name)
        count = int(np.prod(shape)) if shape else 1
        if spec.dtype in (np.float32, bfloat16):
          fields.append(_Field(full_key, spec, _KIND_FLOAT, 4, shape,
                               np.float32, count,
                               seq_cap=int(sequence_max_len),
                               optional=optional, dsi=dsi))
        elif spec.dtype in (np.int64, np.int32, np.uint8, np.bool_):
          size = {np.dtype(np.int64): 8, np.dtype(np.int32): 4,
                  np.dtype(np.uint8): 1, np.dtype(np.bool_): 1}[
                      np.dtype(spec.dtype)]
          fields.append(_Field(full_key, spec, _KIND_INT, size, shape,
                               spec.dtype, count,
                               seq_cap=int(sequence_max_len),
                               optional=optional, dsi=dsi))
        else:
          return None
        continue
      if spec.is_encoded_image:
        if spec.data_format not in (None, 'jpeg', 'JPEG', 'jpg'):
          return None
        if len(shape) not in (3, 4) or spec.dtype != np.uint8 \
            or shape[-1] not in (1, 3):
          return None
        if varlen and (image_mode != 'full' or len(shape) != 4):
          return None  # varlen images are frame LISTS, full decode only
        if image_mode in ('coef', 'coef_sparse', 'coef_packed'):
          if not coef_eligible(spec) or optional or varlen:
            return None  # incl. rank-4: coef mode is single-frame only;
                         # no presence/pad machinery on the coef buffers
          if image_mode == 'coef_packed':
            fields.append(_Field(
                full_key, spec, _KIND_IMAGE_COEF_PACKED, 1, shape, np.uint8,
                count=packed_capacity(spec, sparse_density), dsi=dsi))
          elif image_mode == 'coef_sparse':
            fields.append(_Field(
                full_key, spec, _KIND_IMAGE_COEF_SPARSE, 1, shape, np.int8,
                count=sparse_capacity(spec, sparse_density), dsi=dsi))
          else:
            fields.append(_Field(full_key, spec, _KIND_IMAGE_COEF, 1, shape,
                                 np.int16, dsi=dsi))
        else:
          # Rank-4 [T, H, W, C]: a list of T encoded frames (episode
          # data, e.g. seq2act) — strict count unless varlen (clip/pad);
          # count carries T to the C++ side.
          frames = shape[0] if len(shape) == 4 else 0
          fields.append(_Field(full_key, spec, _KIND_IMAGE_FULL, 1, shape,
                               np.uint8, count=frames, varlen=varlen,
                               optional=optional, dsi=dsi,
                               pad_value=pad_value))
      elif spec.dtype == np.dtype(object):
        return None
      elif spec.dtype in (np.float32, bfloat16):
        if varlen and len(shape) != 1:
          return None  # parser pads/clips dim 0 of the FLAT list: only
                       # rank-1 specs are well-defined
        count = int(np.prod(shape)) if shape else 1
        fields.append(_Field(full_key, spec, _KIND_FLOAT, 4, shape,
                             np.float32, count, varlen=varlen,
                             optional=optional, dsi=dsi,
                             pad_value=pad_value))
      elif spec.dtype in (np.int64, np.int32, np.uint8, np.bool_):
        if varlen and len(shape) != 1:
          return None
        size = {np.dtype(np.int64): 8, np.dtype(np.int32): 4,
                np.dtype(np.uint8): 1, np.dtype(np.bool_): 1}[
                    np.dtype(spec.dtype)]
        count = int(np.prod(shape)) if shape else 1
        fields.append(_Field(full_key, spec, _KIND_INT, size, shape,
                             spec.dtype, count, varlen=varlen,
                             optional=optional, dsi=dsi,
                             pad_value=pad_value))
      else:
        return None
      seen_names.add(spec.name)
  if not fields:
    return None
  # Sequence streams emit derived <key>_length tensors; the validation
  # specs must include them (idempotent when the caller's spec already
  # went through add_sequence_length_specs).
  return NativeLoaderPlan(fields,
                          specs_lib.add_sequence_length_specs(feature_spec),
                          specs_lib.add_sequence_length_specs(label_spec),
                          dataset_keys=dataset_keys)


def _refcounts(arrays: List[np.ndarray]) -> List[int]:
  return [sys.getrefcount(a) for a in arrays]


# What _refcounts reads for an array that its list alone references.
_UNREFERENCED = _refcounts([np.empty(0, np.uint8)])[0]


class NativeBatchedStream:
  """Iterator of (features, labels) batches from the native loader.

  Matches BatchedExampleStream's contract (data/pipeline.py:129). A batch
  is the consumer's for as long as it holds it, or anything made over it
  (a slice, a device array aliasing host memory): the arrays are views of
  buffers this stream owns, and ``_pack`` writes a buffer again only when
  nothing but the stream references it any more.
  """

  def __init__(self, plan: NativeLoaderPlan,
               filenames,
               batch_size: int,
               shuffle: bool = False,
               shuffle_buffer: int = 500,
               num_epochs: Optional[int] = None,
               seed: Optional[int] = None,
               num_threads: Optional[int] = None,
               ring: int = 3,
               verify_crc: bool = False,
               validate: bool = True,
               bucket_sparse: bool = True):
    """``filenames``: a sequence of record paths, or — for a plan whose
    specs carry ``dataset_key``s (multi-dataset zip) — a dict mapping
    each of ``plan.dataset_keys`` to its file list; row r of every batch
    is then assembled from one record of EACH dataset (zip ends with the
    shortest), exactly like BatchedExampleStream's dataset_map path."""
    self._plan = plan
    self._batch_size = int(batch_size)
    self._validate = validate
    # Multi-process SPMD callers MUST pass bucket_sparse=False: each host
    # buckets from its OWN batch's max entry count, and divergent per-host
    # buckets give make_array_from_process_local_data inconsistent global
    # shapes (input_generators.py passes process_count()==1 through here).
    self._bucket_sparse = bool(bucket_sparse)
    self._lib = _lib()
    threads = num_threads or max(1, min(16, (os.cpu_count() or 2)))
    if isinstance(filenames, dict):
      missing = [k for k in plan.dataset_keys if k not in filenames]
      if missing:
        raise ValueError(
            'filenames dict is missing dataset keys {} (plan expects '
            '{}).'.format(missing, plan.dataset_keys))
      file_groups = [list(filenames[k]) for k in plan.dataset_keys]
    else:
      if len(plan.dataset_keys) > 1:
        raise ValueError(
            'plan zips datasets {}; pass filenames as a dict keyed by '
            'dataset key.'.format(plan.dataset_keys))
      file_groups = [list(filenames)]
    lines = [
        'batch_size {}'.format(self._batch_size),
        'ring {}'.format(ring),
        'threads {}'.format(threads),
        'shuffle {}'.format(1 if shuffle else 0),
        'shuffle_buffer {}'.format(shuffle_buffer),
        'seed {}'.format(-1 if seed is None else seed),
        'epochs {}'.format(-1 if num_epochs is None else num_epochs),
        'verify_crc {}'.format(1 if verify_crc else 0),
    ]
    for group in file_groups:
      lines.append('group {}'.format(len(group)))
      lines.extend(group)
    lines.append('fields {}'.format(len(plan.fields)))
    lines.extend(f.config_line() for f in plan.fields)
    config = '\n'.join(lines).encode('utf-8')
    self._handle = self._lib.t2r_loader_create(config, len(config))
    if not self._handle:
      raise RuntimeError('native loader creation failed')
    # Create-time errors are CONFIG errors only (parse/allocate run
    # synchronously); the worker threads start lazily on the first
    # next(), so data/decode errors surface at iteration — the one
    # documented error-surfacing point.
    err = self._lib.t2r_loader_last_error(self._handle)
    if err:
      msg = err.decode('utf-8', 'replace')
      self._lib.t2r_loader_destroy(self._handle)
      self._handle = None
      raise RuntimeError('native loader: ' + msg)
    self._ring = self._lib.t2r_loader_ring_size(self._handle)
    self._views = self._build_views()
    # Per buffer of the layout: the owning arrays batches are copied into.
    self._pools: List[List[np.ndarray]] = [[] for _ in self._views[0]]
    self._closed = False
    # Pipeline X-ray publishing (observability/pipeline_xray.py): the C++
    # loader's cumulative stats become pipeline/{read,decode}/* counter
    # DELTAS at every batch, so the registry stays monotonic even across
    # several streams in one process (each stream publishes only what it
    # added since its own last publish).
    self._published_stats = {name: 0 for name in _STAT_NAMES}
    self._stage_meters = None

  def stats(self) -> Dict[str, int]:
    """Cumulative loader-side stats (record_loader.cc stats_snapshot).

    Zeros before the first ``next()`` — reading stats never launches the
    reader/worker threads (the lazy-launch error-delivery contract).
    After ``close()`` the last published values are gone; zeros again.
    """
    if not self._handle:
      return {name: 0 for name in _STAT_NAMES}
    buf = (ctypes.c_longlong * len(_STAT_NAMES))()
    n = int(self._lib.t2r_loader_stats(self._handle, buf, len(_STAT_NAMES)))
    return {name: int(buf[i]) for i, name in enumerate(_STAT_NAMES[:n])}

  def _publish_stats(self) -> None:
    from tensor2robot_tpu.observability import event, get_registry
    from tensor2robot_tpu.observability.pipeline_xray import (
        DECODE_IDLE_COUNTER,
        DECODE_WORKERS_GAUGE,
        StageMeter,
    )

    if self._stage_meters is None:
      registry = get_registry()
      self._stage_meters = (StageMeter('read', registry),
                            StageMeter('decode', registry),
                            registry.counter(DECODE_IDLE_COUNTER),
                            registry.gauge(DECODE_WORKERS_GAUGE))
    read_meter, decode_meter, idle_counter, workers_gauge = \
        self._stage_meters
    stats = self.stats()
    delta = {name: stats[name] - self._published_stats.get(name, 0)
             for name in stats}
    self._published_stats = stats
    read_meter.add(examples=delta.get('records_read', 0),
                   nbytes=delta.get('bytes_read', 0),
                   busy_s=delta.get('reader_busy_us', 0) / 1e6)
    decode_meter.add(examples=delta.get('rows_parsed', 0),
                     nbytes=delta.get('parse_bytes', 0),
                     busy_s=delta.get('worker_busy_us', 0) / 1e6)
    idle = delta.get('worker_idle_us', 0)
    if idle > 0:
      idle_counter.inc(idle / 1e6)
    workers_gauge.set(float(stats.get('n_workers', 0)))
    # The same deltas on the span ring's clock, batch by batch: what the
    # reader thread and the decode pool did while this batch was made.
    event('data.loader_stats',
          reader_busy_s=delta.get('reader_busy_us', 0) / 1e6,
          reader_wait_slot_s=delta.get('reader_wait_slot_us', 0) / 1e6,
          reader_wait_space_s=delta.get('reader_wait_space_us', 0) / 1e6,
          worker_busy_s=delta.get('worker_busy_us', 0) / 1e6,
          worker_idle_s=idle / 1e6,
          workers=stats.get('n_workers', 0),
          records=delta.get('records_read', 0),
          bytes=delta.get('bytes_read', 0))

  # -- buffer views ----------------------------------------------------------

  def _buffer_layout(self):
    """(field, sub) per buffer index — mirrors record_loader.cc's order."""
    layout = []
    for f in self._plan.fields:
      if f.seq_cap > 0:
        layout.extend([(f, ''), (f, 'len')])
      elif f.kind == _KIND_IMAGE_COEF:
        layout.extend([(f, 'y'), (f, 'cb'), (f, 'cr'), (f, 'qt')])
      elif f.kind == _KIND_IMAGE_COEF_SPARSE:
        layout.extend([(f, 'sd'), (f, 'sv'), (f, 'qt'), (f, 'n')])
      elif f.kind == _KIND_IMAGE_COEF_PACKED:
        layout.extend([(f, 'pw'), (f, 'se'), (f, 'dcn'), (f, 'qt'),
                       (f, 'n'), (f, 'ne')])
      else:
        layout.append((f, ''))
      if f.optional:
        layout.append((f, 'p'))  # per-row presence flags
    return layout

  def _build_views(self):
    layout = self._buffer_layout()
    n_bufs = self._lib.t2r_loader_num_buffers(self._handle)
    if n_bufs != len(layout):
      raise RuntimeError('buffer layout mismatch: {} vs {}'.format(
          n_bufs, len(layout)))
    views = []
    B = self._batch_size
    for slot in range(self._ring):
      slot_views = []
      for buf, (f, sub) in enumerate(layout):
        ptr = self._lib.t2r_loader_buffer_ptr(self._handle, slot, buf)
        size = self._lib.t2r_loader_buffer_size(self._handle, buf)
        if sub == '':
          if f.kind == _KIND_IMAGE_FULL:
            shape = (B,) + f.shape
            dtype = np.uint8
          elif f.seq_cap > 0:
            shape = (B, f.seq_cap) + f.shape
            dtype = f.view_dtype
          else:
            shape = (B,) + f.shape
            dtype = f.view_dtype
        elif sub == 'len':
          shape = (B,)
          dtype = np.int32
        elif sub == 'p':
          shape = (B,)
          dtype = np.uint8
        elif sub == 'y':
          shape = (B, f.h // 8, f.w // 8, 64)
          dtype = np.int16
        elif sub in ('cb', 'cr'):
          shape = (B, f.h // 16, f.w // 16, 64)
          dtype = np.int16
        elif sub == 'sd':
          shape = (B, f.count)
          dtype = np.uint8
        elif sub == 'sv':
          shape = (B, f.count)
          dtype = np.int8
        elif sub == 'pw':
          shape = (B, f.count)
          dtype = np.uint8
        elif sub == 'se':
          shape = (B, f.count // 4)
          dtype = np.int16
        elif sub == 'dcn':
          shape = (B, packed_dc_count(f.spec) // 2)
          dtype = np.uint8
        elif sub in ('n', 'ne'):
          shape = (B,)
          dtype = np.int32
        else:  # qt
          shape = (B, 3, 64)
          dtype = np.uint16
        expect = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if expect != size:
          raise RuntimeError(
              'buffer {} size {} != expected {}'.format(buf, size, expect))
        arr = np.ctypeslib.as_array(
            ctypes.cast(ptr, ctypes.POINTER(ctypes.c_uint8)),
            shape=(size,)).view(dtype).reshape(shape)
        slot_views.append(arr)
      views.append(slot_views)
    return views

  # -- iteration -------------------------------------------------------------

  def _pack(self, slot: int):
    layout = self._buffer_layout()
    # Sparse coef streams: slice the capacity-sized delta/value buffers to
    # the batch's bucketed max entry count BEFORE they leave the loader —
    # the whole point of the format is that the host->device transfer pays
    # for actual entries, not capacity padding.
    buckets: Dict[str, int] = {}
    esc_buckets: Dict[str, int] = {}
    for buf, (f, sub) in enumerate(layout):
      if sub == 'n':
        # Packed wire: f.count is the BYTE capacity of the nibble stream;
        # its own (finer) bucket granularity.
        grain = (PACKED_BUCKET if f.kind == _KIND_IMAGE_COEF_PACKED
                 else SPARSE_BUCKET)
        capacity, into = int(f.count), buckets
      elif sub == 'ne':
        grain, capacity, into = ESCAPE_BUCKET, int(f.count) // 4, esc_buckets
      else:
        continue
      into[f.key] = capacity  # bucket_sparse off: host-invariant
      if self._bucket_sparse:
        max_n = int(self._views[slot][buf].max())
        into[f.key] = min(max(grain, -(-max_n // grain) * grain), capacity)
    # Sequence fields: slice the capacity-padded step dim to the batch's
    # max actual length — the Python parser's pad-to-longest-in-batch
    # semantics (parser.py parse_batch).
    seq_max: Dict[str, int] = {}
    seq_lengths: Dict[str, np.ndarray] = {}
    for buf, (f, sub) in enumerate(layout):
      if sub == 'len':
        lengths = self._views[slot][buf]
        seq_lengths[f.key] = lengths.astype(np.int64)
        seq_max[f.key] = max(1, int(lengths.max()))
    # Optional fields: the Python parser drops a key from any batch where
    # SOME record omitted it (a batch is dense). The C++ side reports
    # per-row presence; a not-fully-present batch drops the key here.
    dropped = set()
    for buf, (f, sub) in enumerate(layout):
      if sub == 'p' and not self._views[slot][buf].all():
        dropped.add(f.key)
    by_key: Dict[str, np.ndarray] = {}
    reused = allocated = 0
    for buf, (f, sub) in enumerate(layout):
      arr = self._views[slot][buf]
      if sub in ('len', 'p') or f.key in dropped:
        continue  # 'len' emitted as <key>_length below
      if sub in ('n', 'ne') and f.kind == _KIND_IMAGE_COEF_PACKED:
        continue  # host-side bucketing inputs only; the device unpack
                  # needs no counts (padding bytes are no-ops)
      if sub in ('sd', 'sv', 'pw'):
        # .copy(), NOT ascontiguousarray: when the bucket equals the full
        # capacity the slice is already contiguous and ascontiguousarray
        # would return a live VIEW into the recycled ring buffer.
        arr = arr[:, :buckets[f.key]].copy()
      elif sub == 'se':
        arr = arr[:, :esc_buckets[f.key]].copy()
      elif sub == 'qt' and f.kind == _KIND_IMAGE_COEF_PACKED:
        arr = self._hoisted_quant_table(f, arr)
      elif f.seq_cap > 0 and sub == '':
        arr = arr[:, :seq_max[f.key]].copy()
      else:
        # The same shape in every batch: into a buffer nobody references
        # any more (every array made over a view holds its owner, numpy
        # collapses ``.base`` chains), else into a new one. A kept batch is
        # never written again; its consumer just costs fresh memory.
        pool = self._pools[buf]
        counts = _refcounts(pool)
        if _UNREFERENCED in counts:
          owner = pool[counts.index(_UNREFERENCED)]
          reused += 1
        else:
          owner = np.empty_like(arr)
          pool.append(owner)
          allocated += 1
        np.copyto(owner, arr)
        arr = owner.view()  # never the owner itself: its count is the test
      key = f.key if not sub else f.key + '/' + sub
      if sub == '' and f.spec.dtype == bfloat16:
        arr = arr.astype(bfloat16)
      by_key[key] = arr
      if f.seq_cap > 0 and sub == '':
        by_key[f.key + '_length'] = seq_lengths[f.key]
    features = SpecStruct()
    labels = SpecStruct()
    for key, arr in by_key.items():
      side, rest = key.split('/', 1)
      (features if side == 'features' else labels)[rest] = arr
    if self._validate:
      coef = any(f.kind in (_KIND_IMAGE_COEF, _KIND_IMAGE_COEF_SPARSE,
                            _KIND_IMAGE_COEF_PACKED)
                 for f in self._plan.fields)
      if not coef:  # coef outputs intentionally mismatch the image specs
        features = specs_lib.validate_and_pack(
            self._plan.feature_spec, features, ignore_batch=True)
        if len(self._plan.label_spec):
          labels = specs_lib.validate_and_pack(
              self._plan.label_spec, labels, ignore_batch=True)
    return (features, labels), reused, allocated

  def _hoisted_quant_table(self, f: _Field, qt: np.ndarray) -> np.ndarray:
    """Batch-uniform quant table, hoisted to ONE [1, 3, 64] wire array.

    The packed wire contract (docs/performance.md "Transfer path"): the
    whole batch shares one set of quantization tables, so 384 bytes per
    example leave the wire. Rows whose tables are all-zero are empty
    payloads (the C++ side's "no table" sentinel) and are skipped; a
    genuine mismatch — a dataset mixing JPEG qualities — is a hard error
    at iteration naming the remedy (image_mode='coef_sparse' ships
    per-example tables). An all-empty batch ships 1s, matching the other
    coef modes' well-defined-dequant convention for zero images.
    """
    flat = qt.reshape(qt.shape[0], -1)
    present = flat.any(axis=1)
    if not present.any():
      return np.ones((1,) + qt.shape[1:], np.uint16)
    first = np.argmax(present)
    if not (flat[present] == flat[first]).all():
      raise RuntimeError(
          "native loader: image_coef_packed requires batch-uniform JPEG "
          "quantization tables for '{}' (the packed wire ships ONE table "
          "per batch); this dataset mixes qualities — use "
          "image_mode='coef_sparse' instead.".format(f.key))
    return qt[first:first + 1].copy()

  def __iter__(self):
    from tensor2robot_tpu.observability import span

    batch_index = 0
    while True:
      # The C++ loader has no whole batch ready: read or decode is behind.
      with span('data.ring_wait', batch=batch_index):
        slot = self._lib.t2r_loader_next(self._handle)
      if slot == -1:
        self._publish_stats()
        return
      if slot < 0:
        err = self._lib.t2r_loader_last_error(self._handle)
        raise RuntimeError('native loader: ' +
                           (err or b'?').decode('utf-8', 'replace'))
      try:
        # Slices, the copy out of the slot, spec validation. The span's
        # histogram is busy-only: the pack rows are already counted by the
        # decode stage, so a batch-stage examples counter here would
        # double-count them in the X-ray capacity table.
        with span('data.pack', batch=batch_index) as sp:
          batch, reused, allocated = self._pack(slot)
          sp.note(bytes=sum(int(getattr(leaf, 'nbytes', 0))
                            for side in batch for leaf in side.values()),
                  reused=reused, allocated=allocated)
        self._publish_stats()
      finally:
        self._lib.t2r_loader_release(self._handle, slot)
      batch_index += 1
      yield batch
      del batch  # or its buffers would read as referenced in the next _pack

  def close(self):
    if not self._closed and self._handle:
      self._closed = True
      self._lib.t2r_loader_destroy(self._handle)
      self._handle = None

  def __del__(self):
    try:
      self.close()
    except Exception:  # pragma: no cover - interpreter teardown
      pass


def native_loader_enabled() -> bool:
  """Env switch: T2R_NATIVE_LOADER=0 disables the fast path."""
  return os.environ.get('T2R_NATIVE_LOADER', '1') not in ('0', 'false', '')
