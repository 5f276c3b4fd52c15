"""Host->device batch feeds: the transfer hop, instrumented and sparse-aware.

Two jobs live here:

1. **The transfer stage of the pipeline X-ray** (ISSUE 7,
   observability/pipeline_xray.py). Every batch the trainer ships crosses
   ``put_batch``, so this is the one place the host->device hop is
   metered: ``pipeline/transfer/{examples,bytes,busy_seconds}`` counters,
   a ``pipeline/transfer/ms`` per-batch histogram, and — via
   :class:`PipelinedFeed` (N-deep; ``DoubleBufferedFeed`` is its depth-2
   name) — the ``pipeline/transfer/buffer_occupancy`` gauge. The
   reliability ``data.stall`` FaultInjector site also lives on this hop:
   an armed stall is indistinguishable from a wedged transfer, which is
   exactly the symptom the X-ray must attribute.

2. **The sparse/packed-coef unpack** (SURVEY hard-part #3). A
   ``DeviceDecodePreprocessor(sparse=True)`` pipeline ships images as
   sparse DCT entry streams (``key/{sd,sv,qt,n}``,
   data/native/record_loader.cc); ``wire_format='packed'`` tightens that
   to the bit-packed wire (``key/{pw,se,dcn}`` + one batch-hoisted
   ``key/qt``, ~1.8x fewer bytes again — docs/performance.md "Transfer
   path"). Either way the stream dims are BUCKETED per batch — the
   format's transfer savings come from slicing buffers to the batch's
   actual entry count. Unpacking them inside the jitted train step would
   recompile the whole model per bucket; instead
   :class:`SparseCoefFeed` converts sparse groups to the fixed-shape
   dense coefficient tensors (``key/{y,cb,cr}``) in a SEPARATE tiny jit
   cached per (batch, bucket) shape, right after the host->device
   transfer:

    host batch (sparse, ~8x fewer bytes) --transfer--> device
      --unpack jit (cumsum + scatter-add, ~15 ms / 64 frames)-->
    dense coef batch --train step (shape-stable, never recompiles)-->

The Trainer routes EVERY batch through a feed's :meth:`put_batch`
(:class:`HostDeviceFeed` when no sparse groups are in play), so the
transfer stage is metered unconditionally.

The shape-stability contract is ASSERTED as telemetry, not just
documented: every emitted batch's shape signature lands in the
``data/feed_shape_signatures`` gauge (must stay 1 — the observability
watchdog's ``recompile`` trigger fires otherwise) and the per-bucket
unpack-jit cache size in ``recompiles/coef_unpack`` (expected to grow
once per bucket, then plateau).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Set, Tuple

from tensor2robot_tpu.data import jpeg_device
from tensor2robot_tpu.observability import get_registry
from tensor2robot_tpu.observability.pipeline_xray import StageMeter
from tensor2robot_tpu.observability.spans import SPAN_BUCKETS_MS
from tensor2robot_tpu.parallel import sharding as sharding_lib
from tensor2robot_tpu.reliability import fault_injection

FEED_SHAPES_GAUGE = 'data/feed_shape_signatures'
UNPACK_COMPILES_GAUGE = 'recompiles/coef_unpack'
TRANSFER_MS_HISTOGRAM = 'pipeline/transfer/ms'
BUFFER_OCCUPANCY_GAUGE = 'pipeline/transfer/buffer_occupancy'


def _batch_examples_and_bytes(batch: dict) -> Tuple[int, int]:
  """(leading dim, total host bytes) of a {'features', 'labels'} batch.

  A leading dim of 1 only wins when NO other leaf disagrees: the packed
  wire ships its batch-hoisted quant table as [1, 3, 64], which must not
  masquerade as the batch size (a true batch of 1 still reports 1).
  """
  examples = 0
  nbytes = 0
  for side in ('features', 'labels'):
    values = batch.get(side)
    if not values:
      continue
    for value in values.values():
      size = getattr(value, 'nbytes', 0)
      nbytes += int(size or 0)
      shape = getattr(value, 'shape', None)
      if shape and (not examples or examples == 1):
        examples = int(shape[0])
  return examples, nbytes


class HostDeviceFeed:
  """The plain host->device hop: shard_batch + transfer-stage telemetry."""

  def __init__(self, mesh):
    self._mesh = mesh
    registry = get_registry()
    self._transfer_meter = StageMeter('transfer', registry)
    self._transfer_ms = registry.histogram(TRANSFER_MS_HISTOGRAM,
                                           bounds=SPAN_BUCKETS_MS)

  def put_batch(self, batch: dict, channel: str = 'train') -> dict:
    """Ships one host batch to the device, metering the hop.

    The hop is timed to COMPLETION (``block_until_ready``), not to
    dispatch: ``device_put`` returns after enqueueing the copy, and on a
    transfer-limited link a dispatch-only measurement would overestimate
    transfer capacity by orders of magnitude and the X-ray could never
    attribute the stage. Blocking here costs no overlap: this host
    thread waits while the device still runs the PREVIOUS step (and the
    production e2e path calls this from :class:`DoubleBufferedFeed`'s
    producer thread, where the wait is free by construction).

    Only the ``'train'`` channel feeds the ``pipeline/transfer`` stage
    counters — the X-ray's e2e flow meter counts train batches, so an
    in-process eval's batches must not inflate the same window's
    transfer capacity. Every channel still lands in the per-batch
    ``pipeline/transfer/ms`` histogram.

    The ``data.stall`` FaultInjector site fires here (the loader/feed
    path's stall injection, docs/reliability.md): a stalled transfer is
    the symptom the pipeline X-ray must catch as ``pipeline_stall`` and
    attribute to this stage.
    """
    examples, nbytes = _batch_examples_and_bytes(batch)
    t0 = time.perf_counter()
    stall_s = fault_injection.stall_data_seconds()
    if stall_s > 0.0:
      time.sleep(stall_s)
    device = self._transfer(batch)
    elapsed = time.perf_counter() - t0
    self._transfer_ms.record(elapsed * 1e3)
    if channel == 'train':
      self._transfer_meter.add(examples=examples, nbytes=nbytes,
                               busy_s=elapsed)
    return self._finish(device, channel)

  def _transfer(self, batch: dict) -> dict:
    """The timed hop: shard + copy, synchronized. Subclass work that is
    NOT the wire (e.g. the sparse unpack jit, whose per-bucket
    compilation costs seconds) belongs in ``_finish`` — inside this
    window it would collapse the measured MB/s and fire a spurious
    ``transfer_regression``."""
    device = sharding_lib.shard_batch(batch, self._mesh)
    try:
      import jax

      jax.block_until_ready(device)
    except Exception:  # noqa: BLE001 — non-array leaves etc.: keep feeding
      pass
    return device

  def _finish(self, device: dict, channel: str) -> dict:
    """Post-transfer device-side work; identity for the plain feed."""
    return device


class SparseCoefFeed(HostDeviceFeed):
  """Converts host batches with sparse coef groups into device batches."""

  def __init__(self, image_shapes: Dict[str, Tuple[int, int]], mesh):
    super().__init__(mesh)
    self._shapes = dict(image_shapes)
    self._jit_cache = {}
    self._signatures: Dict[str, Set[Tuple]] = {}
    registry = get_registry()
    self._shape_gauge = registry.gauge(FEED_SHAPES_GAUGE)
    self._unpack_gauge = registry.gauge(UNPACK_COMPILES_GAUGE)

  @classmethod
  def from_preprocessor(cls, preprocessor, mesh
                        ) -> Optional['SparseCoefFeed']:
    """A feed for a DeviceDecodePreprocessor-wrapped model, else None."""
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )

    # Unwrap decorators (e.g. the TPU Bfloat16PreprocessorWrapper, which
    # train_eval_model installs OUTSIDE the device-decode wrapper) via
    # their ``preprocessor`` property.
    seen = 0
    while (not isinstance(preprocessor, DeviceDecodePreprocessor)
           and seen < 8):
      nxt = getattr(type(preprocessor), 'preprocessor', None)
      if nxt is None:
        return None
      preprocessor = preprocessor.preprocessor
      seen += 1
    if not isinstance(preprocessor, DeviceDecodePreprocessor):
      return None
    spec = preprocessor.raw_in_feature_specification('train')
    from tensor2robot_tpu.specs import algebra
    flat = algebra.flatten_spec_structure(spec)
    shapes = {key: (flat[key].shape[0], flat[key].shape[1])
              for key in preprocessor.image_keys('train')}
    return cls(shapes, mesh=mesh)

  def _unpack_fn(self, height: int, width: int, shape):
    import jax

    cache_key = (height, width, tuple(shape))
    fn = self._jit_cache.get(cache_key)
    if fn is None:
      # Explicit batch-sharded outputs: the train step is jitted with
      # explicit in_shardings, and on a multi-device mesh an INFERRED
      # unpack output sharding need not match it (jax then errors
      # instead of resharding). No donation: the uint8/int8 inputs can't
      # alias the int16 outputs, so donating only produces "donated
      # buffers were not usable" spam.
      out_sharding = sharding_lib.batch_sharding(self._mesh)
      fn = jax.jit(
          lambda sd, sv: jpeg_device.unpack_sparse_coefficients(
              sd, sv, height, width),
          out_shardings=out_sharding)
      self._jit_cache[cache_key] = fn
    return fn

  def _packed_unpack_fn(self, height: int, width: int, pw_shape, se_shape):
    """The packed-wire unpack jit, cached per (geometry, bucket shapes).

    One program covers the whole packed group: AC/DC/escape streams to
    dense coefficient planes (jpeg_device.unpack_packed_coefficients)
    PLUS the broadcast of the batch-hoisted [1, 3, 64] quant table back
    to the per-example [B, 3, 64] the jitted train step consumes — so
    the step's input signature is IDENTICAL to the 'coef' and
    'coef_sparse' paths (same recompile key, same HLO).
    """
    import jax

    cache_key = ('packed', height, width, tuple(pw_shape), tuple(se_shape))
    fn = self._jit_cache.get(cache_key)
    if fn is None:
      import jax.numpy as jnp

      out_sharding = sharding_lib.batch_sharding(self._mesh)

      def unpack(pw, se, dcn, qt):
        y, cb, cr = jpeg_device.unpack_packed_coefficients(
            pw, se, dcn, height, width)
        if qt.shape[0] != pw.shape[0]:
          qt = jnp.broadcast_to(qt[0], (pw.shape[0],) + tuple(qt.shape[1:]))
        return y, cb, cr, qt

      fn = jax.jit(unpack, out_shardings=out_sharding)
      self._jit_cache[cache_key] = fn
    return fn

  def _record_signature(self, features: dict, channel: str) -> None:
    """Counts distinct emitted batch-shape signatures into the gauges.

    The signature covers NAME and SHAPE of every feature the jitted step
    will see — exactly the recompile key. Signatures are tracked per
    ``channel`` because one feed serves several independently-jitted
    programs (train step, eval step, summary pass), each shape-stable on
    its own: an eval batch sized differently from train is legitimate
    and must not trip the train invariant. The exported gauge covers
    only the ``'train'`` channel — the contract the watchdog asserts.
    """
    signature = tuple(sorted(
        (key, tuple(getattr(value, 'shape', ()))
         ) for key, value in features.items()))
    self._signatures.setdefault(channel, set()).add(signature)
    self._shape_gauge.set(float(len(self._signatures.get('train', ()))))
    self._unpack_gauge.set(float(len(self._jit_cache)))

  def _transfer(self, batch: dict) -> dict:
    """The timed hop, hoisted-table aware: the packed wire ships ONE
    [1, 3, 64] quant table per batch, which must ride the wire
    REPLICATED — shard_batch would try to split its leading dim of 1
    over the mesh's data axis. Still inside the timed window: the table
    is wire bytes like everything else (all 384 of them)."""
    features = batch.get('features')
    hoisted = {}
    if features and any(key + '/pw' in features for key in self._shapes):
      features = dict(features)
      for key in self._shapes:
        qt = features.get(key + '/qt')
        shape = getattr(qt, 'shape', None)
        if (key + '/pw' in features and shape and shape[0] == 1):
          hoisted[key + '/qt'] = features.pop(key + '/qt')
      batch = dict(batch)
      batch['features'] = features
    device = super()._transfer(batch)
    if hoisted:
      import jax

      replicated = sharding_lib.replicated(self._mesh)
      if jax.process_count() == 1:
        put = jax.device_put(hoisted, replicated)
      else:
        import numpy as np
        put = {key: jax.make_array_from_process_local_data(
            replicated, np.asarray(value))
               for key, value in hoisted.items()}
      jax.block_until_ready(put)
      features = dict(device['features'])
      features.update(put)
      device = dict(device)
      device['features'] = features
    return device

  def _finish(self, device: dict, channel: str) -> dict:
    """On-device sparse/packed->dense coef unpack where present (untimed:
    the unpack is device compute riding AFTER the metered wire hop)."""
    features = device.get('features')
    if not features or not any(
        key + '/sd' in features or key + '/pw' in features
        for key in self._shapes):
      if features:
        self._record_signature(features, channel)
      return device
    features = dict(features)
    for key, (height, width) in self._shapes.items():
      if key + '/sd' in features:
        sd = features.pop(key + '/sd')
        sv = features.pop(key + '/sv')
        features.pop(key + '/n', None)
        y, cb, cr = self._unpack_fn(height, width, sd.shape)(sd, sv)
      elif key + '/pw' in features:
        pw = features.pop(key + '/pw')
        se = features.pop(key + '/se')
        dcn = features.pop(key + '/dcn')
        qt = features[key + '/qt']
        y, cb, cr, qt = self._packed_unpack_fn(
            height, width, pw.shape, se.shape)(pw, se, dcn, qt)
        features[key + '/qt'] = qt
      else:
        continue
      features[key + '/y'] = y
      features[key + '/cb'] = cb
      features[key + '/cr'] = cr
    self._record_signature(features, channel)
    device = dict(device)
    device['features'] = features
    return device


class PipelinedFeed:
  """N-deep background host->device producer: transfer overlaps compute.

  Wraps a host-batch iterator and a feed: a daemon producer thread
  decodes and ships batches k+1..k+depth while the device runs step k.
  Depth 2 is the classic double buffer; deeper pipelines keep the
  host->device link busy CONTINUOUSLY — with a shallow buffer, any
  decode hiccup drains it and the link then idles while the device
  computes, so the achieved MB/s sits below the link's capacity.

  Design invariants:

    * ONE producer thread, copies serialized and timed to completion
      inside ``put_batch`` — the X-ray's transfer stage meters the hop
      in this thread, so its busy-time MB/s stays an honest link
      estimate (concurrent producers would overlap their busy windows
      and inflate it).
    * Strict FIFO: batches are delivered in the exact order the wrapped
      iterator produced them, each handed off only after its device
      transfer (and any in-feed finishing, e.g. the sparse/packed coef
      unpack dispatch) completed — a consumer can never observe a torn
      or reordered batch, at any depth, even mid-``data.stall``.
    * Device buffers are RELEASED on hand-off: the feed holds at most
      ``depth`` transferred batches plus the one in flight, so HBM cost
      is bounded at ``(depth + 1) x batch bytes`` and the freed buffers
      recycle through the allocator for the next copies. (The unpack
      jits deliberately do NOT donate their stream inputs — mismatched
      dtypes/shapes make XLA refuse the aliasing with per-call spam.)

  The ``pipeline/transfer/buffer_occupancy`` gauge holds the
  buffered-batch fraction at the last hand-off: pinned near 0 means the
  consumer (device) outruns the host path — the pipeline gates; near 1
  means the host comfortably leads.

  Errors from the producer (including the wrapped iterator's
  StopIteration) surface on the consumer side at ``get()``;
  ``close()`` stops the thread without draining it.
  """

  def __init__(self, batch_iterator, feed,
               depth: int = 2, channel: str = 'train'):
    """``feed``: a :class:`HostDeviceFeed` (or anything with its
    ``put_batch(batch, channel=...)``), or a bare callable with the same
    signature (e.g. ``Trainer._put_batch``). ``depth``: how many
    transferred batches may wait ahead of the consumer."""
    put_batch = feed.put_batch if hasattr(feed, 'put_batch') else feed
    self._depth = max(1, int(depth))
    self._buffer = []
    self._lock = threading.Condition()
    self._stopped = False
    self._done = False
    self._errors = []
    self._occupancy = get_registry().gauge(BUFFER_OCCUPANCY_GAUGE)

    def _producer():
      try:
        for batch in batch_iterator:
          device_batch = put_batch(batch, channel=channel)
          with self._lock:
            while len(self._buffer) >= self._depth and not self._stopped:
              self._lock.wait(0.05)
            if self._stopped:
              return
            self._buffer.append(device_batch)
            self._occupancy.set(len(self._buffer) / self._depth)
            self._lock.notify_all()
      except BaseException as e:  # surfaced on the consumer side
        with self._lock:
          self._errors.append(e)
          self._lock.notify_all()
      finally:
        with self._lock:
          self._done = True
          self._lock.notify_all()

    self._thread = threading.Thread(target=_producer, daemon=True,
                                    name='t2r-device-feed')
    self._thread.start()

  def get(self):
    """The next device batch; raises StopIteration at end of data."""
    with self._lock:
      while True:
        if self._buffer:
          batch = self._buffer.pop(0)
          self._occupancy.set(len(self._buffer) / self._depth)
          self._lock.notify_all()
          return batch
        if self._errors:
          raise self._errors[0]
        if self._done:
          raise StopIteration
        self._lock.wait(0.05)

  def __iter__(self):
    return self

  def __next__(self):
    return self.get()

  def close(self, timeout: float = 60.0) -> bool:
    """Stops the producer; returns whether its thread exited in time."""
    with self._lock:
      self._stopped = True
      self._buffer.clear()
      self._occupancy.set(0.0)
      self._lock.notify_all()
    self._thread.join(timeout=timeout)
    return not self._thread.is_alive()


class DoubleBufferedFeed(PipelinedFeed):
  """The depth-2 :class:`PipelinedFeed` under its original name."""
