"""Driver for traffic of kind ``disk``: the configuration's model trained by
``Trainer.train`` from a record file written from the seed.

The construction is ``train_eval_model``'s (bf16 preprocessor wrapper on the
TPU, ``Trainer``, ``DefaultRecordInputGenerator`` through the native loader);
``Trainer`` is built here only because ``train_eval_model`` does not pass
``log_every_n_steps`` on, and the configuration has to say where log windows
fall (PERF.md section 2).

How a run is timed: warm-up steps (compile or cache load; the first step's
loss is the one ``reference`` checks), a device sync, the clock starts;
``Trainer.train`` runs its normal loop; at the first step boundary at or
after ``--seconds`` the hook syncs the device, stops the clock and ends the
loop by raising ``WindowClosed`` from ``after_step``. Nothing is dispatched
after the last timed step, so the stop costs no step; the checkpoint
``Trainer`` writes as it unwinds falls after the clock has stopped. A traced
run goes on past that point for a few seconds under the profiler, so the
profiler disturbs neither the rate nor the counters the per-layer metrics
read.
"""

import hashlib
import os
import shutil
import tempfile
import time
import zlib

import numpy as np

from benchmark.harness import (
    cells,
    common,
    costs,
    peaks,
    records,
    reference,
    timing,
)
from benchmark.harness.common import log


class WindowClosed(Exception):
  """Raised from the hook to end ``Trainer.train`` after the last timed step."""


class RecordedInput:
  """The input generator as ``Trainer.train`` sees it, with the first batch
  kept for the float32 check and ``next`` annotated in the profiler trace."""

  def __init__(self, generator, profiler):
    self._generator = generator
    self._profiler = profiler
    self._iterator = None
    self._put_back = []
    self.first_batch = None
    self.last_next_end_s = None
    self.calls = 0
    self.longest_next = (0.0, 0)  # seconds, and which call it was

  def set_specification_from_model(self, model, mode):
    self._generator.set_specification_from_model(model, mode)

  def prime(self, model, mode, seed):
    """Starts the stream and draws the first batch ahead of ``Trainer.train``,
    which gets the same stream with that batch put back. ``Trainer.train``
    itself asks for an unseeded shuffle; here the shuffle is seeded, and the
    loader hands batches out in dispatch order, so ``--seed`` fixes every
    batch of the run and the first with them."""
    self.set_specification_from_model(model, mode)
    self._iterator = self._generator.create_dataset_iterator(mode=mode,
                                                             seed=seed)
    self.first_batch = next(self._iterator)
    self._put_back = [self.first_batch]
    return self.first_batch

  def create_dataset_iterator(self, mode, **kwargs):
    return self

  def __iter__(self):
    return self

  def __next__(self):
    start_s = time.perf_counter()
    batch = self._put_back.pop() if self._put_back else next(self._iterator)
    self.last_next_end_s = time.perf_counter()
    self.calls += 1
    self.longest_next = max(self.longest_next,
                            (self.last_next_end_s - start_s, self.calls))
    if self._profiler is not None:
      self._profiler.note('data.next', start_s, self.last_next_end_s)
    return batch


class Window:
  """The hook that times the window; see the module docstring."""

  REFILL_STEPS = 2

  def __init__(self, seconds, warm_steps, profiler, trace_seconds, generator,
               devices):
    self.seconds = seconds
    self.warm_steps = warm_steps
    self.profiler = profiler
    self.trace_seconds = trace_seconds
    self.generator = generator
    self.devices = devices
    self.memory_peak_bytes = 0
    self.losses = []
    self.boundaries_s = []
    self.first_sync_s = self.last_sync_s = None
    self.first_step = self.last_step = None
    self.setup_s = None
    self.counters = {}
    self.trace_started_s = None
    self.first_step_done_s = None
    self.first_metrics = {}

  def begin(self, trainer):
    pass

  @staticmethod
  def _sync(state, metrics):
    import jax

    jax.block_until_ready((state.step, metrics['loss']))

  def after_step(self, trainer, state, step, metrics):
    self.losses.append(metrics['loss'])
    if len(self.losses) == 1:
      self._sync(state, metrics)
      self.first_step_done_s = time.perf_counter()
      self.first_metrics = {k: float(v) for k, v in dict(metrics).items()}
      self.counters['after_first_step'] = common.snapshot_counters()
    if self.first_sync_s is None:
      if len(self.losses) >= self.warm_steps:
        self._sync(state, metrics)
        self.counters['before'] = common.snapshot_counters()
        self.setup_s = timing.process_age_s()
        self.first_step = step
        self.first_sync_s = time.perf_counter()
      return
    now = time.perf_counter()
    if self.last_sync_s is None:
      self.boundaries_s.append(now)
      if timing.window_closed(now, self.first_sync_s, self.seconds):
        self._sync(state, metrics)
        self.last_sync_s = time.perf_counter()
        self.last_step = step
        self.counters['after'] = common.snapshot_counters()
        self.memory_peak_bytes = common.memory_peak_bytes(self.devices)
        if self.profiler is None:
          raise WindowClosed()
      return
    # Traced runs only, after the clock has stopped, so that the profiler
    # disturbs neither the rate nor the counters: a few steps to fill the
    # pipeline again, then the profiler for ``trace_seconds``.
    # Between a batch in hand and this call the training thread did its
    # put_batch and dispatched the step (nothing else of any length).
    self.profiler.note('data.put_batch+dispatch',
                       self.generator.last_next_end_s, now)
    if self.trace_started_s is None:
      if step - self.last_step >= self.REFILL_STEPS:
        self.profiler.start()
        self.trace_started_s = time.perf_counter()
    elif now - self.trace_started_s >= self.trace_seconds:
      self._sync(state, metrics)
      self.profiler.stop()
      raise WindowClosed()

  def end(self, trainer, state):
    pass


def ensure_records(cell, model, seed, num_records):
  """The cell's record file, written once per (configuration's spec, seed,
  size, writer source) under the checkout's git-ignored cache."""
  from tensor2robot_tpu.modes import ModeKeys

  specs = records.flat_specs([
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      model.preprocessor.get_in_label_specification(ModeKeys.TRAIN)])
  with open(records.__file__, 'rb') as f:
    source = f.read()
  key = hashlib.sha256(repr((specs, num_records, seed)).encode() +
                       source).hexdigest()[:16]
  path = os.path.join(cells.ROOT, '.bench_cache', 'records',
                      '{}-{}.tfrecord'.format(cell.config_name, key))
  if os.path.exists(path):
    return path, os.path.getsize(path), True
  return path, records.write_records(path, specs, num_records, seed), False


def run(cell, seed, seconds, trace):
  stamps = {'driver_start': time.perf_counter()}
  import jax

  from tensor2robot_tpu import parallel
  from tensor2robot_tpu.data.input_generators import (
      DefaultRecordInputGenerator,
  )
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.preprocessors.bfloat16_wrapper import (
      Bfloat16PreprocessorWrapper,
  )
  from tensor2robot_tpu.trainer.train_eval import Trainer

  stamps['imports'] = time.perf_counter()
  cache_root = common.enable_caches()
  devices = common.claim_devices(cell)
  peak_row = (peaks.peaks_for(devices[0].device_kind)
              if devices[0].platform == 'tpu' else None)
  log('device: platform={} device_kind={!r} count={} (cell uses {}); compile '
      'cache {}', devices[0].platform, devices[0].device_kind,
      len(jax.devices()), len(devices), cache_root)
  train_cfg, traffic = cell.config['train'], cell.traffic
  batch = int(train_cfg['batch_per_chip']) * cell.chips
  model = common.build_model(cell.config['model'])
  ref_model = reference.float32_model(cell.config['model'])

  num_records = max(int(traffic['min_records']),
                    int(traffic['min_global_batches']) * batch)
  records_path, records_bytes, cached = ensure_records(
      cell, model, seed, num_records)
  stamps['records'] = time.perf_counter()
  log('records: {} examples, {} bytes, {} ({})', num_records, records_bytes,
      'found in the cache' if cached else 'written', records_path)

  if model.is_device_tpu:
    model.set_preprocessor(Bfloat16PreprocessorWrapper(model.preprocessor))
  mesh = parallel.create_mesh({'data': -1}, devices=devices)
  model_dir = tempfile.mkdtemp(prefix='bench_train_')
  profiler_dir = tempfile.mkdtemp(prefix='bench_trace_') if trace else None
  trainer = Trainer(model, model_dir, mesh=mesh,
                    **train_cfg.get('trainer_kwargs', {}))
  profiler = common.ProfilerWindow(profiler_dir) if trace else None
  generator = RecordedInput(DefaultRecordInputGenerator(
      file_patterns=records_path, batch_size=batch), profiler)
  stamps['built'] = time.perf_counter()
  features, labels = generator.prime(model, ModeKeys.TRAIN,
                                     common.seed31(seed, 2))
  stamps['first_batch'] = time.perf_counter()
  state = _seeded_state(trainer, model, mesh, features, labels,
                        common.seed31(seed, 1))
  stamps['state'] = time.perf_counter()
  # The same seed must read the same here in every run: the contract's "the
  # same seed gives the same inputs", as a line a reader can compare. The
  # batch is summed twice, example by example in the order it came and in
  # sorted order, so that the same examples in another order can be told
  # from other examples.
  leaves = jax.tree.leaves(state.params)
  in_order, sorted_order = _batch_checksums(jax.tree.leaves(
      (features.to_dict(), labels.to_dict() if labels is not None else {})))
  log('inputs from the seed: record file adler32 {:08x}; first batch by '
      'example, in order {:08x}, sorted {:08x}; first, middle and last '
      'parameter leaf {:08x}', _file_checksum(records_path), in_order,
      sorted_order,
      _checksum([leaves[0], leaves[len(leaves) // 2], leaves[-1]]))
  # Before the window, and before the step donates the state: what the
  # first step's loss must be, by the plain evaluation of the same module on
  # the same parameters and batch, in float32 and at the configuration's own
  # precision. ``Trainer.train`` folds the step into PRNGKey(trainer.seed +
  # 1); the trainer's own seed stays fixed.
  plain = [
      reference.train_loss(
          which, state.params, state.model_state, features.to_dict(),
          labels.to_dict() if labels is not None else None,
          jax.random.PRNGKey(trainer.seed + 1), np.int32(0), mesh, precision)
      for which, precision in ((ref_model, 'highest'), (model, None))]
  stamps['reference'] = time.perf_counter()
  window = Window(seconds, int(train_cfg['warm_steps']), profiler,
                  float(traffic.get('trace_seconds', 2.0)), generator,
                  devices)
  try:
    trainer.train(generator, max_train_steps=10**9, state=state,
                  hooks=[window])
    raise RuntimeError('Trainer.train returned before the window closed')
  except WindowClosed:
    pass
  finally:
    stamps['window_closed'] = time.perf_counter()
    if profiler is not None and profiler.active:
      profiler.stop()
    trainer.close()
    stamps['trainer_closed'] = time.perf_counter()
    shutil.rmtree(model_dir, ignore_errors=True)
    if profiler_dir is not None:
      shutil.rmtree(profiler_dir, ignore_errors=True)

  steps = window.last_step - window.first_step
  window_s = window.last_sync_s - window.first_sync_s
  rate = timing.whole_step_rate(steps, batch, window.first_sync_s,
                                window.last_sync_s, cell.chips)
  losses = np.asarray(jax.device_get(window.losses), np.float64)
  before, after = window.counters['before'], window.counters['after']
  window_compiles = after['jax/compiles'] - before['jax/compiles']
  recompiles = (before['jax/compiles'] -
                window.counters['after_first_step']['jax/compiles'])
  problems = []
  if not np.all(np.isfinite(losses)):
    problems.append('{} non-finite losses'.format(
        int(np.sum(~np.isfinite(losses)))))
  log('first step: {}', ', '.join(
      '{} {:.6g}'.format(k, v) for k, v in sorted(window.first_metrics.items())))
  for got, want, tolerance, what in (
      (window.first_metrics['loss'], plain[1], 'step_rel_tolerance',
       'loss of the first batch, the step against the plain forward at the '
       'configuration\'s precision'),
      (plain[1], plain[0], 'precision_rel_tolerance',
       'loss of the first batch, that plain forward against float32')):
    agrees, report = reference.agree(got, want, float(train_cfg[tolerance]),
                                     what)
    log('{}', report)
    if not agrees:
      problems.append(report)
  if window_compiles:
    problems.append('{:.0f} compiles inside the window'.format(
        window_compiles))
  if recompiles:
    problems.append('{:.0f} compiles in warm-up after the first step: the '
                    'train step holds more than one executable'.format(
                        recompiles))
  if after['pipeline/decode/workers'] <= 0:
    problems.append('the records were not read by the native loader')
  for problem in problems:
    log('INCORRECT: {}', problem)

  _log_setup(stamps, window, cache_root)
  log('window: {} whole steps of {} examples in {:.4f} s on {} chip(s): '
      '{:.3f} examples/s/chip; losses {:.5f} .. {:.5f}', steps, batch,
      window_s, cell.chips, rate, losses[0], losses[-1])
  # Where a run reads low, this says whether one stall did it, and when.
  gaps = np.diff([window.first_sync_s] + window.boundaries_s)
  log('step boundaries on the training thread: median {:.4f} s apart; the '
      'longest: {}; the longest wait for a batch since the start: {:.4f} s, '
      'call {} (the window opened after call {})', np.median(gaps),
      ', '.join('{:.4f} s before step {}'.format(gaps[i], i + 1)
                for i in np.argsort(-gaps)[:3]),
      *generator.longest_next, window.first_step + 1)

  delta = {k: after[k] - before[k] for k in after}
  log('training thread over the window: data.next {:.3f} s, data.put_batch '
      '{:.3f} s, train.step (dispatch) {:.3f} s of {:.3f} s; transfer busy '
      '{:.3f} s for {:.0f} bytes; decode busy {:.3f} s over {:.0f} workers '
      'for {:.0f} examples', delta['span/data.next/seconds'],
      delta['span/data.put_batch/seconds'],
      delta['span/train.step/seconds'], window_s,
      delta['pipeline/transfer/busy_seconds'],
      delta['pipeline/transfer/bytes'],
      delta['pipeline/decode/busy_seconds'], after['pipeline/decode/workers'],
      delta['pipeline/decode/examples'])

  observations = {
      'chips': cell.chips, 'window_s': window_s,
      'steps': steps, 'examples_per_step': batch, 'setup_s': window.setup_s,
      'train_examples_per_s_per_chip': rate,
      'counters': {'before': before, 'after': after},
      'trace': profiler.reduced if profiler is not None else None,
      'peaks': peak_row,
      'memory_peak_bytes': window.memory_peak_bytes,
  }
  if trace:
    observations['cost'] = _step_cost(model, generator.first_batch, batch)
  return {
      'correct': not problems,
      'attempted': len(losses),
      'failed': int(np.sum(~np.isfinite(losses))),
      'observations': observations,
      'device': common.device_report(devices,
                                     observations['memory_peak_bytes']),
  }


def _checksum(arrays):
  value = 1
  for array in arrays:
    data = np.ascontiguousarray(array).reshape(-1).view(np.uint8)
    value = zlib.adler32(data, value)
  return value


def _batch_checksums(arrays):
  """(in order, sorted): one sum per example over every array's row of it,
  folded in the order the batch holds the examples and in sorted order."""
  rows = [_checksum([array[i] for array in arrays])
          for i in range(len(arrays[0]))]
  return tuple(zlib.adler32(np.asarray(order, np.uint32).tobytes())
               for order in (rows, sorted(rows)))


def _file_checksum(path):
  value = 1
  with open(path, 'rb') as f:
    for block in iter(lambda: f.read(1 << 24), b''):
      value = zlib.adler32(block, value)
  return value


def _seeded_state(trainer, model, mesh, features, labels, seed):
  """The train state from ``--seed``, laid out as the trainer lays it out.

  ``Trainer.init_state`` closes over its key, so every new seed would be a
  new program and a new compile in set-up. It is called once with the
  trainer's fixed seed, for its shardings; the weights then come from the
  model's own ``create_train_state`` with the key as an ARGUMENT, one
  program for every seed, in one jitted call on the device."""
  import jax

  from tensor2robot_tpu.modes import ModeKeys

  template = trainer.init_state(features, labels)
  shardings = jax.tree.map(lambda leaf: leaf.sharding, template)
  del template

  def init(key, features, labels):
    features, labels = model.preprocessor.preprocess(
        features, labels, ModeKeys.TRAIN, rng=jax.random.PRNGKey(0))
    return model.create_train_state(key, features, labels)

  # The batch laid over the mesh as the trainer lays it, so that the
  # forward pass of the initialisation runs data-parallel too.
  _, split = reference.layouts(mesh)
  return jax.jit(init, out_shardings=shardings)(
      jax.random.PRNGKey(seed), *jax.device_put((features, labels), split))


def _step_cost(model, first_batch, batch):
  """FLOPs and bytes one train step needs at the global batch, counted from
  the jaxpr of the model's own ``train_step`` on preprocessed shapes."""
  import jax

  from tensor2robot_tpu.modes import ModeKeys

  features, labels = first_batch
  rng = jax.random.PRNGKey(0)

  def preprocessed(features, labels):
    return model.preprocessor.preprocess(features, labels, ModeKeys.TRAIN,
                                         rng=rng)

  pre_features, pre_labels = jax.eval_shape(preprocessed, features, labels)
  state = jax.eval_shape(
      lambda f, l: model.create_train_state(rng, f, l), pre_features,
      pre_labels)
  cost = costs.program_cost(
      lambda s, f, l: model.train_step(s, f, l, rng), state, pre_features,
      pre_labels)
  log('cost per step at batch {}: {:.4g} FLOPs ({:.4g} conv in {} calls, '
      '{:.4g} dot); conv+dot bytes {:.4g}', batch, cost['flops'],
      cost['conv']['flops'], cost['conv']['calls'], cost['dot']['flops'],
      cost['conv']['bytes'] + cost['dot']['bytes'])
  return cost


def _log_setup(stamps, window, cache_root):
  """The set-up breakdown, on an earlier line of every run."""
  s = dict(stamps, first_step_done=window.first_step_done_s)
  age_at_driver = window.setup_s - (window.first_sync_s - s['driver_start'])
  parts = [
      ('process start to driver (python, argparse, harness imports)',
       age_at_driver),
      ('imports (jax, the program)', s['imports'] - s['driver_start']),
      ('devices, caches, model objects, records',
       s['records'] - s['imports']),
      ('mesh, trainer, generator objects', s['built'] - s['records']),
      ('native loader start and first batch', s['first_batch'] - s['built']),
      ('state from the seed on the device (two init programs)',
       s['state'] - s['first_batch']),
      ('plain references, float32 and the configuration\'s precision',
       s['reference'] - s['state']),
      ('train() to the end of the first step (compile or cache load, run)',
       s['first_step_done'] - s['reference']),
      ('remaining warm-up steps', window.first_sync_s - s['first_step_done']),
  ]
  counters = window.counters['before']
  log('set-up {:.2f} s: {}', window.setup_s,
      '; '.join('{} {:.2f}'.format(k, v) for k, v in parts))
  log('compiles before the window: {:.0f} requests, {:.0f} answered by the '
      'persistent cache ({})', counters['jax/compiles'],
      counters['jax/compilation_cache_hits'], cache_root)
  log('after the window: unwinding Trainer.train (emergency checkpoint) and '
      'close {:.2f} s', s['trainer_closed'] - s['window_closed'])
