"""Driver for traffic of kind ``train_tokens``: a token model trained by
``Trainer.train`` from packed-text records written from the seed.

It times exactly as ``train_disk`` does (its ``Window``, ``RecordedInput``
and seeded state are imported, not copied): whole steps between two device
syncs, the profiler after the clock has stopped. It hands the metric readers
the same ``observations`` keys, so that every accepted per-layer metric
reads here too, and differs in three things.

The records. ``records.py`` draws every integer from {0, 1}; token ids have
to come from the slice of the vocabulary the configuration holds, in
documents (``token_records.py``).

The cost. ``costs.py`` counts the jaxpr and so sees the body of a Pallas
kernel once whatever its grid. Here the step's needed FLOPs come from
``token_costs.py``: the causal band only, the pairs the expert layers really
computed (the program's own counter), backward twice the forward,
rematerialisation not counted. ``cost['dot']`` holds only what XLA's output
fusions do, so ``conv_roofline`` does not read a kernel's work over time it
did not take. A Pallas kernel's instruction carries the kernel's name, so
``trace.op_family`` already keeps each kernel's seconds apart
(``reduced['families']``) and the readers in ``metrics/moe_attention.py`` find
them there.

``correct``. As ``train_disk``: every loss finite, no compile in the window
or in warm-up after the first step, the native loader read the records. And
six comparisons on the first batch, each tolerance in the configuration's
file with its reason: (1) the step's loss against the module applied plainly
at its own precision; (2) that against the INDEPENDENT float32 reference the
configuration names (``reference.loss``, a ``module:function`` of ``jax.numpy``
only, with ``reference.settings`` as its settings), one sequence at a time;
(3) the global norm of the step's gradient (the step metric ``grad_norm``)
against the norm of ``jax.grad`` of that reference; (4) the same for every
top-level entry of the parameter tree (``grad_group_norm/<name>``), the
worst held to its limit, so that a wrong layer is not hidden behind the
embedding and the head. Then the STATE the first step leaves behind,
against what the model's own optimizer, on a fresh state, makes of the
reference's gradient: (5) the step's gradient itself, read back from the
part of the optimizer's state that keeps it (``train.gradient_kept_in_state``;
Adam's first moment after one step is (1 - b1) x the gradient), as the
norm of the difference over the norm of the reference's, by top-level
group: a norm can agree where the direction does not; (6) the parameters,
as the norm of the difference over the norm of the expected change: 1 is
what a state left unchanged reads. And the program's ``moe/dropped_pairs``
must read 0 in every step of the window.

Nothing here names a model: the configuration's file gives the model class,
the reference's loss function, its settings and the cost function
(``reference.cost``), and the public keys ``vocab_size`` and
``num_hidden_layers``.
"""

import hashlib
import importlib
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark.harness import (
    cells,
    common,
    peaks,
    reference,
    timing,
    token_records,
)
from benchmark.harness.common import log
from benchmark.harness.train_disk import (
    RecordedInput,
    Window,
    WindowClosed,
    _batch_checksums,
    _checksum,
    _file_checksum,
    _log_setup,
    _seeded_state,
)

STEP_METRICS = ('moe/pairs_held', 'moe/expert_load_max_over_mean',
                'moe/dropped_pairs')
GROUP_PREFIX = 'grad_group_norm/'


class TokenWindow(Window):
  """``train_disk``'s window, keeping the expert layers' step metrics too,
  and reading the state the first step leaves behind against ``expected``
  (``expectations``' trees, parked on the host: nothing of the check lives
  on the device while a step runs)."""

  def __init__(self, expected, moment, *args, **kwargs):
    super().__init__(*args, **kwargs)
    self.step_metrics = []
    self._expected = expected
    self._moment = moment
    self.difference_squares = None

  def after_step(self, trainer, state, step, metrics):
    if self._expected is not None:
      # The first step, before the next one donates its state and before
      # the counters' snapshot that says warm-up compiled nothing. One
      # top-level group at a time comes back from the host.
      import jax
      from optax import tree_utils

      got = {'parameters': state.params,
             'gradient': tree_utils.tree_get(state.opt_state, self._moment)}
      difference = jax.jit(_squares)
      self.difference_squares = {
          kind: {name: float(difference(got[kind][name], want))
                 for name, want in groups.items()}
          for kind, groups in self._expected.items()}
      self._expected = None
    timed = self.first_sync_s is not None and self.last_sync_s is None
    if timed:
      self.step_metrics.append([metrics[name] for name in STEP_METRICS])
    super().after_step(trainer, state, step, metrics)


def _named(path):
  """'module:function' -> the function."""
  module_name, _, name = path.partition(':')
  return getattr(importlib.import_module(module_name), name)


def _squares(tree, other=None):
  """Sum of squares of the tree's leaves (less ``other``'s), in float32."""
  import jax
  import jax.numpy as jnp

  leaves = jax.tree.leaves(tree)
  others = jax.tree.leaves(other) if other is not None else leaves
  return sum(jnp.sum(jnp.square(
      (a - b if other is not None else a).astype(jnp.float32)))
             for a, b in zip(leaves, others))


def independent_reference(loss_fn, params, tokens, settings):
  """(loss, gradient tree) of the plain float32 reference on the batch
  ``tokens`` [B, L]: one sequence at a time, the gradients summed in place,
  so that one sequence's activations and two gradient trees are the most
  that lives beside the parameters."""
  import jax
  import jax.numpy as jnp

  def add(total, grads, params, row):
    loss, new = jax.value_and_grad(
        lambda p: loss_fn(p, row[None], settings))(params)
    return total + loss, jax.tree.map(jnp.add, grads, new)

  # One program for every sequence (the first adds to zeros): a second
  # would be another minute of compilation in set-up.
  add = jax.jit(add, donate_argnums=(1,))
  total = jnp.zeros((), jnp.float32)
  grads = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(params)
  for row in tokens:
    total, grads = add(total, grads, params, row)
  count = tokens.shape[0]
  grads = jax.jit(lambda g: jax.tree.map(lambda leaf: leaf / count, g),
                  donate_argnums=(0,))(grads)
  return float(total) / count, grads


def expectations(model, params, grads, moment):
  """What the first step must report and leave behind, from the reference's
  gradient and the model's own optimizer on a fresh state.

  ({group: gradient norm}, expected, {kind: {group: squared norm of the
  expected change}}), a group being a top-level entry of the parameter
  tree. ``expected`` holds two trees by group, on the HOST: 'parameters',
  the parameters after the update, and 'gradient', the subtree ``moment``
  of the optimizer's state after it: the one that keeps the gradient
  itself (``optax.adam``'s ``mu`` after the first step is (1 - b1) x the
  gradient), so that the step's whole gradient is compared, element by
  element. ``grads`` is donated."""
  import jax
  import optax
  from optax import tree_utils

  def expect(params, grads):
    optimizer = model.create_optimizer()
    fresh = optimizer.init(params)
    updates, after = optimizer.update(grads, fresh, params)
    expected = {'parameters': optax.apply_updates(params, updates),
                'gradient': tree_utils.tree_get(after, moment)}
    started = {'parameters': params,
               'gradient': tree_utils.tree_get(fresh, moment)}
    return ({name: _squares(grads[name]) for name in grads}, expected,
            {kind: {name: _squares(tree[name], started[kind][name])
                    for name in tree} for kind, tree in expected.items()})

  squares, expected, change = jax.jit(expect, donate_argnums=(1,))(
      params, grads)
  squares, expected, change = jax.device_get((squares, expected, change))
  return ({name: float(np.sqrt(value)) for name, value in squares.items()},
          expected, change)


def _set_moments_aside(state, model):
  """(state without its optimizer state, a function that puts it back).

  The float32 reference's gradient needs a parameter-sized tree of its own
  and several GB of temporaries; beside parameters AND two moments the chip
  has no room for them. Before the first step the optimizer's state is
  ``optimizer.init(params)`` and holds nothing but zeros, which is checked
  here; so it is freed for the reference and made again afterwards, by the
  model's own optimizer, laid out as it was."""
  import jax
  import jax.numpy as jnp

  moments = state.opt_state
  total = float(jax.jit(lambda tree: sum(
      jnp.sum(jnp.abs(leaf.astype(jnp.float32)))
      for leaf in jax.tree.leaves(tree)))(moments))
  if total != 0:
    raise RuntimeError('the optimizer state is not zero before the first '
                       'step (sum of magnitudes {})'.format(total))
  structure = jax.tree.structure(moments)
  layout = jax.tree.map(lambda leaf: leaf.sharding, moments)
  for leaf in jax.tree.leaves(moments):
    leaf.delete()

  def restore(state):
    fresh = jax.jit(lambda params: model.create_optimizer().init(params),
                    out_shardings=layout)(state.params)
    if jax.tree.structure(fresh) != structure:
      raise RuntimeError('optimizer.init gives another state than the '
                         'trainer\'s')
    return state.replace(opt_state=fresh)

  return state.replace(opt_state=None), restore


def ensure_records(cell, name, length, vocab, seed):
  """The cell's record file, written once per (sizes, seed, writer source)
  under the checkout's git-ignored cache."""
  t = cell.traffic
  args = (name, int(t['num_records']), length, seed, vocab,
          float(t['zipf_exponent']), int(t['document_median']),
          float(t['document_sigma']), int(t['document_shortest']),
          int(t['document_longest']))
  with open(token_records.__file__, 'rb') as f:
    source = f.read()
  key = hashlib.sha256(repr(args).encode() + source).hexdigest()[:16]
  path = os.path.join(cells.ROOT, '.bench_cache', 'records',
                      '{}-{}.tfrecord'.format(cell.config_name, key))
  if os.path.exists(path):
    return path, os.path.getsize(path), True
  return path, token_records.write_token_records(path, *args), False


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
  if model.is_device_tpu:
    model.set_preprocessor(Bfloat16PreprocessorWrapper(model.preprocessor))
  plain = cell.config['reference']
  settings = dict(common._tuples(plain['settings']))
  layers = int(cell.config['num_hidden_layers'])
  spec = model.get_feature_specification(ModeKeys.TRAIN)['tokens']
  length = int(spec.shape[0])

  records_path, records_bytes, cached = ensure_records(
      cell, spec.name, length, int(cell.config['vocab_size']), seed)
  stamps['records'] = time.perf_counter()
  log('records: {} sequences of {} tokens, {} bytes, {} ({})',
      traffic['num_records'], length, records_bytes,
      'found in the cache' if cached else 'written', records_path)

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
  tokens = np.asarray(features.to_dict()['tokens'])
  leaves = jax.tree.leaves(state.params)
  in_order, sorted_order = _batch_checksums([tokens])
  log('inputs from the seed: record file adler32 {:08x}; first batch by '
      'example, in order {:08x}, sorted {:08x}; first, middle and last '
      'parameter leaf {:08x}; ids {}..{}, {} end-of-document ids',
      _file_checksum(records_path), in_order, sorted_order,
      _checksum([leaves[0], leaves[len(leaves) // 2], leaves[-1]]),
      int(tokens.min()), int(tokens.max()),
      int(np.sum(tokens == token_records.END_OF_DOCUMENT)))
  # Before the window, and before the step donates the state: what the first
  # step must report and leave behind, by the module applied plainly at its
  # own precision (1) and by the independent float32 reference (2 to 5).
  plain_loss = reference.train_loss(
      model, state.params, state.model_state, features.to_dict(), None,
      jax.random.PRNGKey(trainer.seed + 1), np.int32(0), mesh, None)
  stamps['plain'] = time.perf_counter()
  state, restore_moments = _set_moments_aside(state, model)
  ref_loss, ref_grads = independent_reference(
      _named(plain['loss']), state.params,
      jax.device_put(tokens, devices[0]), settings)
  moment = train_cfg['gradient_kept_in_state']
  ref_norms, expected, expected_change = expectations(
      model, state.params, ref_grads, moment)
  del ref_grads
  state = restore_moments(state)
  stamps['reference'] = time.perf_counter()
  log('references: the module applied plainly {:.2f} s; the independent '
      'float32 reference, loss and gradient, {:.2f} s',
      stamps['plain'] - stamps['state'],
      stamps['reference'] - stamps['plain'])
  window = TokenWindow(expected, moment, seconds,
                       int(train_cfg['warm_steps']), profiler,
                       float(traffic.get('trace_seconds', 2.0)), generator,
                       devices)
  del expected
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
  step_metrics = np.asarray(jax.device_get(window.step_metrics),
                            np.float64).reshape(-1, len(STEP_METRICS))
  before, after = window.counters['before'], window.counters['after']
  window_compiles = after['jax/compiles'] - before['jax/compiles']
  recompiles = (before['jax/compiles'] -
                window.counters['after_first_step']['jax/compiles'])
  problems = []
  if not np.all(np.isfinite(losses)):
    problems.append('{} non-finite losses'.format(
        int(np.sum(~np.isfinite(losses)))))
  first = window.first_metrics
  log('first step: {}', ', '.join(
      '{} {:.6g}'.format(k, v) for k, v in sorted(first.items())))
  groups = sorted(ref_norms)
  errors = {name: abs(first.get(GROUP_PREFIX + name, float('nan')) -
                      ref_norms[name]) / max(ref_norms[name], 1e-30)
            for name in groups}
  log('gradient norm by group, the step against the reference (relative '
      'error): {}', ', '.join('{} {:.6g} / {:.6g} ({:.3g})'.format(
          name, first.get(GROUP_PREFIX + name, float('nan')),
          ref_norms[name], errors[name]) for name in groups))
  worst = max(groups, key=lambda name: (not np.isfinite(errors[name]),
                                        errors[name]))
  ref_grad_norm = float(np.sqrt(sum(v * v for v in ref_norms.values())))
  left = {kind: {name: float(np.sqrt(
      window.difference_squares[kind][name] /
      max(float(expected_change[kind][name]), 1e-60))) for name in groups}
          for kind in ('gradient', 'parameters')}
  log('the state after the first step against the model\'s optimizer '
      'applied to the reference\'s gradient, |difference| / |expected '
      'change| by group: the gradient kept in {!r}: {}; the parameters: {}',
      moment, *(', '.join('{} {:.3g}'.format(name, left[kind][name])
                          for name in groups)
                for kind in ('gradient', 'parameters')))
  worst_gradient = max(groups, key=lambda name: (
      not np.isfinite(left['gradient'][name]), left['gradient'][name]))
  change_error = float(np.sqrt(
      sum(window.difference_squares['parameters'].values()) /
      max(sum(float(v) for v in expected_change['parameters'].values()),
          1e-60)))
  for got, want, tolerance, what in (
      (first['loss'], plain_loss, 'step_rel_tolerance',
       '(1) loss of the first batch, the step against the module applied '
       'plainly at the configuration\'s precision'),
      (first['loss'], ref_loss, 'reference_rel_tolerance',
       '(2) loss of the first batch, the step against the independent '
       'float32 reference'),
      (first.get('grad_norm', float('nan')), ref_grad_norm,
       'grad_norm_rel_tolerance',
       '(3) global norm of the first step\'s gradient against the '
       'independent float32 reference\'s'),
      (first.get(GROUP_PREFIX + worst, float('nan')), ref_norms[worst],
       'group_grad_norm_rel_tolerance',
       '(4) norm of the first step\'s gradient by top-level group against '
       'the reference\'s, the worst group, {}'.format(worst))):
    agrees, report = reference.agree(got, want, float(train_cfg[tolerance]),
                                     what)
    log('{}', report)
    if not agrees:
      problems.append(report)
  for reading, tolerance, what in (
      (left['gradient'][worst_gradient], 'gradient_difference_tolerance',
       '(5) the first step\'s gradient, read back from {!r} of the '
       'optimizer\'s state, against the reference\'s, |difference| over '
       '|reference| by top-level group, the worst group, {}'.format(
           moment, worst_gradient)),
      (change_error, 'parameter_change_tolerance',
       '(6) the parameters after the first step against the model\'s '
       'optimizer applied to the reference\'s gradient, |difference| over '
       '|expected change| (a state left unchanged reads 1)')):
    limit = float(train_cfg[tolerance])
    report = '{}: {:.3g} (tolerance {:g})'.format(what, reading, limit)
    log('{}', report)
    if not reading <= limit:
      problems.append(report)
  dropped = step_metrics[:, STEP_METRICS.index('moe/dropped_pairs')]
  if first.get('moe/dropped_pairs', 0.0) or np.any(dropped != 0):
    problems.append('{:.0f} pairs of held experts were not computed'.format(
        first.get('moe/dropped_pairs', 0.0) + dropped.sum()))
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
  log('window: {} whole steps of {} sequences ({} tokens) in {:.4f} s on {} '
      'chip(s): {:.3f} examples/s/chip; losses {:.5f} .. {:.5f}', steps,
      batch, batch * length, window_s, cell.chips, rate, losses[0],
      losses[-1])
  gaps = np.diff([window.first_sync_s] + window.boundaries_s)
  log('step boundaries on the training thread: median {:.4f} s apart; the '
      'longest: {}', np.median(gaps),
      ', '.join('{:.4f} s before step {}'.format(gaps[i], i + 1)
                for i in np.argsort(-gaps)[:3]))
  moe = {
      'pairs_held_per_step': float(step_metrics[:, 0].mean()),
      'tokens_per_step': float(batch * length * layers),
      'load_max_over_mean': float(step_metrics[:, 1].mean()),
      'dropped_pairs': float(dropped.sum()),
  }
  log('expert layers over the window: {:.1f} pairs held a step over {} '
      'layers ({:.4f} a token a layer; {:.0f} .. {:.0f} by step), largest '
      'expert over mean {:.3f}, dropped {:.0f}', moe['pairs_held_per_step'],
      len(settings['window_layers']),
      moe['pairs_held_per_step'] / moe['tokens_per_step'],
      step_metrics[:, 0].min(), step_metrics[:, 0].max(),
      moe['load_max_over_mean'], moe['dropped_pairs'])

  observations = {
      'chips': cell.chips, 'window_s': window_s,
      'steps': steps, 'examples_per_step': batch, 'setup_s': window.setup_s,
      'train_examples_per_s_per_chip': rate,
      'counters': {'before': before, 'after': after},
      'trace': profiler.reduced if profiler is not None else None,
      'peaks': peak_row,
      'memory_peak_bytes': window.memory_peak_bytes,
      'moe': moe,
  }
  if trace:
    cost = _named(plain['cost'])(settings, batch, length,
                                 moe['pairs_held_per_step'])
    observations['cost'] = cost
    log('cost per step at batch {}: {:.4g} FLOPs needed ({:.4g} dense '
        'products, {:.4g} attention over the band, {:.4g} experts over '
        '{:.0f} pairs)', batch, cost['flops'], cost['dot']['flops'],
        cost['attention']['flops'], cost['experts']['flops'],
        moe['pairs_held_per_step'])
  return {
      'correct': not problems,
      'attempted': len(losses),
      'failed': int(np.sum(~np.isfinite(losses))),
      'observations': observations,
      'device': common.device_report(devices,
                                     observations['memory_peak_bytes']),
  }
