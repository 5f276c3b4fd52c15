"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children, no network. Drives the flagship QT-Opt path once
at full width through the entry points a user calls, and checks what comes
out by the repo's own means:

  trainer  Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom (all 19
           layers, bf16) trained for a few steps by ``train_eval_model`` from
           512x640 JPEG records this script writes from a seed, read by
           ``DefaultRecordInputGenerator`` through the native loader,
           cropped to 472x472 on device: native stream asserted, loss
           finite and changing, one executable after warm-up, checkpoint
           committed.
  server   a ``PolicyServer`` built the way ``bin/t2r_serve`` builds it
           (that checkpoint, CEM 64x3, batch 8, the AOT ``load_or_compile``
           path) answers 512x640 requests: none failed, every action finite
           and of the declared shape, no compile at request time; a second
           server then starts from the PERSISTED executable.
  flash    ``parallel/flash_attention.py`` forward and the backward kernel
           at the seq2act long-context shape (L=4096, 8 heads of 64),
           compiled by Mosaic — not interpreted — against the dense
           ``scaled_dot_attention``.

It refuses to run unless JAX's backend is the TPU, uses every local device
(one chip or four), and exits non-zero when any leg fails; no leg is
wrapped in a handler that could end the run with 0. Every time it prints is
a smoke reading (one run, compile included where said), not a measurement.
The last line of stdout is the result JSON.
"""

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

class SmokeFailure(RuntimeError):
  """A leg produced something the repo's own checks reject."""


def _check(condition, message):
  if not condition:
    raise SmokeFailure(message)


def write_grasp_records(path, model, num_records, seed=0):
  """Grasp attempts as reference-format records, through the repo's writer:
  JPEG camera frames, grasp params, and success == 'the gripper closed' (a
  rule the critic can learn, so the loss has somewhere to go)."""
  from tensor2robot_tpu.data.writer import TFRecordReplayWriter
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.specs.struct import SpecStruct
  from tensor2robot_tpu.utils.image import (
      camera_like_frame,
      numpy_to_image_string,
  )

  features = model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN)
  labels = model.preprocessor.get_in_label_specification(ModeKeys.TRAIN)
  spec = SpecStruct(f=features, l=labels)
  height, width = features['state/image'].shape[:2]
  rng = np.random.RandomState(seed)
  with TFRecordReplayWriter() as writer:
    writer.open(path)
    for i in range(num_records):
      values = SpecStruct()
      for key in features:
        if key == 'state/image':
          values['f/' + key] = numpy_to_image_string(
              camera_like_frame(rng, height, width))
        else:
          values['f/' + key] = rng.rand(
              *features[key].shape).astype(np.float32)
      closed = np.asarray([float(i % 2)], np.float32)
      values['f/action/close_gripper'] = closed
      values['l/reward'] = closed.copy()
      writer.write_numpy(spec, values)


def _flagship(**kwargs):
  from tensor2robot_tpu.research.qtopt.t2r_models import (
      Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
  )

  return Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(**kwargs)


def trainer_leg(work_dir, mesh, batch_size=64, steps=6, model_kwargs=None):
  """Leg (a). Returns the model_dir holding the committed checkpoint."""
  import jax

  from tensor2robot_tpu.data.input_generators import (
      DefaultRecordInputGenerator,
  )
  from tensor2robot_tpu.hooks.hook_builder import HookBuilder, TrainHook
  from tensor2robot_tpu.observability import get_registry
  from tensor2robot_tpu.observability.pipeline_xray import (
      DECODE_WORKERS_GAUGE,
  )
  from tensor2robot_tpu.observability.watchdog import RECOMPILE_GAUGE
  from tensor2robot_tpu.trainer import checkpointing, train_eval_model

  model = _flagship(**(model_kwargs or {}))
  records = os.path.join(work_dir, 'grasps-00000.tfrecord')
  write_grasp_records(records, model, num_records=2 * batch_size)

  class StepRecorder(TrainHook, HookBuilder):
    """Loss and host clock at every step (the fetch waits for the step)."""

    def __init__(self):
      self.losses, self.clock = [], []

    def create_hooks(self, t2r_model, trainer):
      return [self]

    def begin(self, trainer):
      self.clock.append(time.perf_counter())

    def after_step(self, trainer, state, step, metrics):
      self.losses.append(float(jax.device_get(metrics['loss'])))
      self.clock.append(time.perf_counter())

  recorder = StepRecorder()
  model_dir = os.path.join(work_dir, 'run')
  result = train_eval_model(
      model, model_dir,
      input_generator_train=DefaultRecordInputGenerator(
          file_patterns=records, batch_size=batch_size),
      max_train_steps=steps, save_checkpoints_steps=steps, mesh=mesh,
      train_hook_builders=[recorder])

  registry = get_registry()
  _check(registry.gauge(DECODE_WORKERS_GAUGE).value > 0,
         'the records were not read by the native loader')
  _check(len(recorder.losses) == steps,
         'ran {} steps, wanted {}'.format(len(recorder.losses), steps))
  _check(np.all(np.isfinite(recorder.losses)),
         'non-finite loss: {}'.format(recorder.losses))
  _check(len(set(recorder.losses)) > 1,
         'loss never changed: {}'.format(recorder.losses))
  _check(int(jax.device_get(result['state'].step)) == steps,
         'train state stopped short of step {}'.format(steps))
  _check(registry.gauge(RECOMPILE_GAUGE).value == 1.0,
         'train step holds {} executables after warm-up, wanted 1'.format(
             registry.gauge(RECOMPILE_GAUGE).value))
  _check(checkpointing.latest_checkpoint_step(model_dir) == steps,
         'no committed checkpoint at step {}'.format(steps))

  params = result['state'].params
  devices = set()
  for leaf in jax.tree_util.tree_leaves(params):
    devices |= leaf.sharding.device_set
  _check(len(devices) == mesh.devices.size,
         'parameters live on {} of {} devices'.format(len(devices),
                                                      mesh.devices.size))
  walls = np.diff(recorder.clock)
  print('trainer: PASS  batch {} x {} steps, loss {:.4f} -> {:.4f}; '
        'smoke reading: first step (set-up + compile) {:.1f} s, steps '
        'between {} s, last step (with the first log window and the '
        'checkpoint save) {:.1f} s'
        .format(batch_size, steps, recorder.losses[0], recorder.losses[-1],
                walls[0], ' '.join('{:.3f}'.format(w) for w in walls[1:-1]),
                walls[-1]))
  return model_dir


def _drive(server, feature_spec, requests, clients, action_shape):
  """``requests`` closed-loop requests from ``clients`` threads; every
  answer is checked, every failure kept."""
  failures, latencies = [], []
  lock = threading.Lock()

  def client(seed, count):
    rng = np.random.RandomState(seed)
    state = {}
    for name, (shape, dtype) in feature_spec.items():
      if np.dtype(dtype) == np.uint8:
        state[name] = rng.randint(0, 255, shape).astype(np.uint8)
      else:
        state[name] = rng.uniform(0, 1, shape).astype(dtype)
    for _ in range(count):
      try:
        result = server.select_action(state, timeout_s=120.0)
        action = np.asarray(result.outputs['action'])
        q = np.asarray(result.outputs['q'])
        if action.shape != action_shape or not np.all(np.isfinite(action)) \
            or not np.all(np.isfinite(q)):
          raise SmokeFailure('bad answer: action {} {} q {}'.format(
              action.shape, action, q))
        with lock:
          latencies.append(result.latency_ms)
      except Exception as e:  # noqa: BLE001 — every failure is reported
        with lock:
          failures.append(repr(e))

  per_client = -(-requests // clients)
  threads = [threading.Thread(target=client, args=(i, per_client))
             for i in range(clients)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  _check(not failures, '{} of {} requests failed: {}'.format(
      len(failures), per_client * clients, failures[:3]))
  return latencies


def server_leg(checkpoint_dir, device_type, image_shape=(512, 640, 3),
               cem_samples=64, cem_iters=3, num_elites=10, max_batch_size=8,
               requests=48, model_kwargs=None):
  """Leg (b): ``bin/t2r_serve``'s single-server construction, verbatim."""
  import jax

  from tensor2robot_tpu.compile.artifact import ArtifactStore
  from tensor2robot_tpu.observability import get_registry
  from tensor2robot_tpu.observability.signals import COMPILE_COUNTER
  from tensor2robot_tpu.predictors import CheckpointPredictor
  from tensor2robot_tpu.research.qtopt.t2r_models import CEM_ACTION_SIZE
  from tensor2robot_tpu.serving import (
      PolicyServer,
      ServingConfig,
      load_or_compile,
  )

  model = _flagship(device_type=device_type, **(model_kwargs or {}))
  predictor = CheckpointPredictor(model, checkpoint_dir, timeout=60.0)
  _check(predictor.restore(), 'no checkpoint restorable from ' +
         checkpoint_dir)
  version, variables = predictor.versioned_variables
  feature_spec = model.serving_feature_spec(image_shape=image_shape)
  jitted = jax.jit(model.make_batched_select_action(
      cem_samples=cem_samples, cem_iters=cem_iters, num_elites=num_elites))
  abstract_args = (
      jax.tree_util.tree_map(
          lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), variables),
      {name: jax.ShapeDtypeStruct((max_batch_size,) + shape, dtype)
       for name, (shape, dtype) in feature_spec.items()},
      jax.ShapeDtypeStruct((), 'uint32'))
  workload = 'serving_{}_b{}'.format(type(model).__name__, max_batch_size)
  config = ServingConfig(max_batch_size=max_batch_size)
  compiles = get_registry().counter(COMPILE_COUNTER)

  def serve(executable, aot_info, count):
    server = PolicyServer(executable, variables, config, version=version,
                          feature_spec=feature_spec, aot_info=aot_info)
    server.start()
    try:
      before = compiles.value
      latencies = _drive(server, feature_spec, count,
                         clients=2 * max_batch_size,
                         action_shape=(CEM_ACTION_SIZE,))
      _check(compiles.value == before,
             '{} compiles at request time'.format(compiles.value - before))
      _check(server.drain(timeout_s=30.0), 'server did not drain')
      stats = server.stats()
      _check(stats['errors_total'] == 0 and stats['rejected_total'] == 0,
             'server counted errors/rejections: {}'.format(stats))
    finally:
      server.close()
    return latencies

  t0 = time.perf_counter()
  artifact = load_or_compile(workload, jitted, abstract_args)
  startup_s = time.perf_counter() - t0
  _check(artifact.path, 'the executable was not persisted')
  latencies = serve(artifact.executable,
                    {'aot_startup': True, 'from_cache': artifact.from_cache,
                     'workload': workload, 'config_id': artifact.config_id},
                    requests)
  print('server: PASS  {} requests, 0 failed, 0 request-time compiles '
        '(CEM {}x{}, batch {}); smoke reading: AOT start ({}) {:.1f} s, '
        'request latency median {:.1f} ms (first batch included)'.format(
            len(latencies), cem_samples, cem_iters, max_batch_size,
            'deserialized' if artifact.from_cache else 'compiled + persisted',
            startup_s, float(np.median(latencies))))

  # Second start: straight from the store, past load_or_compile's
  # in-process memo, so the bytes on disk are what gets loaded.
  t0 = time.perf_counter()
  executable, _, reason = ArtifactStore().load(artifact.key,
                                               artifact.config_id)
  restart_s = time.perf_counter() - t0
  _check(executable is not None,
         'second start could not load the persisted executable: ' + reason)
  serve(executable, {'aot_startup': True, 'from_cache': True,
                     'workload': workload, 'config_id': artifact.config_id},
        2 * max_batch_size)
  print('server: PASS  second start loaded the persisted executable ({}); '
        'smoke reading: {:.1f} s'.format(reason, restart_s))
  predictor.close()


def flash_leg(seq_len=4096, batch=2, heads=8, head_dim=64, interpret=False,
              tolerance=2e-2):
  """Leg (c): the Pallas kernels against the dense reference, bf16 in,
  compared in float32. ``tolerance`` bounds max|flash - dense| relative to
  max|dense|, for the output and for each of dq, dk, dv (bf16 has 8 bits of
  mantissa; the two paths round at different points)."""
  import jax
  import jax.numpy as jnp

  from tensor2robot_tpu.layers.transformer import (
      resolve_attention_mode,
      scaled_dot_attention,
  )
  from tensor2robot_tpu.parallel.flash_attention import flash_attention

  rng = np.random.RandomState(0)
  q, k, v, dout = (jnp.asarray(rng.randn(batch, seq_len, heads, head_dim),
                               jnp.bfloat16) for _ in range(4))

  def flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=interpret)

  def loss_of(attention):
    return lambda q, k, v: jnp.sum(
        attention(q, k, v).astype(jnp.float32) * dout.astype(jnp.float32))

  flash_both = jax.jit(lambda q, k, v: (
      flash(q, k, v), jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)))
  dense_both = jax.jit(lambda q, k, v: (
      scaled_dot_attention(q, k, v, True),
      jax.grad(loss_of(lambda q, k, v: scaled_dot_attention(q, k, v, True)),
               argnums=(0, 1, 2))(q, k, v)))

  if not interpret:
    _check(resolve_attention_mode('auto', seq_len) == 'flash',
           "attention_mode='auto' does not choose the flash kernel at L={} "
           'on this backend'.format(seq_len))
    kernels = flash_both.lower(q, k, v).as_text().count('tpu_custom_call')
    _check(kernels >= 2,
           'lowered program holds {} Mosaic custom calls, wanted the '
           'forward and the backward kernel'.format(kernels))

  t0 = time.perf_counter()
  out, grads = jax.block_until_ready(flash_both(q, k, v))
  flash_s = time.perf_counter() - t0
  ref_out, ref_grads = jax.block_until_ready(dense_both(q, k, v))
  worst = {}
  for name, got, want in zip(('out', 'dq', 'dk', 'dv'), (out,) + grads,
                             (ref_out,) + ref_grads):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    _check(np.all(np.isfinite(got)), 'flash {} is not finite'.format(name))
    worst[name] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
  _check(max(worst.values()) <= tolerance,
         'flash attention disagrees with the dense reference: {} '
         '(tolerance {})'.format(worst, tolerance))
  print('flash: PASS  L={} heads={} head_dim={} {}; worst relative error {} '
        '(tolerance {}); smoke reading: compile + first call {:.1f} s'.format(
            seq_len, heads, head_dim,
            'interpreted' if interpret else 'compiled by Mosaic',
            {k: round(v, 5) for k, v in worst.items()}, tolerance, flash_s))


def main():
  import jax

  from tensor2robot_tpu import parallel, runtime
  from tensor2robot_tpu.observability import (
      get_registry,
      install_jax_listeners,
      roofline,
  )
  from tensor2robot_tpu.observability.signals import (
      CACHE_HIT_COUNTER,
      COMPILE_COUNTER,
  )

  cache_dir = runtime.enable_compile_cache()
  if not runtime.on_tpu():
    print('chip_smoke: JAX backend is {!r}, not the TPU; nothing was '
          'run.'.format(jax.default_backend()), file=sys.stderr)
    return 2
  install_jax_listeners()
  t_start = time.perf_counter()
  device = {'platform': jax.devices()[0].platform,
            'kind': jax.devices()[0].device_kind,
            'count': len(jax.devices())}
  print('device: platform={platform} device_kind={kind!r} '
        'count={count}'.format(**device))
  print('versions: jax {} jaxlib {} libtpu {}'.format(
      jax.__version__, jax.lib.__version__, _libtpu_version()))
  print('compile cache: {}'.format(cache_dir))
  _check(roofline.device_peaks(device['kind']) is not None,
         'device_kind {!r} is not in roofline.PEAKS'.format(device['kind']))
  mesh = parallel.create_mesh()
  print('mesh: {} device order {}'.format(
      dict(mesh.shape), [d.id for d in mesh.devices.flat]))

  registry = get_registry()

  def compiles(leg):
    # Counts, exact: how many programs the leg asked the backend for, and
    # how many of those the persistent cache answered without compiling.
    print('{}: {:.0f} compile requests so far, {:.0f} answered by the '
          'persistent cache'.format(
              leg, registry.counter(COMPILE_COUNTER).value,
              registry.counter(CACHE_HIT_COUNTER).value))

  with tempfile.TemporaryDirectory(prefix='chip_smoke_') as work_dir:
    model_dir = trainer_leg(work_dir, mesh)
    compiles('trainer')
    server_leg(model_dir, device_type='tpu')
    compiles('server')
  flash_leg()
  compiles('flash')
  print('smoke reading: whole run {:.1f} s'.format(
      time.perf_counter() - t_start))
  print(json.dumps({'ok': True, 'device': device}))
  return 0


def _libtpu_version():
  from importlib import metadata

  try:
    return metadata.version('libtpu')
  except metadata.PackageNotFoundError:
    return 'unknown'


if __name__ == '__main__':
  sys.exit(main())
