"""Measured zero-compile cold start of a trainer and a serving program.

Builds the test-scale qtopt critic (the sim critic — a real
Grasping44-spec-keyed QT-Opt model), binds its train step through the
unified ``CompiledArtifact`` store, runs ONE completed (blocked) train
step, and reports:

  * ``time_to_first_step_s`` — wall time from trainer state-init to
    the first step's results being ready: checkpoint/state
    initialization, the artifact load-or-compile bind, and the first
    executed step — exactly the phase the artifact store addresses.
    Imports and model/generator construction happen BEFORE the clock
    starts: they are identical cold vs warm, and leaving ~4 s of
    constant import noise in the window would drown the compile
    savings of a test-scale model (at the 472x472 headline model the
    compile is tens of seconds and the distinction stops mattering);
  * ``step_compiles`` — the ``jax/compiles`` counter delta across
    artifact-bind + first step ONLY (eager-op warmup noise excluded by
    construction): the zero-compile cold-start contract as a number —
    0 on a warm store, > 0 on a cold one;
  * ``serving_time_to_ready_s`` — the serving adapter loading a
    batched CEM select program over the same critic (the
    ``serving/artifact.py`` path);
  * ``artifact_hits`` / ``artifact_misses`` — the store counters.

Run it as a SUBPROCESS for a true process cold start (an in-process
"warm" leg would also be warmed by jax's per-object and eager caches,
which is exactly the measurement error the subprocess discipline
exists to kill):

    python -m tensor2robot_tpu.compile.coldstart \
        --cache_path /tmp/store/tuning_cache.json --model_dir /tmp/run

Prints one JSON line on stdout. Also imported directly by
tests/test_compile_artifact.py — the in-process warm call still proves
the artifact path compiles nothing, because a fresh ``jax.jit`` object
never shares an executable cache with the first trainer's.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict


def measure(cache_path: str, model_dir: str, batch_size: int = 8,
            height: int = 32, width: int = 40,
            serving_batch: int = 4, seed: int = 0,
            model_name: str = 'sim') -> Dict[str, object]:
  """One cold-start measurement; see the module docstring.

  ``model_name``: ``'sim'`` (the test-scale sim critic at
  height x width — what the test suite uses) or ``'grasping44'`` (the
  REAL flagship 19-layer QT-Opt critic at camera resolution: its
  multi-second step compile makes the cold-vs-warm delta unmistakable).
  """
  import jax
  import numpy as np
  import optax
  from jax.sharding import NamedSharding, PartitionSpec as P

  from tensor2robot_tpu import runtime
  from tensor2robot_tpu.data.input_generators import (
      DefaultRandomInputGenerator,
  )
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.observability import get_registry
  from tensor2robot_tpu.observability import signals as signals_lib
  from tensor2robot_tpu.research.qtopt import grasping_sim
  from tensor2robot_tpu.rl.loop import make_cem_select_fn
  from tensor2robot_tpu.serving import artifact as serving_artifact
  from tensor2robot_tpu.trainer import Trainer
  from tensor2robot_tpu.trainer.train_eval import (
      provide_input_generator_with_model_information,
  )
  from tensor2robot_tpu.tuning import cache as cache_lib

  signals_lib.install_jax_listeners()
  registry = get_registry()

  if model_name == 'grasping44':
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
    )

    model = Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
        device_type='tpu' if runtime.on_tpu() else 'cpu')
    height, width = 512, 640  # the flagship camera frame
    workload = 'coldstart_qtopt44_b{}'.format(batch_size)
  elif model_name == 'sim':
    model = grasping_sim.make_sim_critic_model(
        height, width, create_optimizer_fn=lambda: optax.adam(3e-3))
    workload = 'coldstart_qtopt_b{}'.format(batch_size)
  else:
    raise ValueError('model_name must be "sim" or "grasping44"; got '
                     '{!r}.'.format(model_name))
  generator = DefaultRandomInputGenerator(batch_size=batch_size)
  trainer = Trainer(model, model_dir, async_checkpoints=False,
                    save_checkpoints_steps=10**9,
                    log_every_n_steps=10**9, auto_profile=False,
                    enable_watchdog=False, enable_pipeline_xray=False,
                    write_metrics=False, use_compiled_artifacts=True,
                    artifact_workload=workload,
                    tuning_cache_path=cache_path, seed=seed)
  try:
    generator = provide_input_generator_with_model_information(
        generator, model, ModeKeys.TRAIN)
    iterator = generator.create_dataset_iterator(mode=ModeKeys.TRAIN,
                                                 seed=seed)
    features, labels = next(iterator)
    t_start = time.perf_counter()
    state = trainer.init_state(features, labels)
    step_fn = trainer._compile_train_step()  # noqa: SLF001 — this
    # measures the exact first-call bind path the train loop drives.
    device_batch = trainer._put_batch(  # noqa: SLF001
        {'features': features.to_dict(), 'labels': labels.to_dict()})
    base_rng = jax.device_put(jax.random.PRNGKey(seed + 1),
                              NamedSharding(trainer.mesh, P()))

    # The contract window: artifact bind + first executed step. Eager
    # warmup compiles (PRNG seeding, host preprocessing) happened above
    # and are identical cold vs warm — they are process startup, not
    # the step compile this axis measures.
    compiles_before = registry.counter(signals_lib.COMPILE_COUNTER).value
    state, metrics = step_fn(state, device_batch['features'],
                             device_batch['labels'], base_rng)
    jax.block_until_ready(metrics)
    time_to_first_step = time.perf_counter() - t_start
    step_compiles = (registry.counter(signals_lib.COMPILE_COUNTER).value
                     - compiles_before)

    # Serving leg: the batched CEM select program over the same critic
    # through the serving adapter (program pinned by the workload name).
    variables = {'params': state.params}
    if state.model_state:
      variables.update(state.model_state)
    select = make_cem_select_fn(model, cem_samples=4, cem_iters=1,
                                num_elites=2)
    batched = jax.jit(jax.vmap(select, in_axes=(None, 0, 0)))
    obs = {
        'image': np.zeros((serving_batch, height, width, 3), np.uint8),
        'gripper_closed': np.zeros((serving_batch,), np.float32),
        'height_to_bottom': np.full((serving_batch,), 10.0, np.float32),
    }
    keys = jax.random.split(jax.random.PRNGKey(seed), serving_batch)
    t0 = time.perf_counter()
    served = serving_artifact.load_or_compile(
        'coldstart_serving_{}_b{}'.format(model_name, serving_batch),
        batched, (variables, obs, keys),
        cache=cache_lib.ConfigCache(cache_path))
    jax.block_until_ready(served.executable(variables, obs, keys))
    serving_time_to_ready = time.perf_counter() - t0

    scalars = registry.scalars()
    hits = sum(value for tag, value in scalars.items()
               if tag.startswith('compile/artifact_hits'))
    misses = sum(value for tag, value in scalars.items()
                 if tag.startswith('compile/artifact_misses'))
    return {
        'time_to_first_step_s': round(time_to_first_step, 3),
        'step_compiles': int(step_compiles),
        'serving_time_to_ready_s': round(serving_time_to_ready, 3),
        'serving_from_cache': bool(served.from_cache),
        'trainer_from_cache': bool(
            trainer._train_step_artifact is not None  # noqa: SLF001
            and trainer._train_step_artifact.from_cache),  # noqa: SLF001
        'artifact_hits': int(hits),
        'artifact_misses': int(misses),
    }
  finally:
    trainer.close()


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--cache_path', required=True,
                      help='tuning-cache path; artifacts persist beside it')
  parser.add_argument('--model_dir', required=True)
  parser.add_argument('--batch_size', type=int, default=8)
  parser.add_argument('--height', type=int, default=32)
  parser.add_argument('--width', type=int, default=40)
  parser.add_argument('--seed', type=int, default=0)
  parser.add_argument('--model', default='sim',
                      choices=('sim', 'grasping44'),
                      help='trainer model: test-scale sim critic or the '
                           'flagship 19-layer QT-Opt critic.')
  args = parser.parse_args(argv)
  result = measure(args.cache_path, args.model_dir,
                   batch_size=args.batch_size, height=args.height,
                   width=args.width, seed=args.seed,
                   model_name=args.model)
  print(json.dumps(result))
  return 0


if __name__ == '__main__':
  import sys

  from tensor2robot_tpu import runtime

  # Process-wide configuration belongs to the process entry, not to
  # main(argv), which tests call in-process.
  runtime.enable_compile_cache()
  sys.exit(main())
