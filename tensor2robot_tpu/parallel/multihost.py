"""Multi-host (multi-process) training: init, per-host data, checkpoints.

SURVEY §2.9's DCN row: the reference scales across hosts through TF1's
gRPC/TF_CONFIG machinery (utils/train_eval.py:552,
models/abstract_model.py:845-851); here multi-host is JAX's native
multi-process model — one controller process per host, a global mesh over
all devices, per-host input shards assembled into global arrays
(parallel/sharding.py shard_batch -> make_array_from_process_local_data),
and Orbax writing a sharded checkpoint cooperatively from every host.

``python -m tensor2robot_tpu.parallel.multihost --process_id=K ...`` runs
a self-contained two-host dry run on CPU devices — the executable proof
(driven by tests/test_multihost.py) that distributed init + per-host data
+ mesh-sharded training + multi-host checkpointing compose. The same code
path serves real pods: only coordinator_address and the device platform
change.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional


def initialize(coordinator_address: str, num_processes: int,
               process_id: int,
               local_device_count: Optional[int] = None) -> None:
  """jax.distributed.initialize with optional CPU device virtualization.

  Must run before any other JAX call in the process. On TPU pods the
  arguments are auto-detected and this reduces to
  ``jax.distributed.initialize()``.
  """
  if local_device_count is not None:
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '') +
        ' --xla_force_host_platform_device_count={}'.format(
            local_device_count))
  import jax

  jax.distributed.initialize(coordinator_address=coordinator_address,
                             num_processes=num_processes,
                             process_id=process_id)


def multihost_dryrun(workdir: str, num_processes: int, process_id: int,
                     train_steps: int = 2) -> None:
  """Train a mock model across all processes' devices; checkpoint; verify.

  Asserts (a) every host sees the global device count, (b) per-host data
  shards assemble into one global batch (each host reads DIFFERENT files),
  (c) the jitted step runs with gradients psummed across hosts, (d) the
  Orbax checkpoint written cooperatively restores to identical params on
  every host, (e — ISSUE 9) each host emitted its OWN
  ``telemetry.<process_index>.jsonl`` under the SHARED model_dir (two
  processes appending one file would interleave torn lines), stamped
  with its identity, and host 0's fleet view federates every host's
  stream, and (f — ISSUE 15) the train step resolves through the shared
  ``CompiledArtifact`` store: host 0 AOT-compiles and PERSISTS the
  executable behind a barrier, hosts 1..N then bind by DESERIALIZING it
  — their ``jax/compiles`` delta across the bind is asserted 0, closing
  ROADMAP item 4's shared-autotuner/compile-cache clause (N hosts, one
  compile).
  """
  import jax
  import jax.numpy as jnp
  import numpy as np
  from jax.experimental import multihost_utils

  from tensor2robot_tpu import parallel
  from tensor2robot_tpu.data import tfrecord, wire
  from tensor2robot_tpu.data.input_generators import (
      DefaultRecordInputGenerator,
  )
  from tensor2robot_tpu.observability import get_registry
  from tensor2robot_tpu.trainer import Trainer
  from tensor2robot_tpu.utils.mocks import MockT2RModel

  assert jax.process_count() == num_processes, (
      jax.process_count(), num_processes)
  n_local = len(jax.local_devices())
  n_global = len(jax.devices())
  assert n_global == n_local * num_processes

  # Each host writes (then reads) its OWN shard files — the per-host input
  # contract (ref utils/tfdata.py:43-66, PER_HOST_V2).
  model = MockT2RModel(device_type='cpu')
  feature_spec = model.preprocessor.get_in_feature_specification('train')
  label_spec = model.preprocessor.get_in_label_specification('train')
  rng = np.random.RandomState(process_id)
  records = []
  for _ in range(64):
    x = rng.rand(8).astype(np.float32)
    y = np.asarray([float(x.sum() > 4.0)], np.float32)
    records.append(wire.build_example(
        {'measured_position': x, 'valid_position': y}))
  shard_dir = os.path.join(workdir, 'shards')
  os.makedirs(shard_dir, exist_ok=True)
  # All shard files exist for all hosts; host K reads files[K::N].
  path = os.path.join(shard_dir, 'data-{:05d}.tfrecord'.format(process_id))
  tfrecord.write_records(path, records)
  multihost_utils.sync_global_devices('shards_written')

  del feature_spec, label_spec
  mesh = parallel.create_mesh({'data': n_global})
  global_batch = 4 * n_global
  generator = DefaultRecordInputGenerator(
      file_patterns=os.path.join(shard_dir, 'data-*.tfrecord'),
      batch_size=global_batch // num_processes)
  model_dir = os.path.join(workdir, 'model')
  trainer = Trainer(model, model_dir, mesh=mesh, async_checkpoints=False,
                    save_checkpoints_steps=train_steps,
                    log_every_n_steps=10**9,
                    use_compiled_artifacts=True,
                    artifact_workload='multihost_step',
                    tuning_cache_path=os.path.join(workdir,
                                                   'compile_cache.json'))
  # ISSUE 15 satellite: the train step resolves through the SHARED
  # CompiledArtifact store. Host 0 AOT-compiles and persists the
  # executable while everyone else waits at the barrier; hosts 1..N
  # then bind by DESERIALIZING it — with their jax/compiles delta
  # across the bind asserted 0 (N hosts, ONE compile: ROADMAP item 4's
  # shared-autotuner/compile-cache clause). bind_train_step never
  # executes the (collective) step, which is what makes the stagger
  # legal before the first synchronized train step below.
  generator.set_specification_from_model(model, 'train')
  bind_features, bind_labels = next(generator.create_dataset_iterator(
      mode='train', shard_index=process_id, num_shards=num_processes))
  registry = get_registry()
  if process_id == 0:
    artifact = trainer.bind_train_step(bind_features, bind_labels)
    assert artifact is not None and not artifact.from_cache, (
        'host 0 must compile + persist the shared executable', artifact)
  multihost_utils.sync_global_devices('artifact_persisted')
  if process_id != 0:
    # Eager PRNG seeding compiles two tiny programs of its own on first
    # use; they are process start-up, identical with or without a store,
    # and kept out of the window that counts the STEP's compiles.
    jax.random.PRNGKey(0)
    compiles_before = float(registry.scalars().get('jax/compiles', 0.0))
    artifact = trainer.bind_train_step(bind_features, bind_labels)
    compiles_delta = float(
        registry.scalars().get('jax/compiles', 0.0)) - compiles_before
    assert artifact is not None and artifact.from_cache, (
        'follower must deserialize host 0\'s persisted executable',
        artifact)
    assert compiles_delta == 0.0, (
        'follower bind must not compile: jax/compiles delta %r'
        % compiles_delta)
  multihost_utils.sync_global_devices('artifact_bound')
  # Per-host file shards come from the process-aware train() defaults.
  state = trainer.train(generator, max_train_steps=train_steps)
  assert int(jax.device_get(state.step)) == train_steps

  # Fleet observatory: this process wrote ITS stream (indexed, stamped)…
  from tensor2robot_tpu.observability import fleet as fleet_lib
  from tensor2robot_tpu.observability import telemetry_file

  own_stream = os.path.join(
      model_dir, 'telemetry.{}.jsonl'.format(process_id))
  assert os.path.exists(own_stream), own_stream
  own_records = telemetry_file.read_telemetry(own_stream)
  assert own_records and all(
      r.get('process_index') == process_id and
      r.get('process_count') == num_processes for r in own_records), (
          'per-host records missing their identity stamp')
  multihost_utils.sync_global_devices('telemetry_written')
  # …and host 0 federates every host's stream into one fleet view.
  if process_id == 0:
    fleet = fleet_lib.read_fleet(model_dir)
    assert sorted(fleet['hosts']) == list(range(num_processes)), (
        sorted(fleet['hosts']), num_processes)
    summary = fleet_lib.fleet_summary(model_dir)
    assert summary['host_count'] == num_processes, summary

  # Params must agree across hosts (the gradient psum is global).
  flat = jax.tree_util.tree_leaves(jax.device_get(state.params))
  checksum = np.asarray([float(np.sum(np.abs(leaf))) for leaf in flat],
                        np.float32)
  all_sums = np.asarray(multihost_utils.process_allgather(checksum))
  assert np.allclose(all_sums, all_sums[0], rtol=1e-6), all_sums
  trainer.close()

  # Restore the cooperatively-written checkpoint in a fresh Trainer and
  # compare to the live state (init_state restores when a checkpoint
  # exists; all hosts participate in the sharded Orbax restore).
  generator.set_specification_from_model(model, 'train')
  features, labels = next(generator.create_dataset_iterator(
      mode='train', shard_index=process_id, num_shards=num_processes))
  trainer2 = Trainer(model, model_dir, mesh=mesh, async_checkpoints=False,
                     save_checkpoints_steps=10**9, log_every_n_steps=10**9)
  restored = trainer2.init_state(features, labels)
  assert int(jax.device_get(restored.step)) == train_steps
  r_flat = jax.tree_util.tree_leaves(jax.device_get(restored.params))
  for a, b in zip(flat, r_flat):
    np.testing.assert_allclose(a, b, rtol=1e-6)
  trainer2.close()
  multihost_utils.sync_global_devices('done')

  marker = os.path.join(workdir, 'ok_{}'.format(process_id))
  with open(marker, 'w') as f:
    f.write('multihost dryrun ok: {} hosts x {} devices\n'.format(
        num_processes, n_local))


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--workdir', required=True)
  parser.add_argument('--coordinator', default='localhost:9456')
  parser.add_argument('--num_processes', type=int, default=2)
  parser.add_argument('--process_id', type=int, required=True)
  parser.add_argument('--local_device_count', type=int, default=4)
  parser.add_argument('--train_steps', type=int, default=2)
  args = parser.parse_args(argv)
  initialize(args.coordinator, args.num_processes, args.process_id,
             args.local_device_count)
  multihost_dryrun(args.workdir, args.num_processes, args.process_id,
                   args.train_steps)


if __name__ == '__main__':
  main()
