"""The plain evaluations that decide ``correct``.

The repo holds no independent ``jax.numpy`` implementation of these models
(PERF.md, Open questions), so the reference is the configuration's own
module applied plainly: no sharding on one chip, no donation, outside the
trainer's step. It is applied twice. Built a second time in float32 under
``default_matmul_precision('highest')`` it says what the loss is; as the
program builds it (bf16 on the TPU) it says what that precision makes of the
same batch. The step's loss is held to the second closely, and the second to
the first within what the precision costs, so that a fault of the trainer's
step (part of the batch left out, a wrong rng, a bad layout or donation) is
not hidden behind the room bf16 needs. A term the module's own ``loss_fn``
dropped would pass both.
"""

import numpy as np


def float32_model(model_spec):
  from benchmark.harness import common

  return common.build_model(model_spec, compute_dtype=np.float32)


def train_loss(ref_model, params, model_state, features, labels, base_rng,
               step, mesh, precision=None):
  """The loss ``Trainer``'s step must report for this batch at ``step``: the
  step's own rng derivation (fold the step in, split for the preprocessor
  and the network), then ``ref_model``'s preprocess and ``loss_fn``, with
  matrix products at ``precision`` (None: the backend's default).

  On one chip everything sits un-sharded on that chip. On a mesh of several
  the batch is laid over its ``data`` axis and the rest replicated: the
  un-sharded float32 forward of a global batch of 256 needs 16.5 GB of
  temporaries on one 16 GB chip (real-size compile, PR 24). The check then
  still fails a dropped loss term, lower precision, a wrong rng or a batch
  left out in part, but shares the partitioner with the step it checks."""
  import jax

  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.specs.struct import SpecStruct

  def loss_of(params, model_state, features, labels, base_rng, step):
    rng = jax.random.fold_in(base_rng, step)
    pre_rng, step_rng = jax.random.split(rng)
    features, labels = ref_model.preprocessor.preprocess(
        SpecStruct(**features),
        SpecStruct(**labels) if labels is not None else None,
        ModeKeys.TRAIN, rng=pre_rng)
    net_rng, _ = jax.random.split(step_rng)
    loss, _ = ref_model.loss_fn(params, model_state, features, labels,
                                ModeKeys.TRAIN, net_rng)
    return loss

  whole, split = layouts(mesh)
  args = (jax.device_put((params, model_state), whole) +
          jax.device_put((features, labels), split) +
          jax.device_put((base_rng, step), whole))
  with jax.default_matmul_precision(precision):
    return float(jax.jit(loss_of)(*args))


def layouts(mesh):
  """(replicated, batch) shardings: the batch over the ``data`` axis of a
  mesh of several chips, everything on the one chip of a mesh of one."""
  from jax.sharding import NamedSharding, PartitionSpec

  whole = NamedSharding(mesh, PartitionSpec())
  split = whole if mesh.size == 1 else NamedSharding(
      mesh, PartitionSpec('data'))
  return whole, split


def agree(got, want, rel, what):
  """(ok, message). ``rel`` is relative to |want|."""
  error = abs(got - want) / max(abs(want), 1e-12)
  ok = bool(np.isfinite(got)) and error <= rel
  return ok, '{}: {!r} against {!r}, relative error {:.3g} (tolerance ' \
      '{:g})'.format(what, got, want, error, rel)
