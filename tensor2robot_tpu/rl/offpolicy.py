"""Off-policy QT-Opt training: Bellman backups against a lagged
filesystem target network.

The reference trains its critics supervised on pre-labeled targets; the
Bellman backup lived in a separate updater service feeding the replay
buffer, with the TARGET network decoupled from the live one through the
filesystem — the lagged-export contract of
/root/reference/hooks/checkpoint_hooks.py:96-206 (a one-version-behind
export dir) consumed by whatever computes targets. This module closes
that loop in-process, TPU-first:

  * The **target network** is the newest version in the LAGGED export dir
    maintained by ``LaggedCheckpointExportHook`` — weights exactly one
    export interval behind the live critic, discovered by polling the
    filesystem like any robot-side consumer (same contract, same atomic
    version dirs). ``refresh_target`` reloads only when a new version has
    committed, so the target updates in discrete steps the way TD3/QT-Opt
    target networks do.
  * The **Bellman labels** ``y = r + gamma * (1 - done) * max_a' Q_t(s', a')``
    are computed INSIDE the jitted train step: the candidate-action max
    rides the critic's CEM megabatch contract
    (/root/reference/models/critic_model.py:128-141 — one batched forward
    scores B*K (state, action) pairs), so the backup costs one fused
    forward on the MXU, not a host-side loop.
  * Timeout transitions should be written with ``done=0`` (bootstrap
    through time limits); only genuine terminals (grasp attempted) carry
    ``done=1``. See research/qtopt/grasping_sim.py.

The target forward defaults to batch-statistics mode (TRAIN-mode BN,
state untouched): early in training the running stats a PREDICT forward
would use are cold, and bootstrapped targets computed through them are
systematically wrong for thousands of steps.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.export import export_generators
from tensor2robot_tpu.hooks.checkpoint_hooks import LaggedCheckpointExportHook
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.specs.struct import SpecStruct

DONE_KEY = 'done'
NEXT_PREFIX = 'next/'


def strip_offpolicy_features(features):
  """Drops the off-policy extras (``done``, ``next/*``) from a features
  mapping — the critic-spec subset used for init_state and the inner
  supervised step. The ONE owner of the key convention alongside
  :func:`split_offpolicy_batch`."""
  return {key: features[key] for key in features
          if key != DONE_KEY and not key.startswith(NEXT_PREFIX)}


def split_offpolicy_batch(features):
  """Splits loader features into (train_features, next_features, done).

  The replay records carry the critic's own in-spec keys plus the
  off-policy extras: ``next/<state-key>`` mirrors of every state feature
  and a scalar ``done``. The critic's train step must only see its own
  spec (the preprocessor validates), so the extras are split off here;
  ``next/`` keys are renamed back to their state names so the next-state
  struct IS a valid (partial) critic input.
  """
  train_features, next_features = {}, {}
  done = None
  for key in features:
    if key == DONE_KEY:
      done = jnp.asarray(features[key], jnp.float32)
    elif key.startswith(NEXT_PREFIX):
      next_features[key[len(NEXT_PREFIX):]] = features[key]
    else:
      train_features[key] = features[key]
  if done is None:
    raise ValueError("off-policy batches need a '{}' feature.".format(
        DONE_KEY))
  return train_features, next_features, done


class BellmanQTOptTrainer:
  """Critic training loop with filesystem-lagged Bellman targets.

  Args:
    model: a ``CriticModel``; its reward label becomes the Bellman target.
    trainer: the harness ``Trainer`` wrapping ``model``.
    candidate_actions_fn:
      ``(rng, batch_size, next_features) -> {action-key: [B*K, ...]}``
      flat candidate ACTION features for the target max, grouped per
      state in contiguous blocks (the megabatch layout: row b*K+j is
      state b's j-th candidate). K is fixed by the function. Action-spec
      keys that carry next-STATE status (e.g. Grasping44's
      gripper_closed) are read from ``next_features`` and repeated K
      times per state.
    num_candidates: K, the candidates per state.
    gamma: discount.
    target_update_steps: export (and therefore target-refresh) interval.
    target_forward_mode: mode for the target Q forward; TRAIN (default)
      uses batch statistics (see module docstring), EVAL/PREDICT use
      running stats.
    exports_to_keep: version retention in both export dirs.
  """

  def __init__(self,
               model,
               trainer,
               candidate_actions_fn: Callable,
               num_candidates: int,
               gamma: float = 0.9,
               target_update_steps: int = 20,
               target_forward_mode: str = ModeKeys.TRAIN,
               exports_to_keep: int = 3):
    self.model = model
    self.trainer = trainer
    self.gamma = float(gamma)
    self.num_candidates = int(num_candidates)
    self.target_update_steps = int(target_update_steps)
    self._candidate_actions_fn = candidate_actions_fn
    self._target_forward_mode = target_forward_mode
    self.export_dir = os.path.join(trainer.model_dir, 'export',
                                   'latest_exporter')
    self.lagged_export_dir = os.path.join(trainer.model_dir, 'export',
                                          'lagged_exporter')
    # Raw receivers: the artifact's declared in-spec is the MODEL spec
    # (fixed shapes) rather than a device-decode wrapper's dynamic sparse
    # in-spec; the in-process target consumer never feeds the artifact.
    self._hook = LaggedCheckpointExportHook(
        self.export_dir,
        self.lagged_export_dir,
        export_every_steps=self.target_update_steps,
        exports_to_keep=exports_to_keep,
        export_generator=export_generators.VariablesExportGenerator(
            export_raw_receivers=True))
    self.target_variables = None
    self.target_version: Optional[int] = None
    self._step_fn = None
    self._host_step: Optional[int] = None  # mirrors state.step, host-side
    # Sparse-coef pipelines: the trainer's feed only knows the model's
    # own image keys; replay batches additionally carry the next-state
    # mirrors, which must be unpacked to dense coefficients BEFORE the
    # jitted step too (bucketed sparse shapes would recompile it).
    self._feed = None
    from tensor2robot_tpu.data.device_feed import SparseCoefFeed
    base_feed = SparseCoefFeed.from_preprocessor(model.preprocessor,
                                                 trainer.mesh)
    if base_feed is not None:
      shapes = dict(base_feed._shapes)
      shapes.update({NEXT_PREFIX + key: value
                     for key, value in base_feed._shapes.items()})
      self._feed = SparseCoefFeed(shapes, mesh=trainer.mesh)

  # -- target-network lifecycle ---------------------------------------------

  def seed_target(self, state) -> None:
    """Exports the current (usually init) weights so a target exists.

    The first export also seeds the lagged dir (the hook's initial-copy
    behavior, ref checkpoint_hooks.py:96), so training can start with a
    well-defined target = init params.
    """
    self._hook._export(self.trainer, state)
    if not self.refresh_target():
      raise RuntimeError('seeding the lagged export dir failed '
                         '({}).'.format(self.lagged_export_dir))

  def refresh_target(self) -> bool:
    """Reloads target weights if a NEW lagged version has committed."""
    versions = export_generators.list_exported_versions(
        self.lagged_export_dir)
    if not versions or versions[-1] == self.target_version:
      return False
    version_dir = os.path.join(self.lagged_export_dir, str(versions[-1]))
    variables = export_generators.load_exported_variables(version_dir)
    self.target_variables = jax.device_put(
        jax.tree.map(jnp.asarray, variables))
    self.target_version = versions[-1]
    return True

  def after_step(self, state, step: int) -> None:
    """Export on the interval, then pick up whatever newly lagged."""
    self._hook.after_step(self.trainer, state, step, None)
    self.refresh_target()

  # -- the jitted Bellman step ----------------------------------------------

  def bellman_targets(self, target_variables, next_features, reward, done,
                      rng):
    """y = r + gamma * (1 - done) * max over K candidate actions.

    Traced inside the combined step. ``next_features`` are the raw
    (loader-shaped) next-STATE features under their state keys; candidate
    ACTION features are sampled here, and the critic's own preprocessor +
    state tiling produce the megabatch the target network scores.
    """
    model = self.model
    batch = jnp.asarray(reward).shape[0]
    rng_c, _ = jax.random.split(jnp.asarray(rng))
    candidates = self._candidate_actions_fn(rng_c, batch, next_features)
    # Candidates own ALL action keys; next_features contributes the state.
    state_feats = {key: value for key, value in next_features.items()
                   if not key.startswith('action/')}
    feats = SpecStruct(**dict(state_feats, **candidates))
    feats, _ = model.preprocessor.preprocess(feats, None, ModeKeys.PREDICT,
                                             rng=None)
    feats = model.tile_state_for_action_batch(feats)
    outputs, _ = model.inference_network_fn(
        target_variables, feats, None, self._target_forward_mode, None)
    q = jnp.asarray(outputs[model.q_key]).reshape(batch,
                                                  self.num_candidates)
    max_q = jnp.max(q, axis=-1)
    done = jnp.asarray(done, jnp.float32).reshape(batch)
    reward = jnp.asarray(reward, jnp.float32).reshape(batch)
    return reward + self.gamma * (1.0 - done) * max_q

  def compile_step(self):
    """jit (state, target_vars, features, labels, rng) -> (state, metrics).

    ``features`` is the full off-policy batch (critic keys + next/ +
    done); ``labels['reward']`` is the immediate reward from the replay.
    The inner supervised step is the trainer's own compiled step, inlined
    into this trace, so sharding/donation semantics match plain training.
    """
    if self._step_fn is not None:
      return self._step_fn
    inner_step = self.trainer._compile_train_step()

    def step(state, target_variables, features, labels, base_rng):
      rng = jax.random.fold_in(jnp.asarray(base_rng), state.step)
      rng_bellman, rng_train = jax.random.split(rng)
      train_features, next_features, done = split_offpolicy_batch(features)
      y = self.bellman_targets(target_variables, next_features,
                               labels['reward'], done, rng_bellman)
      y = jax.lax.stop_gradient(y)
      new_state, metrics = inner_step(state, train_features,
                                      {'reward': y[:, None]}, rng_train)
      metrics = dict(metrics)
      metrics['bellman_target_mean'] = jnp.mean(y)
      metrics['done_fraction'] = jnp.mean(done)
      return new_state, metrics

    self._step_fn = jax.jit(step, donate_argnums=(0,))
    return self._step_fn

  def train_step(self, state, host_batch, rng):
    """One off-policy step from a host batch; drives export + refresh.

    ``host_batch``: {'features': ..., 'labels': ...} dict from the
    record stream (sparse coef groups are unpacked by the trainer feed).
    The step counter is mirrored host-side (synced from the device once,
    then incremented locally) so off-interval steps pay neither a device
    sync nor the export-dir poll — the trainer's no-host-round-trip-per-
    step discipline (train_eval.py _compile_train_step).
    """
    if self.target_variables is None:
      self.seed_target(state)
    if self._host_step is None:
      self._host_step = int(jax.device_get(state.step))
    if self._feed is not None:
      batch = self._feed.put_batch(host_batch)
    else:
      batch = self.trainer._put_batch(host_batch)
    step_fn = self.compile_step()
    state, metrics = step_fn(state, self.target_variables,
                             batch['features'], batch['labels'], rng)
    self._host_step += 1
    if self._host_step % self.target_update_steps == 0:
      self.after_step(state, self._host_step)
    return state, metrics

  def close(self) -> None:
    self.trainer.close()


def concat_ranking_pairs(pairs):
  """Concatenates every arm of (better, worse) pairs into ONE batch.

  Returns ``(combined, arm_rows)``: a single feature dict with all arms
  stacked along the batch dim in pair order (better0, worse0, better1,
  worse1, ...), and the per-arm row counts needed to split scores back
  out. Callers that evaluate on-device repeatedly concatenate
  once, ``device_put`` the combined batch, and score each eval with
  :func:`ranking_accuracy_from_scores`.
  """
  arms = [arm for pair in pairs for arm in pair]
  if not arms:
    return {}, []
  keys = list(arms[0])
  combined = {
      k: np.concatenate([np.asarray(arm[k]) for arm in arms])
      for k in keys
  }
  first = keys[0]
  arm_rows = [int(np.asarray(arm[first]).shape[0]) for arm in arms]
  return combined, arm_rows


def ranking_accuracy_from_scores(scores, arm_rows) -> float:
  """Fraction ranked correctly, from one score vector over all arms.

  ``scores``: [sum(arm_rows)] critic outputs for a batch built by
  :func:`concat_ranking_pairs`; consecutive (better, worse) arm slices
  are compared elementwise.
  """
  scores = np.asarray(scores).ravel()
  if scores.size != sum(arm_rows):
    raise ValueError(
        'Got {} scores for arms totalling {} rows — q_fn must return one '
        'score per row.'.format(scores.size, sum(arm_rows)))
  correct = total = 0
  offset = 0
  for i in range(0, len(arm_rows), 2):
    rows_better, rows_worse = arm_rows[i], arm_rows[i + 1]
    if rows_better != rows_worse:
      raise ValueError(
          'Pair {} has mismatched arm sizes {} vs {}.'.format(
              i // 2, rows_better, rows_worse))
    better = scores[offset:offset + rows_better]
    worse = scores[offset + rows_better:offset + rows_better + rows_worse]
    correct += int((better > worse).sum())
    total += rows_better
    offset += rows_better + rows_worse
  return correct / max(total, 1)


def pairwise_ranking_accuracy(q_fn, pairs) -> float:
  """Fraction of (features_better, features_worse) pairs ranked correctly.

  The convergence criterion for analytic-MDP benchmarks: each pair holds
  two (state, action) feature dicts whose ground-truth Q* ordering is
  known with margin; ``q_fn(features) -> [B]`` is the live critic.

  Both arms of every pair are evaluated in ONE concatenated forward — by
  construction, not by caller discipline. A per-arm forward would be
  wrong for critics normalized with batch statistics: batch-stat BN
  removes any feature that is constant within a forward batch, and each
  arm of a ranking pair holds a constant action column — exactly the
  signal being measured (the round-5 debugging find; regression-tested
  in tests/test_offpolicy.py TestRankingAccuracyBatchStats).
  """
  combined, arm_rows = concat_ranking_pairs(pairs)
  if not arm_rows:
    return 0.0
  return ranking_accuracy_from_scores(q_fn(combined), arm_rows)
