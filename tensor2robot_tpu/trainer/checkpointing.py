"""Checkpoint management: async Orbax save/restore with the reference's
retention and warm-start semantics.

Parity targets:
  * TF1 Saver registered in SAVERS + keep policy
    (/root/reference/models/abstract_model.py:782-793,:84-85)
  * async checkpointing via AsyncCheckpointSaverHook
    (/root/reference/hooks/async_export_hook_builder.py:128)
  * warm start / partial restore from a foreign checkpoint
    (/root/reference/models/abstract_model.py:88-118,:372-381)
  * eval-vs-GC race protection by snapshotting checkpoints
    (/root/reference/utils/train_eval.py:599-667)
  * continuous-eval checkpoints_iterator (/root/reference/utils/train_eval.py:570)

Orbax gives us atomic directory commits, so the reference's tmp-file
detection heuristics collapse to "is the step committed"; the polling
loops survive because robot-side consumers still discover checkpoints by
watching the filesystem (SURVEY.md §2.9 'filesystem as transport').
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import numpy as np
import orbax.checkpoint as ocp

from tensor2robot_tpu.observability import span
from tensor2robot_tpu.reliability import fault_injection
from tensor2robot_tpu.reliability.errors import CorruptCheckpointError
from tensor2robot_tpu.reliability.logutil import log_warning as _log
from tensor2robot_tpu.reliability.retry import RetryPolicy, retry

CHECKPOINT_SUBDIR = 'checkpoints'

# Shared default for checkpoint I/O: 3 attempts, ~0.05/0.1s backoff. Kept
# short — checkpoint saves sit on the training hot loop, and a filesystem
# that stays down for longer than this should fail the run (RetryError)
# rather than stall it silently.
DEFAULT_CKPT_RETRY = RetryPolicy(max_attempts=3, base_delay_secs=0.05)

# Version of the in-checkpoint parameter LAYOUT (not the tree structure).
# Layout changes are shape-compatible but numerically incompatible — a
# silent restore would produce scrambled math — so the version is written
# next to the checkpoints and verified on restore. History:
#   2: transformer qkv columns head-major ([H, 3, Dh] groups, was
#      q|k|v-major) and pipelined pipe_blocks leaves [S, k, ...] (was
#      [L, ...]); layers/transformer.py round 4.
PARAM_LAYOUT_VERSION = 2
_FORMAT_FILENAME = 'format.json'


class CheckpointManager:
  """Thin wrapper over ocp.CheckpointManager for TrainState pytrees."""

  def __init__(self,
               model_dir: str,
               keep_checkpoint_max: int = 5,
               save_interval_steps: int = 1,
               async_checkpoints: bool = True,
               best_fn: Optional[Callable[[Any], float]] = None,
               best_mode: str = 'min',
               assume_param_layout: Optional[int] = None,
               retry_policy: Optional[RetryPolicy] = None,
               quarantine_damaged: bool = True):
    """Args mirror the reference's gin-exposed Saver/RunConfig knobs.

    Args:
      model_dir: root run directory; checkpoints live in
        ``<model_dir>/checkpoints``.
      keep_checkpoint_max: retention count (ref abstract_model.py:84).
      save_interval_steps: dedupe interval enforced by orbax.
      async_checkpoints: background commit thread — the
        AsyncCheckpointSaverHook equivalent.
      best_fn: optional metrics -> scalar for best-checkpoint retention.
      best_mode: 'min' | 'max'.
      assume_param_layout: the user's explicit assertion of the LAYOUT
        version of pre-marker checkpoints in this directory (the marker
        only exists from round 5 on, so an unmarked directory is
        ambiguous between the current layout and older ones). Passing
        the current ``PARAM_LAYOUT_VERSION`` stamps the marker and lets
        the run resume; any other value (or None, the default) keeps
        the loud failure.
      retry_policy: backoff policy for transient save/restore failures
        (flaky NFS/GCS); None uses DEFAULT_CKPT_RETRY. Non-transient
        errors (layout mismatch, bad template) propagate immediately.
      quarantine_damaged: rename visibly damaged step dirs aside
        (``<step>.corrupt``) when a restore trips over them. Only the
        manager that OWNS the directory (the trainer's) should do this;
        read-only consumers (predictors, warm starts) pass False so a
        polling reader never mutates a live training directory.
    """
    self._assume_param_layout = assume_param_layout
    self._retry_policy = retry_policy or DEFAULT_CKPT_RETRY
    self._quarantine_damaged = quarantine_damaged
    self._keep_checkpoint_max = keep_checkpoint_max
    self.directory = os.path.join(model_dir, CHECKPOINT_SUBDIR)
    self._options = ocp.CheckpointManagerOptions(
        max_to_keep=keep_checkpoint_max,
        save_interval_steps=save_interval_steps,
        enable_async_checkpointing=async_checkpoints,
        best_fn=best_fn,
        best_mode=best_mode,
        create=True,
    )
    self._manager = ocp.CheckpointManager(self.directory,
                                          options=self._options)

  def save(self, step: int, state, metrics: Optional[dict] = None,
           force: bool = False) -> bool:
    # Marker I/O hits the same flaky mount as the checkpoint itself:
    # retry it too. (Its deterministic ValueErrors are not retryable and
    # pass straight through.)
    retry(self._write_format_marker, self._retry_policy,
          site=fault_injection.SITE_CKPT_SAVE)

    def _save():
      fault_injection.maybe_fail(fault_injection.SITE_CKPT_SAVE)
      return self._manager.save(
          int(step), args=ocp.args.StandardSave(state), metrics=metrics,
          force=force)

    # The span holds only the SYNCHRONOUS portion; with async
    # checkpointing the background commit is invisible here (the trainer
    # sees it at wait_until_finished).
    with span('ckpt.save', step=int(step)):
      return retry(_save, self._retry_policy,
                   site=fault_injection.SITE_CKPT_SAVE)

  def restore(self, state_template, step: Optional[int] = None):
    """Restores into the structure/shardings of ``state_template``.

    ``state_template`` may be a concrete pytree or one of
    ``jax.ShapeDtypeStruct`` leaves (from ``jax.eval_shape``). None —
    the form foreign readers use (predictors, warm starts) — returns the
    saved tree whole on this process's first local device: a reader's
    devices are not the writer's, and Orbax's own default (the shardings
    that SAVED it) fails wherever they differ, e.g. a four-chip run's
    checkpoint served from one chip.
    """
    if step is None:
      step = self.latest_step()
    if step is None:
      raise FileNotFoundError(
          'No checkpoint found in {}.'.format(self.directory))
    retry(self._check_format_marker, self._retry_policy,
          site=fault_injection.SITE_CKPT_RESTORE)

    def _restore():
      fault_injection.maybe_fail(fault_injection.SITE_CKPT_RESTORE)
      template = state_template
      if template is None:
        template = self._saved_tree_on_local_device(int(step))
      return self._manager.restore(
          int(step), args=ocp.args.StandardRestore(template))

    try:
      with span('ckpt.restore'):
        return retry(_restore, self._retry_policy,
                     site=fault_injection.SITE_CKPT_RESTORE)
    except (ValueError, KeyError) as e:
      # Orbax reports a half-written or GC-gutted step dir as assorted
      # ValueErrors ('Must provide args of type Composite...') — these
      # are non-retryable, so they arrive here after the FIRST attempt
      # (a damaged dir does not get better with backoff). When a step is
      # visibly damaged on disk, quarantine it (rename aside — a damaged
      # dir also poisons the manager's item-layout inference for EVERY
      # step) and reclassify as CorruptCheckpointError so skip layers
      # can ride it out; a ValueError with all checkpoints intact (bad
      # template, layout mismatch) stays fatal.
      damage = self._step_damage(int(step))
      if damage is not None:
        self._quarantine_damaged_step(int(step), damage)
        raise CorruptCheckpointError(self.directory, int(step),
                                     damage) from e
      damaged_other = []
      for s in self._on_disk_steps():
        other_damage = self._step_damage(s)
        if other_damage is not None:
          damaged_other.append((s, other_damage))
      if damaged_other:
        # The requested step is intact; a DIFFERENT damaged step poisoned
        # the manager's construction-time item-layout inference. Clean up
        # (owner only), then read the requested step directly, bypassing
        # the poisoned manager — an intact newest checkpoint must never
        # be skipped because an older one is damaged.
        for other, other_damage in damaged_other:
          self._quarantine_damaged_step(other, other_damage)
        try:
          return self._restore_step_direct(int(step), state_template)
        except Exception as direct_error:  # noqa: BLE001 — reclassified
          raise CorruptCheckpointError(
              self.directory, damaged_other[0][0],
              damaged_other[0][1] + ' (poisoned the restore of step {}; '
              'direct read also failed: {})'.format(
                  step, direct_error)) from e
      raise

  def _restore_step_direct(self, step: int, state_template):
    """Reads one step's 'default' item without the (poisoned) manager."""
    if state_template is None:
      state_template = self._saved_tree_on_local_device(step)
    item_dir = os.path.join(self.directory, str(step), 'default')
    checkpointer = ocp.StandardCheckpointer()
    try:
      return checkpointer.restore(item_dir, target=state_template)
    finally:
      checkpointer.close()

  def _saved_tree_on_local_device(self, step: int):
    """The tree saved at ``step`` as ShapeDtypeStructs placed on the
    first local device — the template of a template-less restore."""
    item_dir = os.path.join(self.directory, str(step), 'default')
    checkpointer = ocp.StandardCheckpointer()
    try:
      tree = checkpointer.metadata(item_dir).item_metadata.tree
    except (FileNotFoundError, AttributeError) as e:
      # A gutted or vanished step has no tree to describe. ValueError is
      # what restore() classifies against the on-disk damage, exactly as
      # it does for Orbax's own complaint about such a step.
      raise ValueError(
          'step {} holds no readable tree metadata'.format(step)) from e
    finally:
      checkpointer.close()
    sharding = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=sharding), tree)

  def _on_disk_steps(self):
    if not os.path.isdir(self.directory):
      return []
    return sorted(int(name) for name in os.listdir(self.directory)
                  if name.isdigit())

  def _step_damage(self, step: int) -> Optional[str]:
    """Describes visible on-disk damage for ``step``, or None if intact.

    Conservative on purpose: only conditions an atomically-committed orbax
    step can never exhibit (missing/empty dir, no _CHECKPOINT_METADATA)
    count as damage — they arise from retention GC or a crashed commit.
    """
    step_dir = os.path.join(self.directory, str(step))
    if not os.path.isdir(step_dir):
      return 'step directory missing'
    entries = os.listdir(step_dir)
    if not entries:
      return 'step directory empty'
    if '_CHECKPOINT_METADATA' not in entries:
      return 'checkpoint metadata missing'
    return None

  def _quarantine_damaged_step(self, step: int, damage: str) -> None:
    """Renames a damaged step dir aside and rebuilds the orbax manager.

    The rename (never a delete — the bytes stay for forensics) both stops
    pollers from rediscovering the broken step and un-poisons orbax's
    construction-time item-layout inference; the rebuild makes the fresh
    layout visible to this manager. No-op unless this manager owns the
    directory (``quarantine_damaged``) — a read-only consumer must not
    mutate a training run's files out from under the trainer.
    """
    if not self._quarantine_damaged:
      return
    src = os.path.join(self.directory, str(step))
    if os.path.isdir(src):
      dest = src + '.corrupt'
      suffix = 1
      while os.path.exists(dest):
        dest = '{}.corrupt{}'.format(src, suffix)
        suffix += 1
      try:
        os.replace(src, dest)
        _log('Quarantined damaged checkpoint step %d (%s): %s -> %s',
             step, damage, src, dest)
      except OSError as e:
        _log('Could not quarantine damaged checkpoint %s: %s', src, e)
        return
    try:
      self._manager.close()
    except Exception as e:  # noqa: BLE001 — already on the failure path
      _log('Closing poisoned checkpoint manager failed: %s', e)
    self._manager = ocp.CheckpointManager(self.directory,
                                          options=self._options)

  def _stamp_marker(self) -> None:
    path = os.path.join(self.directory, _FORMAT_FILENAME)
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
      json.dump({'param_layout_version': PARAM_LAYOUT_VERSION}, f)
    os.replace(tmp, path)

  def _unmarked_steps(self):
    if not os.path.isdir(self.directory):
      return []
    if os.path.exists(os.path.join(self.directory, _FORMAT_FILENAME)):
      return []
    return sorted(int(name) for name in os.listdir(self.directory)
                  if name.isdigit())

  def _write_format_marker(self) -> None:
    path = os.path.join(self.directory, _FORMAT_FILENAME)
    if os.path.exists(path):
      return
    # An UNMARKED directory with checkpoints is ambiguous: the marker
    # only exists from round 5 on, so those steps may be the current
    # layout (round-4 builds) or an older one. Stamping the current
    # version over them would let a later restore of old-layout params
    # pass silently — refuse unless the caller asserts the layout.
    existing = self._unmarked_steps()
    if existing and self._assume_param_layout != PARAM_LAYOUT_VERSION:
      raise ValueError(
          'Checkpoint dir {} holds pre-marker checkpoints (steps {}) of '
          'UNKNOWN param layout. If they were written by a build with '
          'layout version {} (head-major qkv, [S, k] pipe_blocks), pass '
          'CheckpointManager(..., assume_param_layout={}) to stamp the '
          'marker and resume; otherwise migrate or clear the directory.'
          .format(self.directory, existing[:5], PARAM_LAYOUT_VERSION,
                  PARAM_LAYOUT_VERSION))
    self._stamp_marker()

  def _check_format_marker(self) -> None:
    """Fail loudly on checkpoints with an older/unknown parameter layout.

    Shape-compatible layout changes (see PARAM_LAYOUT_VERSION) restore
    without error but scramble the numerics; the marker turns that into
    an actionable exception instead. ``assume_param_layout`` is the
    explicit escape hatch for pre-marker directories whose layout the
    user knows.
    """
    path = os.path.join(self.directory, _FORMAT_FILENAME)
    if not os.path.exists(path):
      if self._assume_param_layout == PARAM_LAYOUT_VERSION:
        self._stamp_marker()
        return
      raise ValueError(
          'Checkpoint dir {} has no {} marker: its param layout is '
          'unknown (the marker exists from round 5 on). If these '
          'checkpoints were written with layout version {} (head-major '
          'qkv columns, [S, k] pipe_blocks), pass '
          'CheckpointManager(..., assume_param_layout={}) to proceed; '
          'older-layout checkpoints restore shape-compatibly but '
          'numerically SCRAMBLED — re-train or migrate those.'
          .format(self.directory, _FORMAT_FILENAME, PARAM_LAYOUT_VERSION,
                  PARAM_LAYOUT_VERSION))
    with open(path) as f:
      version = json.load(f).get('param_layout_version')
    if version != PARAM_LAYOUT_VERSION:
      raise ValueError(
          'Checkpoint dir {} has param-layout version {} but this build '
          'expects {}; restoring would scramble parameters. Re-train or '
          'migrate.'.format(self.directory, version, PARAM_LAYOUT_VERSION))

  def latest_step(self) -> Optional[int]:
    return self._manager.latest_step()

  def reload(self) -> None:
    """Re-reads the step list from disk.

    Orbax caches the step list at construction; a concurrent trainer
    process writing checkpoints (the continuous-eval topology,
    ref train_eval.py:570) is invisible without this.
    """
    self._manager.reload()

  def all_steps(self) -> Sequence[int]:
    return sorted(self._manager.all_steps())

  def wait_until_finished(self) -> None:
    self._manager.wait_until_finished()

  def close(self) -> None:
    self._manager.close()

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    self.close()


def all_checkpoint_steps(model_dir: str) -> list:
  """All committed checkpoint steps under model_dir, newest first.

  Orbax commits atomically by renaming; a bare numeric dir is live
  (in-flight saves have an .orbax-checkpoint-tmp suffix and fail isdigit).
  """
  directory = os.path.join(model_dir, CHECKPOINT_SUBDIR)
  if not os.path.isdir(directory):
    return []
  return sorted((int(name) for name in os.listdir(directory)
                 if name.isdigit()), reverse=True)


def latest_checkpoint_step(model_dir: str) -> Optional[int]:
  """Newest committed checkpoint step under model_dir, or None."""
  steps = all_checkpoint_steps(model_dir)
  return steps[0] if steps else None


def checkpoints_iterator(model_dir: str,
                         timeout_secs: float = 600.0,
                         min_interval_secs: float = 1.0,
                         stop_fn: Optional[Callable[[], bool]] = None
                         ) -> Iterator[int]:
  """Yields new checkpoint steps as they appear (ref train_eval.py:570).

  Terminates when no new checkpoint arrives within ``timeout_secs`` or
  ``stop_fn`` returns True.
  """
  last_step = None
  # monotonic, not time.time(): a wall-clock jump (NTP step, DST) must not
  # spuriously expire — or indefinitely extend — the eval timeout.
  deadline = time.monotonic() + timeout_secs
  while True:
    if stop_fn is not None and stop_fn():
      return
    step = latest_checkpoint_step(model_dir)
    if step is not None and step != last_step:
      last_step = step
      deadline = time.monotonic() + timeout_secs
      yield step
      continue
    if time.monotonic() > deadline:
      return
    time.sleep(min_interval_secs)


# -- warm start -------------------------------------------------------------


def create_warm_start_fn(checkpoint_dir: str,
                         step: Optional[int] = None,
                         include: Optional[Callable[[str], bool]] = None):
  """Returns params -> params merging values restored from a foreign run.

  The JAX form of ``default_init_from_checkpoint_fn``'s partial restore
  (/root/reference/models/abstract_model.py:88-118): leaves present in the
  checkpoint under the same tree path (and passing ``include`` on the
  '/'-joined path) replace freshly-initialized values; everything else
  keeps its init. Shape mismatches are skipped, matching the reference's
  tolerance for evolving label spaces.
  """

  def warm_start(params):
    # Read-only against a foreign run's directory: never quarantine there.
    manager = CheckpointManager(checkpoint_dir, async_checkpoints=False,
                                quarantine_damaged=False)
    try:
      restore_step = step if step is not None else manager.latest_step()
      if restore_step is None:
        raise FileNotFoundError(
            'No checkpoint to warm start from in {}.'.format(checkpoint_dir))
      restored = manager.restore(None, step=restore_step)
    finally:
      manager.close()
    if isinstance(restored, dict) and 'params' in restored:
      restored = restored['params']

    flat_restored = _flatten_with_paths(restored)
    flat_params, treedef = jax.tree_util.tree_flatten_with_path(params)
    merged = []
    for path, value in flat_params:
      key = _path_str(path)
      candidate = flat_restored.get(key)
      if candidate is not None and (include is None or include(key)):
        if np.shape(candidate) == np.shape(value):
          value = jax.numpy.asarray(candidate, dtype=value.dtype)
      merged.append(value)
    return jax.tree_util.tree_unflatten(treedef, merged)

  return warm_start


def _path_str(path) -> str:
  parts = []
  for entry in path:
    if hasattr(entry, 'key'):
      parts.append(str(entry.key))
    elif hasattr(entry, 'idx'):
      parts.append(str(entry.idx))
    else:
      parts.append(str(entry))
  return '/'.join(parts)


def _flatten_with_paths(tree) -> dict:
  flat, _ = jax.tree_util.tree_flatten_with_path(tree)
  return {_path_str(path): value for path, value in flat}
