"""ExportedModelPredictor: serve from versioned export directories.

Parity target: /root/reference/predictors/exported_savedmodel_predictor.py:50-274.
Behaviors preserved:
  * poll the export root for the newest valid numeric version, skipping
    tmp-prefixed/partial dirs (:238-274), with a restore timeout (:120-148)
  * load feature/label specs from assets.extra/t2r_assets.pbtxt (:162-170)
  * global-step reconciliation from the artifact (:181-189)
  * retry on concurrent-write/GC races: a version vanishing mid-load falls
    back to the next-newest (:160-198)
  * serialized tf.Example receiver: ``predict_serialized`` parses record
    bytes with the spec-driven wire parser before the same feed

Two serving backends:
  * with a T2RModel: jitted preprocess+predict over restored variables
    (fresh XLA compile, fastest path on the serving host's own chip)
  * without any Python model code: the artifact's serialized StableHLO
    predict function (jax.export) — the SavedModel-like deployment mode
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional

import jax
import numpy as np

from tensor2robot_tpu.export import export_generators
from tensor2robot_tpu.observability import get_registry
from tensor2robot_tpu.predictors.abstract_predictor import AbstractPredictor
from tensor2robot_tpu.reliability.logutil import log_warning
from tensor2robot_tpu.specs import assets as assets_lib
from tensor2robot_tpu.specs.struct import SpecStruct  # predict_serialized

_POLL_INTERVAL_SECS = 1.0
_WAIT_REPORT_INTERVAL_SECS = 10.0
EXPORT_WAIT_GAUGE = 'inference/export_wait_seconds'


class _Loaded:
  """One loaded export version, swapped in as a single reference.

  The pre-PR-8 implementation assigned serve_fn / variables / specs /
  version as SEPARATE attributes; a predict racing a hot-swap could pair
  the new serve function with the old variables (or parse request bytes
  with the old spec and feed the new weights) — a mixed-version result.
  Everything a serving call touches now rides one immutable snapshot
  (versioned-params contract, ISSUE 8; regression test in
  tests/test_predictors.py).
  """

  __slots__ = ('variables', 'exported_fn', 'serve_fn', 'raw_receivers',
               'feature_spec', 'label_spec', 'version', 'global_step',
               'model_path', 'parser')

  def __init__(self, variables, exported_fn, serve_fn, raw_receivers,
               feature_spec, label_spec, version, global_step, model_path):
    self.variables = variables
    self.exported_fn = exported_fn
    self.serve_fn = serve_fn
    self.raw_receivers = raw_receivers
    self.feature_spec = feature_spec
    self.label_spec = label_spec
    self.version = version
    self.global_step = global_step
    self.model_path = model_path
    # Derived lazily from THIS snapshot's spec on first
    # predict_serialized; racing builders construct equal parsers, so
    # last-write-wins is benign.
    self.parser = None


class ExportedModelPredictor(AbstractPredictor):
  """Serves the newest artifact under an export root directory."""

  def __init__(self,
               export_dir: str,
               t2r_model=None,
               timeout: float = 600.0):
    """Args:
      export_dir: the versioned export root (e.g.
        <model_dir>/export/latest_exporter).
      t2r_model: optional model for the recompile backend; None uses the
        artifact's serialized predict function.
      timeout: restore() polling budget in seconds (ref :57 — 600s).
    """
    self._export_dir = export_dir
    self._model = t2r_model
    self._timeout = timeout
    self._loaded: Optional[_Loaded] = None

  # -- restore ---------------------------------------------------------------

  def _try_load_version(self, version: int) -> bool:
    version_dir = os.path.join(self._export_dir, str(version))
    try:
      exported_fn = None
      if self._model is None:
        # Fail fast BEFORE the expensive variables restore: artifacts
        # whose serialization fell back to None can never serve model-less.
        fn_path = os.path.join(version_dir,
                               export_generators.PREDICT_FN_FILENAME)
        from jax import export as jax_export

        with open(fn_path, 'rb') as f:
          exported_fn = jax_export.deserialize(f.read())
      feature_spec, label_spec, step = assets_lib.load_t2r_assets_from_file(
          os.path.join(version_dir, assets_lib.EXTRA_ASSETS_DIRECTORY,
                       assets_lib.T2R_ASSETS_FILENAME))
      variables = export_generators.load_exported_variables(version_dir)
    except (OSError, ValueError, FileNotFoundError):
      return False  # racing GC/partial write: caller falls back
    raw = bool(export_generators.load_serving_config(version_dir)
               .get('raw_receivers', False))
    previous = self._loaded
    serve_fn = None
    if self._model is not None:
      if previous is not None and previous.serve_fn is not None \
          and raw == previous.raw_receivers:
        serve_fn = previous.serve_fn  # same receiver mode: keep the jit
      else:
        # Honor the artifact's receiver mode: raw artifacts must NOT be
        # preprocessed again (ref abstract_export_generator.py:52).
        serve_fn = jax.jit(
            export_generators.make_serve_fn(self._model, raw_receivers=raw))
    if step is None:
      try:
        step = assets_lib.load_global_step_from_file(version_dir)
      except (OSError, ValueError):
        step = 0
    # The snapshot is fully built BEFORE the one reference assignment: a
    # concurrent predict sees either all of the old version or all of
    # the new one.
    self._loaded = _Loaded(
        variables=variables, exported_fn=exported_fn, serve_fn=serve_fn,
        raw_receivers=raw, feature_spec=feature_spec, label_spec=label_spec,
        version=version, global_step=int(step or 0), model_path=version_dir)
    return True

  def restore(self) -> bool:
    """Polls for a version newer than the current one (ref :120-148)."""
    # monotonic (matching CheckpointPredictor): a wall-clock jump must
    # not expire or extend the polling budget.
    wait_start = time.monotonic()
    deadline = wait_start + self._timeout
    next_report = wait_start + _WAIT_REPORT_INTERVAL_SECS
    # Labeled per export root: concurrent predictors must not clobber
    # each other's wait signal (see CheckpointPredictor.restore).
    wait_gauge = get_registry().gauge_family(
        EXPORT_WAIT_GAUGE, ('dir',)).series(self._export_dir)
    try:
      while True:
        versions = export_generators.list_exported_versions(self._export_dir)
        loaded = self._loaded
        fresh = [v for v in versions
                 if loaded is None or v > loaded.version]
        # Newest first; a vanished/partial dir falls back to the next one
        # (ref :160-198 retry semantics).
        for version in reversed(fresh):
          if self._try_load_version(version):
            return True
        if loaded is not None and versions:
          return True  # current version still newest and valid
        now = time.monotonic()
        if now >= next_report:
          elapsed = now - wait_start
          wait_gauge.set(elapsed)
          log_warning(
              'ExportedModelPredictor: still waiting for an export in %s '
              '(%.0fs elapsed, %.0fs until timeout).', self._export_dir,
              elapsed, max(deadline - now, 0.0))
          next_report = now + _WAIT_REPORT_INTERVAL_SECS
        if now > deadline:
          return False
        time.sleep(_POLL_INTERVAL_SECS)
    finally:
      wait_gauge.set(0.0)

  # -- serving ---------------------------------------------------------------

  def _loaded_snapshot(self) -> _Loaded:
    loaded = self._loaded  # ONE read; restore() swaps the whole reference
    if loaded is None:
      raise ValueError('The predictor has not been restored yet.')
    return loaded

  @property
  def variables(self):
    """The restored variables pytree (for custom jitted serving paths,
    e.g. DeviceCEMPolicy's one-dispatch CEM — checkpoint_predictor parity)."""
    return self._loaded_snapshot().variables

  @property
  def versioned_variables(self):
    """``(version, variables)`` from one atomic snapshot read — what a
    serving hot-swap consumes (PolicyServer.swap_from_predictor)."""
    loaded = self._loaded_snapshot()
    return loaded.version, loaded.variables

  @staticmethod
  def _predict_from(loaded: _Loaded, features: Dict[str, np.ndarray]
                    ) -> Dict[str, np.ndarray]:
    if loaded.serve_fn is not None:
      outputs = loaded.serve_fn(loaded.variables, dict(features))
    else:
      outputs = loaded.exported_fn.call(loaded.variables, dict(features))
    return {k: np.asarray(v) for k, v in jax.device_get(outputs).items()}

  def predict_versioned(self, features: Dict[str, np.ndarray]):
    loaded = self._loaded_snapshot()
    return self._predict_from(loaded, features), loaded.version

  def predict(self, features: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    return self.predict_versioned(features)[0]

  def predict_serialized(self, records) -> Dict[str, np.ndarray]:
    """tf.Example receiver: record bytes -> parse by spec -> predict.

    ref default_export_generator.py:104-138 (the tf_example receiver).
    The parser, spec, and weights all come from ONE snapshot — request
    bytes can never be parsed with one version's spec and scored with
    another's weights.
    """
    loaded = self._loaded_snapshot()
    if loaded.parser is None:
      from tensor2robot_tpu.data.parser import ExampleParser  # lazy: serving
      loaded.parser = ExampleParser(loaded.feature_spec, SpecStruct())
    if isinstance(records, bytes):
      records = [records]
    features, _ = loaded.parser.parse_batch(records)
    return self._predict_from(loaded, features.to_dict())

  def get_feature_specification(self):
    return self._loaded_snapshot().feature_spec

  def get_label_specification(self):
    return self._loaded_snapshot().label_spec

  @property
  def is_loaded(self) -> bool:
    return self._loaded is not None

  @property
  def model_version(self) -> int:
    loaded = self._loaded
    return loaded.version if loaded is not None else 0

  @property
  def global_step(self) -> int:
    loaded = self._loaded
    return loaded.global_step if loaded is not None else 0

  @property
  def model_path(self) -> str:
    loaded = self._loaded
    return loaded.model_path if loaded is not None else ''

  def close(self) -> None:
    # Dropping the snapshot also resets version tracking: a closed
    # predictor must not short-circuit a later restore() into "current
    # version still newest and valid" while holding no loaded state.
    self._loaded = None
