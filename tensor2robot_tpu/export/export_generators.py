"""Export generators: write versioned, self-describing serving artifacts.

Parity targets:
  * AbstractExportGenerator  /root/reference/export_generators/abstract_export_generator.py:43
  * DefaultExportGenerator   /root/reference/export_generators/default_export_generator.py:47-138
  * t2r_assets in assets.extra  /root/reference/utils/train_eval.py:296-370

TPU-native redesign. The reference exports TF1 SavedModels whose graph bakes
in placeholders + preprocessing; robot-side predictors reload them with a
session. Here the serving artifact is:

    <export_root>/<version>/            (numeric version, ATOMICALLY renamed
      variables/                         from a tmp- prefix, so pollers never
        ...orbax checkpoint...           see partial exports — the reference's
      assets.extra/t2r_assets.pbtxt      tmp-dir filtering contract,
      assets.extra/t2r_assets.json       exported_savedmodel_predictor.py:238)
      global_step.txt
      predict_fn.jaxexport               (optional: serialized StableHLO of the
                                          full preprocess+forward predict step
                                          via jax.export — loadable WITHOUT the
                                          Python model class, the SavedModel
                                          analog)

``assets.extra/t2r_assets.pbtxt`` keeps the exact reference contract so any
tooling that reads specs from exports keeps working. The numpy receiver
semantics (feed a dict of arrays matching the preprocessor in-spec) live in
the predictor; the tf.Example receiver is the predictor parsing serialized
examples with the spec-driven wire parser before the same feed.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from jax import export as jax_export

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.specs import assets as assets_lib
from tensor2robot_tpu.specs import generators as spec_generators
from tensor2robot_tpu.specs.struct import SpecStruct

VARIABLES_SUBDIR = 'variables'
PREDICT_FN_FILENAME = 'predict_fn.jaxexport'
WARMUP_REQUESTS_FILENAME = 'warmup_requests.npz'
SERVING_CONFIG_FILENAME = 'serving_config.json'
_TMP_PREFIX = 'tmp-'


def state_from_variables(variables, step: int = 0):
  """Variables pytree (an artifact/checkpoint's content) -> TrainState.

  The shared inverse of ``TrainState.variables()``: 'params' and optional
  'avg_params' split out, everything else is model_state.
  """
  from tensor2robot_tpu.models.abstract_model import TrainState
  variables = dict(variables)
  params = variables.pop('params')
  avg_params = variables.pop('avg_params', None)
  return TrainState(step=np.asarray(step, np.int32), params=params,
                    model_state=variables, opt_state=None,
                    avg_params=avg_params, ema_state=None)


def make_serve_fn(model, raw_receivers: bool = False):
  """The ONE serving function: (variables, features) -> outputs dict.

  Used by the export serializer and both predictors so serving semantics
  (PREDICT-mode preprocessing unless ``raw_receivers``, action tiling and
  avg-params selection via ``model.predict_step``) are defined exactly once.
  """

  def serve(variables, features):
    state = state_from_variables(variables)
    features = SpecStruct(**features)
    if not raw_receivers:
      features, _ = model.preprocessor.preprocess(
          features, None, ModeKeys.PREDICT, rng=None)
    return dict(model.predict_step(state, features))

  return serve


def garbage_collect_versions(export_root: str, keep: int) -> None:
  """Deletes all but the newest ``keep`` committed versions."""
  import shutil
  for version in list_exported_versions(export_root)[:-keep or None]:
    shutil.rmtree(os.path.join(export_root, str(version)),
                  ignore_errors=True)


def _is_version_dir(name: str) -> bool:
  return name.isdigit()


def list_exported_versions(export_root: str) -> List[int]:
  """Committed (atomically renamed) numeric version dirs, ascending."""
  if not os.path.isdir(export_root):
    return []
  return sorted(int(name) for name in os.listdir(export_root)
                if _is_version_dir(name))


def next_version(export_root: str) -> int:
  """Monotonic wall-clock version, bumped past any existing dir."""
  version = int(time.time())
  existing = list_exported_versions(export_root)
  if existing and version <= existing[-1]:
    version = existing[-1] + 1
  return version


def write_serving_artifact(export_root: str,
                           variables: Any,
                           feature_spec,
                           label_spec,
                           global_step: int,
                           predict_fn_bytes: Optional[bytes] = None,
                           warmup_features: Optional[Dict[str, np.ndarray]] = None,
                           version: Optional[int] = None,
                           raw_receivers: bool = False) -> str:
  """Writes one versioned artifact; returns its committed path.

  The write happens under a ``tmp-`` prefix and is committed with a single
  ``os.rename`` so concurrent pollers only ever observe complete exports
  (ref exported_savedmodel_predictor.py:238-274 tmp filtering + retries).
  """
  if version is None:
    version = next_version(export_root)
  os.makedirs(export_root, exist_ok=True)
  final_dir = os.path.join(export_root, str(version))
  tmp_dir = os.path.join(export_root, _TMP_PREFIX + str(version))

  host_variables = jax.tree.map(np.asarray, jax.device_get(variables))
  checkpointer = ocp.StandardCheckpointer()
  try:
    checkpointer.save(os.path.join(tmp_dir, VARIABLES_SUBDIR), host_variables)
    checkpointer.wait_until_finished()
  finally:
    checkpointer.close()

  assets_lib.write_t2r_assets_to_file(
      feature_spec, label_spec, global_step,
      os.path.join(tmp_dir, assets_lib.EXTRA_ASSETS_DIRECTORY,
                   assets_lib.T2R_ASSETS_FILENAME))
  assets_lib.write_global_step_to_file(global_step, tmp_dir)
  if predict_fn_bytes is not None:
    with open(os.path.join(tmp_dir, PREDICT_FN_FILENAME), 'wb') as f:
      f.write(predict_fn_bytes)
  if warmup_features is not None:
    np.savez(os.path.join(tmp_dir, WARMUP_REQUESTS_FILENAME),
             **{k: np.asarray(v) for k, v in warmup_features.items()})
  import json
  with open(os.path.join(tmp_dir, SERVING_CONFIG_FILENAME), 'w') as f:
    json.dump({'raw_receivers': bool(raw_receivers)}, f)
  os.rename(tmp_dir, final_dir)
  return final_dir


def load_serving_config(version_dir: str) -> dict:
  import json
  try:
    with open(os.path.join(version_dir, SERVING_CONFIG_FILENAME)) as f:
      return json.load(f)
  except (OSError, ValueError):
    return {'raw_receivers': False}


def load_exported_variables(version_dir: str) -> Any:
  """Restores the raw variables pytree from one exported version."""
  checkpointer = ocp.StandardCheckpointer()
  try:
    return checkpointer.restore(os.path.join(version_dir, VARIABLES_SUBDIR))
  finally:
    checkpointer.close()


class AbstractExportGenerator:
  """Builds serving artifacts for a model (ref abstract_export_generator.py:43).

  ``export_raw_receivers`` mirrors the reference flag (:52): when True the
  artifact's declared in-spec is the MODEL's feature spec (client preprocesses);
  when False it is the PREPROCESSOR's in-spec and the exported predict function
  runs preprocessing in-graph.
  """

  def __init__(self, export_raw_receivers: bool = False):
    self._export_raw_receivers = export_raw_receivers
    self._model = None

  def set_specification_from_model(self, t2r_model) -> None:
    """ref abstract_export_generator.py:61 — binds specs (here: the model)."""
    self._model = t2r_model

  @property
  def model(self):
    if self._model is None:
      raise ValueError(
          'set_specification_from_model must be called before exporting.')
    return self._model

  def serving_feature_spec(self) -> SpecStruct:
    """The in-spec the serving client must feed."""
    if self._export_raw_receivers:
      return self.model.get_feature_specification_for_packing(ModeKeys.PREDICT)
    return self.model.preprocessor.get_in_feature_specification(
        ModeKeys.PREDICT)

  def create_serving_fn(self):
    """Pure (variables, features) -> outputs serving function."""
    return make_serve_fn(self.model, raw_receivers=self._export_raw_receivers)

  def serialize_predict_fn(self, variables, features) -> Optional[bytes]:
    """Best-effort StableHLO serialization of the serving function.

    Makes the artifact loadable with zero Python model code (the SavedModel
    property). The batch dimension is exported SYMBOLICALLY so the artifact
    serves any batch size (the reference's None-batch placeholders,
    default_export_generator.py:61). Returns None when the function cannot
    be lowered (e.g. host callbacks inside a custom model).
    """
    serve = self.create_serving_fn()
    variables_abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        variables)

    def _features_abstract(batch_dim):
      return {k: jax.ShapeDtypeStruct((batch_dim,) + np.shape(v)[1:],
                                      np.asarray(v).dtype)
              for k, v in features.items()}

    try:
      (batch_dim,) = jax_export.symbolic_shape('b')
      exported = jax_export.export(jax.jit(serve))(
          variables_abstract, _features_abstract(batch_dim))
      return exported.serialize()
    except Exception:  # pylint: disable=broad-except
      pass
    try:
      # Models that can't trace with a symbolic batch (e.g. fixed CEM
      # tiling) fall back to the warmup batch's concrete shape.
      exported = jax_export.export(jax.jit(serve))(
          variables_abstract,
          _features_abstract(int(np.shape(next(iter(features.values())))[0])))
      return exported.serialize()
    except Exception:  # pylint: disable=broad-except
      return None

  def export(self, export_root: str, variables, global_step: int,
             batch_size: int = 1, version: Optional[int] = None) -> str:
    """Writes one artifact for the current variables; returns its path."""
    feature_spec = self.serving_feature_spec()
    label_spec = self.model.get_label_specification(ModeKeys.PREDICT)
    warmup = spec_generators.make_random_numpy(
        feature_spec, batch_size=batch_size).to_dict()
    predict_fn_bytes = self.serialize_predict_fn(variables, warmup)
    return write_serving_artifact(
        export_root, variables, feature_spec, label_spec, global_step,
        predict_fn_bytes=predict_fn_bytes, warmup_features=warmup,
        version=version, raw_receivers=self._export_raw_receivers)


class DefaultExportGenerator(AbstractExportGenerator):
  """The standard generator (ref default_export_generator.py:47): in-graph
  preprocessing + numpy receiver semantics."""


class VariablesExportGenerator(AbstractExportGenerator):
  """Variables-only artifact: no StableHLO predict fn, no warmup batch.

  For high-frequency export consumers that are in-process and already hold
  the model class — the filesystem target-network loop (rl/offpolicy.py
  polls the lagged dir every few train steps; re-lowering the serving
  function per export would dominate the update interval). The artifact
  keeps the directory contract (specs, global step, atomic commit), minus
  ``predict_fn.jaxexport`` and ``warmup_requests.npz``.
  """

  def serialize_predict_fn(self, variables, features):
    del variables, features
    return None

  def export(self, export_root: str, variables, global_step: int,
             batch_size: int = 1, version: Optional[int] = None) -> str:
    del batch_size
    return write_serving_artifact(
        export_root, variables, self.serving_feature_spec(),
        self.model.get_label_specification(ModeKeys.PREDICT), global_step,
        version=version, raw_receivers=self._export_raw_receivers)
