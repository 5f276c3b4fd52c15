"""The training/eval harness: mesh-sharded jitted train loop.

Parity target: /root/reference/utils/train_eval.py:404-596 (train_eval_model
assembling Estimator/TPUEstimator + TrainSpec/EvalSpec + exporters + hooks)
and the model_fn skeleton it drives (/root/reference/models/abstract_model.py
:651-823). The TF1 machinery maps as:

  (TPU)Estimator + RunConfig          -> Trainer: one jitted train_step
      donated + sharded over a Mesh; iterations are plain Python around a
      fully-compiled XLA program (infeed == shard_batch on host arrays)
  CrossShardOptimizer all-reduce      -> psum inserted by XLA from the
      batch's 'data'-axis sharding — nothing to write
  TrainSpec/EvalSpec + exporters      -> train_eval_model(): alternating
      train/eval phases, exporters invoked after each eval
  continuous eval (checkpoints_iterator + backup ckpt) -> eval_continuously()
  TPU bf16 wrapper                    -> Bfloat16PreprocessorWrapper applied
      when model.is_device_tpu (host pipeline emits bf16 arrays directly)
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensor2robot_tpu.data.input_generators import AbstractInputGenerator
from tensor2robot_tpu.models.abstract_model import AbstractT2RModel, TrainState
from tensor2robot_tpu.models.model_interface import ModelInterface
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import (
    AutoProfiler,
    GoodputTracker,
    TelemetryLogger,
    Watchdog,
    WatchdogConfig,
    event,
    get_registry,
    span,
)
from tensor2robot_tpu.observability import fleet as fleet_lib
from tensor2robot_tpu.observability import goodput as goodput_lib
from tensor2robot_tpu.observability import pipeline_xray as xray_lib
from tensor2robot_tpu.observability import roofline as roofline_lib
from tensor2robot_tpu.observability import signals as signals_lib
from tensor2robot_tpu.observability import watchdog as watchdog_lib
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import sharding as sharding_lib
from tensor2robot_tpu.preprocessors.bfloat16_wrapper import (
    Bfloat16PreprocessorWrapper,
)
from tensor2robot_tpu.reliability import fault_injection
from tensor2robot_tpu.reliability import quarantine as quarantine_lib
from tensor2robot_tpu.reliability.errors import (
    CHECKPOINT_SKIP_ERRORS,
    NonFiniteLossError,
    TrainingPreempted,
)
from tensor2robot_tpu.reliability.preemption import graceful_shutdown
from tensor2robot_tpu.specs import assets as assets_lib
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.trainer import checkpointing

NAN_POLICIES = ('off', 'skip', 'raise', 'rollback')

_logv = None


def _log(msg: str, *args) -> None:
  global _logv
  if _logv is None:
    from absl import logging as _absl_logging  # deferred: absl optional
    _logv = _absl_logging.info
  _logv(msg, *args)


def _json_scalar(value):
  """Host scalar -> JSON-safe float (NaN/inf become None; arrays mean)."""
  if value is None:
    return None
  value = float(np.mean(value))
  return value if np.isfinite(value) else None


class _StepWatcher:
  """Records WHEN steps finish on the device, with no profiler.

  The training thread hands over a leaf of each step's ``metrics`` output
  (never the state: that is donated to the next step) as it dispatches;
  this daemon thread waits for one step at a time and appends the event
  ``train.step_done`` (``step``, ``steps_covered``) to the span ring, on
  the clock of every other span. The event also carries the values of the
  step metrics the MODEL names (``traced_step_metrics``, none by default),
  read here after the wait, so the training thread pays nothing and the
  ring holds them for any driver. It waits for the OLDEST pending step —
  in a device-bound loop the host leads by several steps and each is seen
  as it ends — unless that one is already done while newer ones wait: then
  this thread has fallen behind a fast loop, and it skips to the first
  step still running (or the newest), so it samples, one wake-up at a
  time. ``steps_covered`` says how many steps an event stands for.

  With ``train.step``'s end (step n dispatched) and ``train.step_done`` of
  step n-1 a reader knows the interval between completions, how far the
  host leads, and the time the device had nothing to run,
  ``max(0, dispatched(n) - done(n-1))``.
  """

  def __init__(self, traced_step_metrics: Sequence[str] = ()):
    self._traced = tuple(traced_step_metrics)
    self._cond = threading.Condition()
    self._pending: collections.deque = collections.deque()
    self._stopped = False
    self._thread = threading.Thread(target=self._run, daemon=True,
                                    name='t2r-step-watch')
    self._thread.start()

  def submit(self, step: int, metrics) -> None:
    leaves = jax.tree_util.tree_leaves(metrics)
    if not leaves:
      return
    traced = {name: metrics[name] for name in self._traced
              if name in metrics}
    with self._cond:
      self._pending.append((step, leaves[0], traced))
      self._cond.notify()

  def stop(self) -> None:
    """Ends the thread; called on every exit path of ``train``. A step
    still running is waited out (the caller is about to sync anyway)."""
    with self._cond:
      self._stopped = True
      self._pending.clear()
      self._cond.notify()
    self._thread.join(timeout=60.0)
    if self._thread.is_alive():
      _log('Step watcher still waiting for the device after 60 s; left '
           'behind as a daemon thread.')

  def _run(self) -> None:
    last_step = None
    while True:
      with self._cond:
        while not self._pending and not self._stopped:
          self._cond.wait()
        if self._stopped:
          return
        step, leaf, traced = self._pending.popleft()
        while self._pending and leaf.is_ready():
          step, leaf, traced = self._pending.popleft()
      try:
        jax.block_until_ready(leaf)
        traced = {name: float(value) for name, value in traced.items()}
      except Exception:  # noqa: BLE001 — a failed step ends no other way
        continue
      # After a rollback the step counter runs backwards: such an event
      # stands for itself alone.
      covered = step - last_step if last_step is not None else 1
      event('train.step_done', step=step, steps_covered=max(covered, 1),
            **traced)
      last_step = step


def provide_input_generator_with_model_information(
    input_generator: AbstractInputGenerator,
    t2r_model: AbstractT2RModel,
    mode: str) -> AbstractInputGenerator:
  """Binds the model's (preprocessed) specs to the input generator.

  Ref: utils/train_eval.py:101 + abstract_input_generator.py:80.
  """
  input_generator.set_specification_from_model(t2r_model, mode)
  return input_generator


class Trainer:
  """Owns the mesh, the compiled step functions, and checkpointing."""

  def __init__(self,
               model: AbstractT2RModel,
               model_dir: str,
               mesh: Optional[Mesh] = None,
               use_fsdp: bool = False,
               tp_rules: Optional[Sequence[Tuple[str, Any]]] = None,
               seed: int = 0,
               keep_checkpoint_max: int = 5,
               save_checkpoints_steps: int = 500,
               async_checkpoints: bool = True,
               log_every_n_steps: int = 100,
               use_avg_params_for_eval: Optional[bool] = None,
               write_metrics: bool = True,
               eval_name: Optional[str] = None,
               profile_steps: Optional[Sequence[int]] = None,
               auto_profile: bool = True,
               profile_budget: int = 2,
               profile_window_steps: int = 5,
               profile_min_interval_secs: float = 600.0,
               enable_watchdog: bool = True,
               watchdog_config: Optional[WatchdogConfig] = None,
               enable_pipeline_xray: bool = True,
               xray_config: Optional[xray_lib.XrayConfig] = None,
               enable_fleet: Optional[bool] = None,
               fleet_config: Optional[fleet_lib.FleetConfig] = None,
               nan_policy: str = 'skip',
               nan_rollback_budget: int = 3,
               nan_check_every_n_steps: int = 1,
               owns_checkpoint_dir: bool = True,
               tuned_config: Optional[Any] = None,
               tuning_cache_path: Optional[str] = None,
               use_compiled_artifacts: bool = False,
               artifact_workload: Optional[str] = None,
               feed_depth: int = 1,
               host_identity: Optional[Dict[str, object]] = None,
               shared_telemetry: Optional[TelemetryLogger] = None):
    """write_metrics: emit TensorBoard events (train scalars under
    model_dir, eval under model_dir/eval[_<eval_name>] — the reference's
    per-eval-run dirs, ref utils/train_eval.py:539-547).
    profile_steps: (start, stop) global steps bracketing ONE static
    jax.profiler trace written under model_dir/plugins (SURVEY §5); the
    window now also produces a forensics/<step>.json report.
    auto_profile: let the watchdog trigger additional budgeted capture
    windows when it detects an anomaly (docs/observability.md): at most
    ``profile_budget`` triggered captures per run, each
    ``profile_window_steps`` steps long, at least
    ``profile_min_interval_secs`` (monotonic) apart.
    enable_watchdog / watchdog_config: rolling-baseline anomaly
    detection (step-time regression, goodput drop, recompiles, HBM
    growth) at the log cadence; detections are counted, written to
    telemetry.jsonl, and — with auto_profile — answered with a capture.
    enable_pipeline_xray / xray_config: per-stage host->device dataflow
    attribution at the log cadence (docs/observability.md "Pipeline
    X-ray"): each window emits a ``t2r.pipeline.v1`` telemetry record
    naming the gating stage and its headroom vs. the device rate, and
    the pipeline anomaly kinds (pipeline_stall / worker_starvation /
    transfer_regression) feed the same capture loop as the watchdog's.
    enable_fleet / fleet_config: fleet observation at the log cadence
    (docs/observability.md "Fleet observatory"): reads every host's
    heartbeat under the shared model_dir, emits a ``t2r.fleet.v1``
    telemetry record (per-host table, skew, gating host, fleet-min
    goodput), and routes ``straggler`` / ``host_dead`` anomalies into
    the same budgeted-capture loop. ``None`` (default) auto-enables on
    multi-process runs; ``True`` forces it on (single-process runs with
    simulated peers — tests, the MULTICHIP fleet phase).
    nan_policy: what the non-finite-loss sentinel does
    (docs/reliability.md): 'skip' (default) discards the poisoned update
    on device — params/opt state keep their pre-step values, only the
    step counter advances, zero host syncs; 'rollback' restores the last
    committed checkpoint (at most ``nan_rollback_budget`` times per
    train() call, then raises NonFiniteLossError); 'raise' fails
    immediately; 'off' reproduces the unguarded seed behavior.
    nan_check_every_n_steps: host-side loss check cadence for
    'raise'/'rollback' (each check syncs the device; 'skip' never does).
    owns_checkpoint_dir: whether this trainer is the writer of
    model_dir's checkpoints. False for eval-only jobs sharing a live
    training directory: their manager then never quarantines (renames)
    damaged step dirs out from under the owning trainer
    (checkpointing.CheckpointManager quarantine_damaged).
    tuned_config: autotuned compile config for the train step
    (docs/performance.md "Compile-config autotuner"). Accepts a
    ``tuning.CompileConfig``, its dict form, or a WORKLOAD NAME string —
    the string is looked up in the persistent config cache at first
    compile, keyed by this step's actual shapes/dtypes + device_kind +
    jax version, so a cache miss (never-tuned workload, changed batch
    size, different chip) silently runs the stock compile. Only the
    config's ``compiler_options`` apply here; ``model_overrides`` are
    layout changes that must come in through the model constructor and
    are ignored (logged) by this hook. The applied config id is exposed
    as ``active_config_id`` and stamped into forensics reports so a
    perf regression is attributable to the config that produced it.
    tuning_cache_path: cache file for the string form (default:
    tuning.default_cache_path()).
    use_compiled_artifacts: resolve the train step through the unified
    ``CompiledArtifact`` store (tensor2robot_tpu/compile, docs/
    performance.md "Cold start"): at first compile the trainer looks up
    the persisted executable for its REAL first-batch shapes — keyed by
    workload | device_kind | jax version | shapes | lowered-program
    hash | config — and a warm start deserializes it, so the first step
    EXECUTES without a single XLA compile. A miss, a stale payload, or
    a corrupt file degrades to the stock compile and persists the
    result for next time; a tuned-config winner resolved from the cache
    passes the same shared guard as the legacy hook (model-override
    winners refused, ``winner_ok=False`` placeholders ignored).
    artifact_workload: the store key's workload name. Defaults to the
    ``tuned_config`` string when one is given (so the autotuner sweep's
    persisted candidates are found — the winner's executable is free at
    train time), else ``trainer_<model class name>``. The
    lowered-program hash in the key makes name collisions harmless:
    a different program is a miss, never a wrong load.
    feed_depth: > 1 pipelines the train channel's host->device hop
    through an N-deep :class:`~tensor2robot_tpu.data.device_feed.
    PipelinedFeed`: a producer thread transfers batches k+1..k+depth
    (decode + copy, sparse/packed unpack dispatch) while the device runs
    step k, so on a transfer-limited host the copy hides under compute
    instead of serializing with it (docs/performance.md "Transfer
    path"). The goodput 'data' fraction then measures only the time the
    loop actually WAITED for a buffered batch; the X-ray transfer stage
    keeps timing each copy to completion in the producer thread, so
    MB/s attribution is unchanged. 1 (default) keeps the synchronous
    hop.
    host_identity: overrides the fleet identity stamp
    (``signals.host_identity()``) for this trainer's telemetry,
    heartbeat, recovery-marker and forensics records. The elastic
    driver (tensor2robot_tpu/elastic) uses it because each simulated
    host of the CPU federation is its own jax world —
    ``jax.process_index()`` is 0 everywhere — while the ELASTIC host
    index must route each process to its own ``telemetry.<i>.jsonl``.
    shared_telemetry: use this TelemetryLogger instead of constructing
    one, and do NOT close it in ``close()`` — the elastic driver keeps
    ONE per-host stream alive across the per-epoch trainers it builds
    (two loggers appending one file from one process would interleave
    buffered writes mid-line).
    """
    self.model = model
    self.model_dir = model_dir
    self.mesh = mesh if mesh is not None else mesh_lib.create_mesh()
    self.use_fsdp = use_fsdp
    # (path-regex, PartitionSpec) pairs for tensor-parallel params over the
    # mesh's 'model' axis (parallel/sharding.py TP_RULES_TRANSFORMER);
    # None = no TP. The model must also be built with the matching
    # tp_axis so activations carry the same placement.
    self.tp_rules = tp_rules
    self.seed = seed
    self.log_every_n_steps = log_every_n_steps
    self.save_checkpoints_steps = save_checkpoints_steps
    if use_avg_params_for_eval is None:
      use_avg_params_for_eval = model.use_avg_model_params
    self.use_avg_params_for_eval = use_avg_params_for_eval
    os.makedirs(model_dir, exist_ok=True)
    self.checkpoint_manager = checkpointing.CheckpointManager(
        model_dir,
        keep_checkpoint_max=keep_checkpoint_max,
        save_interval_steps=1,
        async_checkpoints=async_checkpoints,
        quarantine_damaged=owns_checkpoint_dir)
    self._state_sharding = None
    self._train_step_fn = None
    self._train_step_jitted = None  # the raw jit object (cache-size probe)
    self._step_abstract = None  # ShapeDtypeStruct args for AOT relowering
    self._eval_step_fn = None
    self._predict_step_fn = None
    self._throughput = None  # (examples/sec, step_time_s) from last train run
    self.last_eval_state = None  # state used by the most recent evaluate()
    self._write_metrics = write_metrics
    self._eval_name = eval_name
    self._auto_profiler = AutoProfiler(
        model_dir,
        static_window=profile_steps,
        window_steps=profile_window_steps,
        max_captures=profile_budget if auto_profile else 0,
        min_interval_secs=profile_min_interval_secs)
    self._watchdog = (Watchdog(watchdog_config) if enable_watchdog
                      else None)
    self._xray = (xray_lib.PipelineXray(xray_config)
                  if enable_pipeline_xray else None)
    self._enable_fleet = enable_fleet
    self._fleet_config = fleet_config
    self._fleet_observer: Optional[fleet_lib.FleetObserver] = None
    self._host_identity: Optional[Dict[str, object]] = (
        dict(host_identity) if host_identity else None)
    self._shared_telemetry = shared_telemetry
    # Compile-event accounting (jax/compiles, jax/compile_ms) feeds the
    # watchdog's recompile detection; idempotent per process.
    signals_lib.install_jax_listeners()
    if nan_policy not in NAN_POLICIES:
      raise ValueError('nan_policy must be one of {}; got {!r}.'.format(
          NAN_POLICIES, nan_policy))
    self._nan_policy = nan_policy
    self._nan_rollback_budget = int(nan_rollback_budget)
    self._nan_check_every_n_steps = max(1, int(nan_check_every_n_steps))
    self._train_writer = None
    self._eval_writer = None
    self._telemetry = None
    self._last_goodput = None
    # Seconds the last ``init_state`` took (its span's ``elapsed``).
    self._init_state_s = 0.0
    self._device_feed = None
    self._device_feed_built = False
    self._tuned_config = tuned_config
    self._tuning_cache_path = tuning_cache_path
    self._use_compiled_artifacts = bool(use_compiled_artifacts)
    self._artifact_workload = artifact_workload
    self._feed_depth = max(1, int(feed_depth))
    self._train_step_compiled = None  # AOT executable under tuned options
    self._train_step_artifact = None  # CompiledArtifact (provenance+HLO)
    self._step_cost_cache = None  # cost-model totals (False = resolved none)
    self.active_config_id: Optional[str] = None

  def _put_batch(self, batch: dict, channel: str = 'train'):
    """Host batch -> sharded device batch, sparse-coef aware.

    With a DeviceDecodePreprocessor(sparse=True) pipeline the input
    batches carry bucketed sparse DCT streams; the feed unpacks them to
    the fixed-shape dense coefficient tensors right after transfer so the
    jitted step never recompiles (data/device_feed.py). Everything else
    is a plain shard_batch. ``channel`` scopes the feed's shape-stability
    accounting to the jitted program consuming the batch: the eval step
    is its own compile, so its (legitimately different) batch shape must
    not trip the train-step invariant.
    """
    if not self._device_feed_built:
      from tensor2robot_tpu.data.device_feed import (
          HostDeviceFeed,
          SparseCoefFeed,
      )
      # EVERY batch crosses a feed (plain HostDeviceFeed when no sparse
      # groups are in play) so the pipeline X-ray's transfer stage is
      # metered unconditionally.
      self._device_feed = (SparseCoefFeed.from_preprocessor(
          self.model.preprocessor, self.mesh)
          or HostDeviceFeed(self.mesh))
      self._device_feed_built = True
    return self._device_feed.put_batch(batch, channel=channel)

  @property
  def train_metrics_writer(self):
    """Lazy TensorBoard writer for the train run (None when disabled)."""
    if self._write_metrics and self._train_writer is None:
      from tensor2robot_tpu.trainer.metrics import MetricsWriter
      self._train_writer = MetricsWriter(self.model_dir)
    return self._train_writer

  @property
  def eval_metrics_writer(self):
    if self._write_metrics and self._eval_writer is None:
      from tensor2robot_tpu.trainer.metrics import MetricsWriter
      subdir = ('eval_' + self._eval_name) if self._eval_name else 'eval'
      self._eval_writer = MetricsWriter(os.path.join(self.model_dir, subdir))
    return self._eval_writer

  @property
  def host_identity(self) -> Dict[str, object]:
    """This process's fleet identity (cached): the host_meta stamp every
    telemetry record/heartbeat and forensics report carries."""
    if self._host_identity is None:
      self._host_identity = signals_lib.host_identity()
    return self._host_identity

  @property
  def telemetry_logger(self):
    """Lazy telemetry.jsonl + heartbeat writer (None when metrics are off).

    Multi-process runs get per-host filenames
    (``telemetry.<process_index>.jsonl``) via the identity host_meta —
    N processes sharing one model_dir must never append to one file.
    """
    if self._shared_telemetry is not None:
      return self._shared_telemetry
    if self._write_metrics and self._telemetry is None:
      self._telemetry = TelemetryLogger(self.model_dir,
                                        host_meta=self.host_identity)
    return self._telemetry

  @property
  def fleet_observer(self) -> Optional[fleet_lib.FleetObserver]:
    """Lazy fleet observer (None when disabled/single-process)."""
    enabled = self._enable_fleet
    if enabled is None:
      enabled = int(self.host_identity.get('process_count') or 1) > 1
    if not enabled or not self._write_metrics:
      return None
    if self._fleet_observer is None:
      self._fleet_observer = fleet_lib.FleetObserver(
          self.model_dir, self.host_identity, config=self._fleet_config)
    return self._fleet_observer

  @property
  def last_goodput(self):
    """The GoodputTracker of the most recent train() call (or None)."""
    return self._last_goodput

  @property
  def auto_profiler(self) -> AutoProfiler:
    """The capture-window owner (static profile_steps + triggered)."""
    return self._auto_profiler

  @property
  def watchdog(self) -> Optional[Watchdog]:
    return self._watchdog

  def _train_step_hlo(self) -> Optional[str]:
    """Compiled-HLO text of the train step for forensics collective
    stats. Under a tuned config the LIVE tuned executable's HLO is used
    (the report is stamped with its id — analyzing a stock recompile
    would attribute ops of a program that never ran); otherwise relowers
    from the recorded abstract args (one extra XLA compile — acceptable
    once per budgeted capture, never in the loop).
    """
    if self._train_step_artifact is not None and \
        self._train_step_artifact.hlo_text:
      # Unified-artifact path: the post-optimization HLO rode the
      # persisted payload, so forensics reads the STORED program — no
      # relowering, and it works even for a deserialized executable
      # whose backend cannot render text.
      return self._train_step_artifact.hlo_text
    if self._train_step_compiled is not None:
      try:
        return self._train_step_compiled.as_text()
      except Exception:  # noqa: BLE001 — fall through to the relower
        pass
    if self._train_step_jitted is None or self._step_abstract is None:
      return None
    return self._train_step_jitted.lower(
        *self._step_abstract).compile().as_text()

  def _sample_recompiles(self, registry) -> None:
    """``recompiles/train_step``: the jitted step's executable-cache
    size. Exactly 1 on a healthy run — the device_feed shape-stability
    contract as a number; growth means some batch silently triggered a
    full model recompile (the watchdog's ``recompile`` detection)."""
    if self._train_step_jitted is None:
      return
    if self._train_step_compiled is not None:
      # Tuned-config AOT path: exactly one executable exists by
      # construction and the jit cache stays empty — report the healthy 1.
      registry.gauge(watchdog_lib.RECOMPILE_GAUGE).set(1.0)
      return
    try:
      size = self._train_step_jitted._cache_size()
    except Exception:  # noqa: BLE001 — private probe; absent on old jax
      return
    registry.gauge(watchdog_lib.RECOMPILE_GAUGE).set(float(size))

  def _step_cost(self) -> Optional[Dict[str, object]]:
    """Per-device train-step FLOPs/bytes through THE shared cost model
    (parallel/hlo_analysis.program_cost), which the ``perf/mfu`` and
    ``perf/hbm_bw_util`` gauges and the forensics roofline record read
    too, so they agree by construction. Resolution order mirrors
    ``_train_step_hlo``: persisted artifact HLO, then the live tuned
    executable, then a one-off relower from the recorded abstract args.
    Resolved once and cached (False = resolved to nothing)."""
    if self._step_cost_cache is not None:
      return self._step_cost_cache or None
    cost = None
    try:
      from tensor2robot_tpu.parallel import hlo_analysis
      if self._train_step_artifact is not None and \
          self._train_step_artifact.hlo_text:
        cost = hlo_analysis.program_cost(self._train_step_artifact.hlo_text)
      elif self._train_step_compiled is not None:
        cost = hlo_analysis.program_cost(self._train_step_compiled)
      elif self._train_step_jitted is not None and \
          self._step_abstract is not None:
        cost = hlo_analysis.program_cost(
            self._train_step_jitted.lower(*self._step_abstract).compile())
    except Exception:  # noqa: BLE001 — perf accounting must never kill a run
      cost = None
    self._step_cost_cache = cost if cost and cost.get('flops') else False
    return self._step_cost_cache or None

  def _publish_perf(self, registry, step_time_s: float) -> None:
    """``perf/mfu`` + ``perf/hbm_bw_util`` for this log window.

    Only on hosts whose ``device_kind`` has a peaks-table entry — CPU
    (and unknown kinds) publish nothing rather than a fabricated 0, so
    the watchdog's ``mfu_regression`` check is trivially quiet there
    and CPU test runs pay no relower cost (the step cost is only
    resolved once a peaks entry exists)."""
    if step_time_s <= 0.0:
      return
    kind = str(self.host_identity.get('device_kind', 'unknown'))
    if roofline_lib.device_peaks(kind) is None:
      return
    cost = self._step_cost()
    if cost is None:
      return
    try:
      roofline_lib.publish_perf_gauges(
          registry, float(cost['flops']), float(cost['bytes']),
          step_time_s, kind)
    except Exception:  # noqa: BLE001
      pass

  # -- state ---------------------------------------------------------------

  def _batch_sharding(self):
    return sharding_lib.batch_sharding(self.mesh)

  def init_state(self, features: SpecStruct,
                 labels: Optional[SpecStruct],
                 mode: str = ModeKeys.TRAIN) -> TrainState:
    """Initializes (or restores) a sharded TrainState from a sample batch.

    ``features``/``labels`` are an IN-spec batch from the input pipeline;
    they are run through the preprocessor so variable shapes match what the
    (preprocessed) train step feeds the network.
    """
    # One span whoever calls (``train``, a driver ahead of it): the parent
    # of the compile records of the init program and of ``ckpt.restore``.
    with span('train.init_state', restored=0) as sp:
      state = self._init_or_restore_state(features, labels, mode, sp)
    self._init_state_s = sp.elapsed
    return state

  def _init_or_restore_state(self, features, labels, mode, sp) -> TrainState:
    rng = jax.random.PRNGKey(self.seed)
    features, labels = self.model.preprocessor.preprocess(
        features, labels, mode, rng=jax.random.PRNGKey(self.seed + 2))
    abstract_state = jax.eval_shape(
        lambda: self.model.create_train_state(rng, features, labels))
    self._state_sharding = sharding_lib.train_state_sharding(
        abstract_state, self.mesh, use_fsdp=self.use_fsdp,
        tp_rules=self.tp_rules)
    # Re-read disk: a concurrent trainer may have written checkpoints
    # since this manager was constructed (continuous-eval topology).
    self.checkpoint_manager.reload()
    steps = sorted(self.checkpoint_manager.all_steps(), reverse=True)
    if steps:
      template = jax.tree.map(
          lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                               sharding=s),
          abstract_state, self._state_sharding)
      # Newest first, skipping checkpoints that fail to restore for
      # transient reasons (half-written by a concurrent trainer, deleted
      # by retention GC between listing and read, flaky filesystem). A
      # restore problem that hits EVERY committed step is real: re-raise
      # rather than silently reinitializing and discarding the run.
      last_error = None
      for candidate in steps:
        _log('Restoring checkpoint at step %d from %s', candidate,
             self.model_dir)
        try:
          state = self.checkpoint_manager.restore(template, step=candidate)
          sp.note(restored=1)
          return state
        except CHECKPOINT_SKIP_ERRORS as e:
          last_error = e
          _log('Checkpoint %d in %s failed to restore (%s); trying the '
               'previous one.', candidate, self.model_dir, e)
      raise last_error
    # No checkpoint: this is a FRESH state. Callers chaining train() calls
    # without checkpointing must thread the returned state explicitly or
    # each call restarts from initialization — log so that's visible.
    _log('No checkpoint in %s; initializing fresh train state.',
         self.model_dir)
    if getattr(self.model, 'warm_start_fn', None) is not None:
      # Warm start restores a foreign checkpoint (real I/O): run it eagerly
      # exactly once and shard the result, instead of tracing it under jit
      # where the restored weights would be baked in as XLA constants.
      state = self.model.create_train_state(rng, features, labels)
      return jax.device_put(state, self._state_sharding)
    init_fn = jax.jit(
        lambda f, l: self.model.create_train_state(rng, f, l),
        out_shardings=self._state_sharding)
    # shard_batch, not device_put: multi-process hosts hold only their
    # slice of the global batch (parallel/sharding.py:59-74).
    features = sharding_lib.shard_batch(features.to_dict(), self.mesh)
    labels = (sharding_lib.shard_batch(labels.to_dict(), self.mesh)
              if labels is not None else None)
    return init_fn(features, labels)

  # -- compiled steps -------------------------------------------------------

  def _compile_train_step(self):
    if self._train_step_fn is not None:
      return self._train_step_fn
    model = self.model
    nan_policy = self._nan_policy

    def step(state, features, labels, base_rng, force_nan):
      # Fold the step into the rng on-device: no host round-trip per step.
      rng = jax.random.fold_in(base_rng, state.step)
      pre_rng, step_rng = jax.random.split(rng)
      # The preprocessor runs INSIDE the jitted step: crops/distortions/casts
      # execute on device, fused by XLA into the forward pass (the TPU-native
      # replacement for the reference's host-side tf.data map,
      # utils/tfdata.py:572-574).
      features, labels = model.preprocessor.preprocess(
          SpecStruct(**features),
          SpecStruct(**labels) if labels is not None else None,
          ModeKeys.TRAIN, rng=pre_rng)
      new_state, metrics = model.train_step(state, features, labels,
                                            step_rng)
      metrics = dict(metrics)
      loss = metrics.get('loss')
      if loss is not None:
        # ``force_nan`` is the FaultInjector's 'step.nan' site: a traced
        # scalar (no recompile per toggle) poisoning the loss on device.
        loss = jnp.where(force_nan, jnp.nan, loss)
        metrics['loss'] = loss
        if nan_policy == 'skip':
          # Discard a poisoned update without leaving the device: every
          # leaf keeps its pre-step value when the loss is non-finite,
          # except the step counter, which advances so loop/bookkeeping
          # and checkpoint steps stay aligned ("batch dropped").
          good = jnp.all(jnp.isfinite(loss))
          guarded = jax.tree.map(
              lambda new, old: jnp.where(good, new, old), new_state, state)
          new_state = guarded.replace(step=new_state.step)
          metrics['nonfinite_loss_skipped'] = 1 - good.astype(jnp.int32)
      return new_state, metrics

    batch = self._batch_sharding()
    replicated = NamedSharding(self.mesh, P())
    # The artifact path compiles WITHOUT donation: a persisted
    # (serialize_executable) train step with input/output aliasing baked
    # in executes incorrectly after deserialization on this jaxlib's CPU
    # backend — an Orbax-restored state donated into a deserialized
    # executable comes back with a skewed step counter / rng fold
    # (pinned by tests/test_elastic.py's cross-process repro; the same
    # program self-compiled, or run on fresh-init state, is fine). The
    # cost is one transient state copy per step; the stock jit path
    # keeps the donation.
    jit_kwargs = {}
    if not self._use_compiled_artifacts:
      jit_kwargs['donate_argnums'] = (0,)
    jitted = jax.jit(
        step,
        in_shardings=(self._state_sharding, batch, batch, replicated,
                      replicated),
        out_shardings=(self._state_sharding, replicated),
        **jit_kwargs)

    def call(state, features, labels, base_rng, force_nan=None):
      # force_nan defaults off so external callers of the compiled step
      # (tests, rl/offpolicy) keep the pre-reliability 4-arg signature.
      if force_nan is None:
        force_nan = np.asarray(False)
      if self._step_abstract is None:
        # Shape/dtype skeleton BEFORE the call (state is donated): lets
        # forensics relower the exact compiled program without holding
        # any buffers alive.
        self._step_abstract = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(jnp.shape(leaf),
                                              jnp.result_type(leaf)),
            (state, features, labels, base_rng, force_nan))
        self._bind_compiled_step(jitted, self._step_abstract)
      if self._train_step_compiled is not None:
        return self._train_step_compiled(state, features, labels, base_rng,
                                         force_nan)
      return jitted(state, features, labels, base_rng, force_nan)

    self._train_step_jitted = jitted
    self._train_step_fn = call
    return self._train_step_fn

  def bind_train_step(self, features: SpecStruct,
                      labels: Optional[SpecStruct]):
    """AOT-binds the train-step executable WITHOUT executing a step.

    The cold-start prewarm hook: resolves the step through the
    ``CompiledArtifact`` store (or the legacy tuned hook) from a sample
    host batch alone — tracing, lowering, and (on a store hit)
    deserializing, but never running the program. That split is what
    lets a multi-host bring-up stagger "host 0 compiles + persists,
    hosts 1..N deserialize" around a barrier even though the step
    itself is a collective no host can run alone
    (``parallel/multihost.py``), and it is how an elastic rebuild can
    bind before its first probe step. Returns the bound
    ``CompiledArtifact`` (None when binding fell back to the stock jit
    path). Idempotent: a later ``train()`` reuses the binding.
    """
    rng = jax.random.PRNGKey(self.seed)
    pre_features, pre_labels = self.model.preprocessor.preprocess(
        features, labels, ModeKeys.TRAIN,
        rng=jax.random.PRNGKey(self.seed + 2))
    abstract_state = jax.eval_shape(
        lambda: self.model.create_train_state(rng, pre_features,
                                              pre_labels))
    self._state_sharding = sharding_lib.train_state_sharding(
        abstract_state, self.mesh, use_fsdp=self.use_fsdp,
        tp_rules=self.tp_rules)
    self._compile_train_step()
    if self._step_abstract is None:
      # The batch crosses the real device feed so the abstract batch
      # carries GLOBAL shapes (a multi-process host's local slice is
      # only 1/Nth of what the step consumes).
      device_batch = self._put_batch(
          {'features': features.to_dict(),
           'labels': labels.to_dict() if labels is not None else None})
      base_rng = jax.random.PRNGKey(self.seed + 1)
      self._step_abstract = jax.tree.map(
          lambda leaf: jax.ShapeDtypeStruct(jnp.shape(leaf),
                                            jnp.result_type(leaf)),
          (abstract_state, device_batch['features'],
           device_batch['labels'], base_rng, np.asarray(False)))
      self._bind_compiled_step(self._train_step_jitted,
                               self._step_abstract)
    return self._train_step_artifact

  def _resolve_tuned_config(self, args):
    """tuned_config (CompileConfig | dict | workload-name str) ->
    (config, from_cache).

    The string form is the production hook: look the workload up in the
    persistent tuning cache under THIS step's shapes/dtypes + device_kind
    + jax version. A miss returns None — the trainer must run identically
    with and without a cache entry. ``from_cache`` distinguishes a
    cache-resolved winner from a directly-passed config: a direct
    config's ``model_overrides`` were applied by the caller at model
    construction, a cache-resolved one's were NOT.
    """
    from tensor2robot_tpu import tuning

    spec = self._tuned_config
    if spec is None:
      return None, False
    if isinstance(spec, tuning.CompileConfig):
      return spec, False
    if isinstance(spec, dict):
      return tuning.CompileConfig.from_dict(spec), False
    cache = tuning.ConfigCache(self._tuning_cache_path)
    key = tuning.cache_key(
        str(spec), tuning.abstract_signature(args),
        getattr(jax.devices()[0], 'device_kind', 'unknown'))
    entry = cache.lookup(key)
    # The shared stale-winner guard (compile/artifact.py): cache misses,
    # winner_ok=False placeholders, and winners carrying model_overrides
    # (which the trainer cannot re-apply at compile time — half-applying
    # just their flags would run an unmeasured hybrid attributed to the
    # winner's id) all resolve to the stock compile HERE, identically
    # for this legacy hook and the artifact load path.
    from tensor2robot_tpu.compile import artifact as artifact_lib
    config, reason = artifact_lib.resolve_cache_winner(entry)
    if config is None:
      _log('Tuning cache for workload %r (%s) yields no applicable '
           'winner (%s); using the stock compile.%s', spec, key, reason,
           ' Apply the overrides at model construction and pass the '
           'config directly to use this winner.'
           if reason == 'model_overrides' else '')
      return None, True
    return config, True

  def _bind_compiled_step(self, jitted, args) -> None:
    """Binds the train-step executable at first call: the unified
    CompiledArtifact cold-start path when ``use_compiled_artifacts``,
    else the legacy AOT-under-tuned-options hook.

    Best-effort by the same contract as the legacy hook: any store or
    compile failure costs a log line and falls back to the stock jit
    path, never the training run.
    """
    if not self._use_compiled_artifacts:
      self._apply_tuned_config(jitted, args)
      return
    try:
      from tensor2robot_tpu.compile import artifact as artifact_lib

      config, from_cache = self._resolve_tuned_config(args)
      if config is not None and config.model_overrides and not from_cache:
        # Direct-form config: the caller applied the layout overrides at
        # model construction; only the flags compile here.
        _log('Tuned config %s carries model_overrides %s — applied at '
             'model construction, not here.', config.config_id,
             sorted(config.model_overrides))
      workload = self._artifact_workload
      if workload is None:
        workload = (str(self._tuned_config)
                    if isinstance(self._tuned_config, str)
                    else 'trainer_' + type(self.model).__name__.lower())
      with span('train.artifact_load'):
        artifact = artifact_lib.load_or_compile(
            workload, jitted, args, config=config,
            cache_path=self._tuning_cache_path,
            telemetry=self.telemetry_logger, program_key=True)
      self._train_step_compiled = artifact.executable
      self._train_step_artifact = artifact
      if config is not None and (config.compiler_options
                                 or (config.model_overrides
                                     and not from_cache)):
        # Same attribution rule as the legacy hook: a config took effect
        # here (flags) or at model construction (direct-form overrides).
        self.active_config_id = config.config_id
      _log('Train step bound from CompiledArtifact store: workload=%s '
           '%s (config %s, key %s).', workload,
           'deserialized persisted executable' if artifact.from_cache
           else 'compiled + persisted', artifact.config_id, artifact.key)
    except Exception as e:  # noqa: BLE001 — store trouble must not kill
      # training: degrade to the legacy hook (which itself degrades to
      # the stock jit compile).
      _log('CompiledArtifact bind failed (%s); using the legacy tuned '
           'hook.', e)
      self._train_step_compiled = None
      self._train_step_artifact = None
      self._apply_tuned_config(jitted, args)

  def _apply_tuned_config(self, jitted, args) -> None:
    """AOT-compiles the train step under the tuned compiler options.

    Best-effort by contract: a stale cache entry naming a flag this
    jaxlib rejects must cost a log line and fall back to the stock
    compile, never the training run. ``active_config_id`` is set only
    when the config actually took effect.
    """
    try:
      config, from_cache = self._resolve_tuned_config(args)
    except Exception as e:  # noqa: BLE001 — cache I/O must never kill train
      _log('Tuned-config resolution failed (%s); using stock compile.', e)
      return
    if config is None:
      return
    if config.model_overrides:
      # Cache-resolved winners with overrides never reach here — the
      # shared resolve_cache_winner guard already refused them — so
      # this is the DIRECT form: the caller applied the overrides at
      # model construction; only the flags compile below.
      _log('Tuned config %s carries model_overrides %s — layout changes '
           'apply at model construction, not here; ignoring them.',
           config.config_id, sorted(config.model_overrides))
    if not config.compiler_options:
      # Overrides-only config: attributable only when the CALLER applied
      # the overrides at model construction (direct form). A
      # cache-resolved one took no effect here — stamping its id would
      # attribute runs to a config that never applied.
      if not from_cache:
        self.active_config_id = config.config_id
      return
    from tensor2robot_tpu.tuning import autotuner
    try:
      with span('train.tuned_compile'):
        self._train_step_compiled = autotuner.compile_with_config(
            jitted, args, config)
      self.active_config_id = config.config_id
      _log('Train step compiled under tuned config %s (%s).',
           config.config_id, config.compiler_options)
    except Exception as e:  # noqa: BLE001 — unknown flag on this backend
      self._train_step_compiled = None
      _log('Tuned config %s failed to compile (%s); using stock compile.',
           config.config_id, e)

  def _compile_eval_step(self):
    if self._eval_step_fn is not None:
      return self._eval_step_fn
    model = self.model
    use_avg = self.use_avg_params_for_eval

    def step(state, features, labels):
      features, labels = model.preprocessor.preprocess(
          SpecStruct(**features),
          SpecStruct(**labels) if labels is not None else None,
          ModeKeys.EVAL, rng=None)
      variables = state.variables(use_avg_params=use_avg)
      outputs, _ = model.inference_network_fn(
          variables, features, labels, ModeKeys.EVAL, None)
      metrics = model.model_eval_fn(
          variables, features, labels, outputs, ModeKeys.EVAL)
      return dict(metrics)

    batch = self._batch_sharding()
    self._eval_step_fn = jax.jit(
        step, in_shardings=(self._state_sharding, batch, batch),
        out_shardings=NamedSharding(self.mesh, P()))
    return self._eval_step_fn

  def _compile_predict_step(self):
    if self._predict_step_fn is not None:
      return self._predict_step_fn
    model = self.model

    def step(state, features):
      features, _ = model.preprocessor.preprocess(
          SpecStruct(**features), None, ModeKeys.PREDICT, rng=None)
      outputs = model.predict_step(state, features)
      return dict(outputs)

    self._predict_step_fn = jax.jit(
        step, in_shardings=(self._state_sharding, self._batch_sharding()))
    return self._predict_step_fn

  # -- loops ----------------------------------------------------------------

  def train(self,
            input_generator: AbstractInputGenerator,
            max_train_steps: int,
            state: Optional[TrainState] = None,
            hooks: Sequence[Any] = (),
            shard_index: Optional[int] = None,
            num_shards: Optional[int] = None) -> TrainState:
    """Runs the training loop up to global step ``max_train_steps``.

    ``shard_index``/``num_shards`` select this host's slice of the input
    files; they default to the JAX process index/count, so multi-host
    training reads per-host shards with no extra wiring (the PER_HOST_V2
    contract, ref utils/tfdata.py:43-66).
    """
    # Everything between the call and the first iteration, under one
    # name: the first batch, the state, and whatever jax traces, lowers
    # or compiles for them (the compile.* records, signals.py).
    with span('train.startup') as startup:
      if shard_index is None:
        shard_index = jax.process_index()
      if num_shards is None:
        num_shards = jax.process_count()
      input_generator = provide_input_generator_with_model_information(
          input_generator, self.model, ModeKeys.TRAIN)
      # Not ``data.next``: the input metrics read that name over the window.
      with span('train.first_batch'):
        iterator = input_generator.create_dataset_iterator(
            mode=ModeKeys.TRAIN, shard_index=shard_index,
            num_shards=num_shards)
        features, labels = next(iterator)
      restore_s = 0.0
      if state is None:
        # For the recovery timeline: after a preemption the span's time is
        # the mesh/state rebuild + checkpoint restore phase.
        state = self.init_state(features, labels)
        restore_s = self._init_state_s
      step_fn = self._compile_train_step()
      base_rng = jax.device_put(jax.random.PRNGKey(self.seed + 1),
                                NamedSharding(self.mesh, P()))
      start_step = int(jax.device_get(state.step))
      startup.note(start_step=start_step)
      if start_step >= max_train_steps:
        _log('Checkpoint already at step %d >= max_train_steps %d; skipping.',
             start_step, max_train_steps)
        return state
      batch_size = int(
          jax.tree_util.tree_leaves(features.to_dict())[0].shape[0])
      for hook in hooks:
        hook.begin(self)
      # perf_counter, not time.time(): steps/sec and goodput must survive
      # wall-clock jumps (NTP step, DST) — the monotonic-deadline discipline
      # the reliability layer already follows (docs/reliability.md).
      t_last = time.perf_counter()
      steps_since_log = 0
      metrics = None
      step_i = start_step
      batch = (features, labels)
      # feed_depth > 1: route the train channel through the N-deep
      # pipelined feed — the producer thread decodes AND transfers batches
      # ahead while the device computes, so the loop below only ever waits
      # on an already-resident batch (the wait is the honest goodput
      # 'data' cost). The first batch — already drawn for init_state — is
      # chained back in so no data is skipped.
      pipelined = None
      if self._feed_depth > 1:
        import itertools

        from tensor2robot_tpu.data.device_feed import PipelinedFeed

        def _host_batch(pair):
          batch_features, batch_labels = pair
          return {'features': batch_features.to_dict(),
                  'labels': (batch_labels.to_dict()
                             if batch_labels is not None else None)}

        pipelined = PipelinedFeed(
            map(_host_batch, itertools.chain([batch], iterator)),
            self._put_batch, depth=self._feed_depth)
      rollback_budget = self._nan_rollback_budget
      host_nan_check = self._nan_policy in ('raise', 'rollback')
      completed = False
      # Goodput accounting: every loop second lands in exactly one of
      # productive / data / checkpoint / retry (docs/observability.md).
      tracker = GoodputTracker()
      self._last_goodput = tracker
      registry = get_registry()
      # Pre-register the well-known reliability counters: a dashboard must
      # see an explicit 0.0 on a clean run (an absent tag is
      # indistinguishable from broken wiring — the guarantee the pre-registry
      # quarantine export already gave).
      registry.counter(quarantine_lib.RECORDS_SKIPPED_COUNTER)
      registry.counter(quarantine_lib.FILES_ABANDONED_COUNTER)
      registry.counter('reliability/nan_rollbacks')
      registry.counter('reliability/preemptions')
      registry.gauge(watchdog_lib.RECOMPILE_GAUGE)
      # Forensics wiring: reports carry the live goodput split plus the
      # active tuned-config id (attributable perf), and the collective
      # stats come from relowering the step we just compiled.
      self._auto_profiler.context_fn = \
          lambda: {'goodput': tracker.fractions(),
                   'tuned_config': self.active_config_id,
                   'host': self.host_identity,
                   'pipeline': (self._xray.last_record
                                if self._xray is not None else None)}
      self._auto_profiler.hlo_text_fn = self._train_step_hlo
      telemetry = self.telemetry_logger
      if telemetry is not None:
        telemetry.log('run_start', step=start_step,
                      max_train_steps=int(max_train_steps),
                      batch_size=batch_size, nan_policy=self._nan_policy)
        telemetry.flush()
      # A pending recovery marker means the previous incarnation of this
      # model_dir died in a preemption: the first completed step closes
      # the recovery timeline (t2r.recovery.v1, fleet.py).
      pending_recovery = None
      if telemetry is not None:
        marker = fleet_lib.consume_recovery_marker(
            self.model_dir,
            process_index=self.host_identity.get('process_index'))
        if marker is not None:
          pending_recovery = (marker, restore_s, time.perf_counter())

    def commit_goodput(iter_start, data_s, ckpt_s, retry_s):
      # ``productive`` is the remainder, so the categories partition the
      # iteration's wall time exactly and fractions sum to 1.0.
      total = time.perf_counter() - iter_start
      tracker.add(goodput_lib.DATA, data_s)
      tracker.add(goodput_lib.CHECKPOINT, ckpt_s)
      tracker.add(goodput_lib.RETRY, retry_s)
      tracker.add(goodput_lib.PRODUCTIVE,
                  total - data_s - ckpt_s - retry_s)

    with graceful_shutdown() as shutdown:
      step_watcher = _StepWatcher(self.model.traced_step_metrics)
      try:
        while step_i < max_train_steps:
          iter_start = time.perf_counter()
          data_s = ckpt_s = retry_s = 0.0
          # One pass of the loop under one name, so that every second of
          # the training thread has one (its self time is the loop's own
          # overhead); closed in the finally below on every exit path.
          iteration = span('train.iteration', step=step_i + 1)
          iteration.__enter__()
          # try/finally, not explicit commit calls: an iteration that
          # exits via continue, preemption, OR an exception (NaN raise,
          # corruption budget, retry exhaustion — often the longest,
          # most interesting seconds) still lands in the accounting.
          try:
            report_path = self._auto_profiler.maybe_profile(step_i)
            if report_path is not None and telemetry is not None:
              telemetry.log('forensics', step=step_i, report=report_path)
              # The capture's roofline attribution also rides the jsonl
              # stream (compact t2r.roofline.v1 payload) so summarize/
              # tail/doctor see it without opening report files.
              try:
                with open(report_path, encoding='utf-8') as f:
                  roofline_record = json.load(f).get('roofline')
              except Exception:  # noqa: BLE001 — report is best-effort
                roofline_record = None
              if roofline_record:
                telemetry.log(
                    'roofline', step=step_i,
                    **roofline_lib.telemetry_payload(roofline_record))
              telemetry.flush()
            with span('data.put_batch', step=step_i + 1) as sp:
              if pipelined is not None:
                # Blocks only while the buffer is EMPTY — the producer
                # thread owns decode + transfer; transfer telemetry and
                # the data.stall site fire there (device_feed.py).
                device_batch = pipelined.get()
              else:
                features, labels = batch
                device_batch = self._put_batch(
                    {'features': features.to_dict(),
                     'labels': labels.to_dict() if labels is not None
                     else None})
            data_s += sp.elapsed
            force_nan = np.asarray(
                fault_injection.fires(fault_injection.SITE_STEP_NAN))
            # NOTE: the step span measures dispatch, not device compute —
            # jax returns before the XLA program finishes; host-side
            # blocking (donated-buffer backpressure) does land here. When
            # the step FINISHED on the device is the train.step_done event
            # of the same ``step``, recorded by the watcher thread.
            with span('train.step', step=step_i + 1):
              state, metrics = step_fn(state, device_batch['features'],
                                       device_batch['labels'], base_rng,
                                       force_nan)
            step_watcher.submit(step_i + 1, metrics)
            # The 'step.slow' injection site: a host-side stall the
            # watchdog must detect as a step-time regression — charged
            # to productive time exactly like a real slowdown would be.
            slow_s = fault_injection.slow_step_seconds()
            if slow_s > 0.0:
              time.sleep(slow_s)
            step_i += 1
            steps_since_log += 1
            if pending_recovery is not None:
              marker, marker_restore_s, resume_t0 = pending_recovery
              pending_recovery = None
              recovery = fleet_lib.build_recovery_record(
                  marker, marker_restore_s,
                  time.perf_counter() - resume_t0, step_i)
              registry.gauge(fleet_lib.RECOVERY_GAUGE).set(
                  recovery['preemption_recovery_seconds'])
              _log('Recovered from preemption at step %s in %.1f s '
                   '(save %.1fs, down %.1fs, restore %.1fs, first step '
                   '%.1fs).', recovery['preempted_step'],
                   recovery['preemption_recovery_seconds'],
                   recovery['phases']['emergency_save_s'],
                   recovery['phases']['downtime_s'],
                   recovery['phases']['restore_s'],
                   recovery['phases']['first_step_s'])
              if telemetry is not None:
                telemetry.log('recovery', step=step_i, **recovery)
                telemetry.flush()
            # The sentinel also fires on every step that is about to be
            # checkpointed (periodic or final): with nan_check_every_n_steps
            # > 1 an unvetted save could otherwise commit NaN params, and a
            # later rollback would restore the poison.
            if host_nan_check and (
                step_i % self._nan_check_every_n_steps == 0
                or step_i % self.save_checkpoints_steps == 0
                or step_i == max_train_steps):
              with span('train.nan_check') as sp:
                state, step_i, rolled_back = self._check_finite_loss(
                    state, metrics, step_i, rollback_budget)
              if rolled_back:
                # The whole check-and-restore, plus the re-fetch below, is
                # recovery overhead, not productive time.
                retry_s += sp.elapsed
                rollback_budget -= 1
                steps_since_log = 0
                t_last = time.perf_counter()
                if pipelined is None:
                  with span('data.next', step=step_i + 1) as sp:
                    batch = next(iterator)
                  retry_s += sp.elapsed
                continue
            if (step_i % self.log_every_n_steps == 0
                or step_i == max_train_steps):
              # The log window under one name: the fetch of the metrics
              # (a device sync) through the exports to telemetry.flush().
              with span('train.log_window', step=step_i):
                metrics = jax.device_get(dict(metrics))
                dt = time.perf_counter() - t_last
                examples_per_sec = batch_size * steps_since_log / max(dt, 1e-9)
                step_time_s = dt / max(steps_since_log, 1)
                self._throughput = (examples_per_sec, step_time_s)
                _log('step %d: loss=%s (%.1f examples/sec)', step_i,
                     metrics.get('loss'), examples_per_sec)
                # Performance-forensics sampling, BEFORE the exports so
                # the same window's watermarks/anomaly counters land in
                # this very TensorBoard write and telemetry record.
                signals_lib.sample_memory(registry)
                self._sample_recompiles(registry)
                # Live MFU ledger: gauges land BEFORE the watchdog pass so
                # mfu_regression sees this very window's utilization, and
                # before the exports so TensorBoard + telemetry carry it.
                self._publish_perf(registry, step_time_s)
                pipeline_record = None
                if self._xray is not None:
                  # X-ray before watchdog: a data-path incident should
                  # claim the capture under its pipeline kind (with the
                  # stage attribution in the trigger), not as the generic
                  # step_time_regression the same stall also causes.
                  pipeline_record, pipeline_anomalies = self._xray.observe(
                      step_i, examples=batch_size * steps_since_log,
                      window_seconds=dt,
                      goodput_seconds=tracker.seconds())
                  for anomaly in pipeline_anomalies:
                    _log('Pipeline X-ray anomaly: %s', anomaly.message)
                    if telemetry is not None:
                      telemetry.log('anomaly', step=step_i,
                                    anomaly=anomaly.kind,
                                    message=anomaly.message,
                                    detail=anomaly.detail)
                    self._auto_profiler.request_capture(
                        anomaly.kind, step_i, anomaly.detail)
                fleet_record = None
                if self.fleet_observer is not None:
                  # Fleet before watchdog: a straggler IS a step-time
                  # regression locally, but the fleet kind carries the
                  # host attribution — it should claim the capture.
                  fleet_record, fleet_anomalies = \
                      self.fleet_observer.observe(
                          step_i, step_time_s=step_time_s,
                          examples_per_sec=examples_per_sec,
                          productive_fraction=tracker.fractions().get(
                              'productive'))
                  for anomaly in fleet_anomalies:
                    _log('Fleet anomaly: %s', anomaly.message)
                    if telemetry is not None:
                      telemetry.log('anomaly', step=step_i,
                                    anomaly=anomaly.kind,
                                    message=anomaly.message,
                                    detail=anomaly.detail)
                    self._auto_profiler.request_capture(
                        anomaly.kind, step_i, anomaly.detail)
                if self._watchdog is not None:
                  for anomaly in self._watchdog.observe(
                      step_i, step_time_s, tracker.seconds()):
                    _log('Watchdog anomaly: %s', anomaly.message)
                    if telemetry is not None:
                      telemetry.log('anomaly', step=step_i,
                                    anomaly=anomaly.kind,
                                    message=anomaly.message,
                                    detail=anomaly.detail)
                    self._auto_profiler.request_capture(
                        anomaly.kind, step_i, anomaly.detail)
                writer = self.train_metrics_writer
                if writer is not None:
                  scalars = {k: float(np.mean(v)) for k, v in metrics.items()
                             if np.ndim(v) == 0}
                  scalars['global_step/sec'] = 1.0 / max(
                      dt / max(steps_since_log, 1), 1e-9)
                  scalars['examples/sec'] = examples_per_sec
                  # The unified telemetry pipeline: every registry counter/
                  # gauge/histogram-summary (quarantine, retries, rollbacks,
                  # span and inference latencies) plus the goodput split —
                  # tolerated damage and lost wall-clock are never invisible.
                  scalars.update(registry.scalars())
                  scalars.update(tracker.scalars())
                  writer.write_scalars(step_i, scalars)
                  writer.flush()
                if telemetry is not None:
                  snapshot = registry.snapshot()
                  # Gauges ride along so offline tooling (doctor) can
                  # compute across SAMPLES — "prefetch queue empty in 81%
                  # of samples" needs the series, not the last value.
                  telemetry.log('train', step=step_i,
                                loss=_json_scalar(metrics.get('loss')),
                                examples_per_sec=examples_per_sec,
                                step_time_s=step_time_s,
                                goodput=tracker.fractions(),
                                goodput_seconds=tracker.seconds(),
                                counters=snapshot['counters'],
                                gauges=snapshot['gauges'])
                  if pipeline_record is not None:
                    # The t2r.pipeline.v1 attribution record: gating stage
                    # + headroom vs. the device rate, per log window.
                    telemetry.log('pipeline', step=step_i, **pipeline_record)
                  if fleet_record is not None:
                    # The t2r.fleet.v1 federation record: per-host table,
                    # skew, gating host, fleet-min goodput, per window.
                    telemetry.log('fleet', step=step_i, **fleet_record)
                  # Window stats ride the heartbeat so a peer's
                  # FleetObserver can read the whole fleet's health from
                  # N tiny atomic files instead of N telemetry re-parses.
                  telemetry.heartbeat(
                      step_i, step_time_s=step_time_s,
                      examples_per_sec=examples_per_sec,
                      productive_fraction=tracker.fractions().get(
                          'productive'))
                  telemetry.flush()
              t_last = time.perf_counter()
              steps_since_log = 0
            if step_i % self.save_checkpoints_steps == 0:
              ckpt_t0 = time.perf_counter()
              self.save_checkpoint(state)
              ckpt_s += time.perf_counter() - ckpt_t0
            if hooks:
              with span('train.hooks', step=step_i):
                for hook in hooks:
                  hook.after_step(self, state, step_i, metrics)
            preempt_signum = None
            if shutdown.requested:
              preempt_signum = int(shutdown.signum)
            elif fault_injection.fires(fault_injection.SITE_HOST_PREEMPT):
              # The injected host-preemption site: the SAME end-to-end
              # path a SIGTERM drives, deterministically — what makes
              # the recovery timeline a measurable, testable quantity.
              preempt_signum = fault_injection.INJECTED_PREEMPT_SIGNUM
            if preempt_signum is not None:
              # Commit everything before re-raising: the restart resumes
              # from this exact step instead of the last periodic save.
              ckpt_t0 = time.perf_counter()
              self.save_checkpoint(state, force=True)
              self.checkpoint_manager.wait_until_finished()
              save_s = time.perf_counter() - ckpt_t0
              ckpt_s += save_s
              registry.counter('reliability/preemptions').inc()
              if telemetry is not None:
                telemetry.log('preempted', step=step_i,
                              signum=preempt_signum)
                telemetry.heartbeat(step_i)
                telemetry.flush()
                # Start the recovery clock: the resuming process (a
                # different pid) consumes this marker and emits the
                # t2r.recovery.v1 record at its first completed step.
                fleet_lib.write_recovery_marker(
                    self.model_dir, step_i, preempt_signum, save_s,
                    process_index=self.host_identity.get('process_index'))
              raise TrainingPreempted(preempt_signum, step_i)
            if step_i < max_train_steps and pipelined is None:
              with span('data.next', step=step_i + 1) as sp:
                batch = next(iterator)
              data_s += sp.elapsed
          finally:
            iteration.__exit__(None, None, None)
            commit_goodput(iter_start, data_s, ckpt_s, retry_s)
        completed = True
      finally:
        step_watcher.stop()
        if pipelined is not None:
          # Stop the producer on EVERY exit path — a live thread parked
          # inside the native loader's next() would otherwise race the
          # stream teardown below (and at interpreter exit).
          pipelined.close()
        # A dangling profiler trace breaks the next start_trace: close
        # it on EVERY exit path. Clean completion gets the full
        # forensics report; failure paths just stop the trace (the
        # report machinery must never mask the unwinding exception).
        if completed:
          report_path = self._auto_profiler.finish(step_i)
          if report_path is not None and telemetry is not None:
            telemetry.log('forensics', step=step_i, report=report_path)
        else:
          self._auto_profiler.abort()
        if not completed:
          # NonFiniteLossError means ``state`` holds the NaN-poisoned
          # update ('raise', or 'rollback' with the budget exhausted) —
          # committing it would make the poison the newest checkpoint
          # and wedge every restart. Flush writers only in that case.
          exc = sys.exc_info()[1]
          poisoned = isinstance(exc, NonFiniteLossError)
          if telemetry is not None and not isinstance(exc,
                                                      TrainingPreempted):
            # Preemption already wrote its own record above; everything
            # else gets a final abort marker (best-effort — the original
            # exception is unwinding and must stay the one raised).
            try:
              telemetry.log('run_abort', step=step_i,
                            error=type(exc).__name__,
                            goodput=tracker.fractions())
            except Exception as e:  # noqa: BLE001
              _log('Telemetry abort record failed: %s', e)
          self._flush_and_emergency_save(state, skip_save=poisoned)
    final_t0 = time.perf_counter()
    self.save_checkpoint(state, force=True)
    tracker.add(goodput_lib.CHECKPOINT, time.perf_counter() - final_t0)
    if telemetry is not None:
      telemetry.log('run_end', step=step_i, goodput=tracker.fractions(),
                    goodput_seconds=tracker.seconds())
      telemetry.heartbeat(step_i)
      telemetry.flush()
    for hook in hooks:
      hook.end(self, state)
    return state

  def _check_finite_loss(self, state, metrics, step_i: int,
                         rollback_budget: int):
    """Host-side non-finite-loss sentinel for 'raise'/'rollback'.

    Returns (state, step_i, rolled_back). Forces a device sync (the cost
    documented on ``nan_check_every_n_steps``).
    """
    loss = metrics.get('loss') if hasattr(metrics, 'get') else None
    if loss is None:
      return state, step_i, False
    loss_val = np.asarray(jax.device_get(loss))
    if np.all(np.isfinite(loss_val)):
      return state, step_i, False
    if self._nan_policy == 'raise':
      raise NonFiniteLossError(step_i, 'nan_policy="raise"')
    if rollback_budget <= 0:
      raise NonFiniteLossError(
          step_i, 'rollback budget exhausted after {} rollback(s)'.format(
              self._nan_rollback_budget))
    try:
      self.checkpoint_manager.wait_until_finished()
      self.checkpoint_manager.reload()
      latest = self.checkpoint_manager.latest_step()
      if latest is None:
        raise NonFiniteLossError(
            step_i, 'no committed checkpoint to roll back to')
      _log('Non-finite loss at step %d: rolling back to checkpoint %d '
           '(%d rollback(s) left).', step_i, latest, rollback_budget - 1)
      # The current (poisoned but shape-valid) state doubles as the
      # restore template: same tree, dtypes, and shardings.
      restored = self.checkpoint_manager.restore(state, step=latest)
    except NonFiniteLossError:
      raise
    except Exception as e:
      # A rollback that fails for ANY reason must still unwind as
      # NonFiniteLossError: the finally-block emergency save keys on that
      # type to know ``state`` is poisoned and must not be committed.
      raise NonFiniteLossError(
          step_i, 'rollback failed: {}'.format(e)) from e
    # Rollbacks were log-only before the telemetry layer; now they are a
    # first-class counter plus a jsonl event naming both steps.
    get_registry().counter('reliability/nan_rollbacks').inc()
    if self.telemetry_logger is not None:
      self.telemetry_logger.log('rollback', step=step_i,
                                restored_step=int(latest))
      self.telemetry_logger.flush()
    return restored, int(latest), True

  def _flush_and_emergency_save(self, state, skip_save: bool = False) -> None:
    """Failure-path cleanup: commit the state we have, flush writers.

    Best-effort by design — the original exception is already unwinding
    and must stay the one the caller sees. (If the failure happened
    inside the jitted step, ``state`` may hold donated buffers; the save
    then fails and is logged, never raised.) ``skip_save`` suppresses the
    checkpoint when the state is known-poisoned (non-finite loss).
    """
    if not skip_save:
      try:
        self.save_checkpoint(state, force=True)
        self.checkpoint_manager.wait_until_finished()
      except Exception as e:  # noqa: BLE001
        _log('Emergency checkpoint failed: %s', e)
    for writer in (self._train_writer, self._eval_writer, self._telemetry,
                   self._shared_telemetry):
      if writer is not None:
        try:
          writer.flush()
        except Exception as e:  # noqa: BLE001
          _log('Writer flush on failure path failed: %s', e)

  def evaluate(self,
               input_generator: AbstractInputGenerator,
               eval_steps: int,
               state: Optional[TrainState] = None) -> Dict[str, float]:
    """Averaged eval metrics over ``eval_steps`` batches (ref model_eval_fn)."""
    input_generator = provide_input_generator_with_model_information(
        input_generator, self.model, ModeKeys.EVAL)
    iterator = input_generator.create_dataset_iterator(mode=ModeKeys.EVAL)
    batch = next(iterator)
    if state is None:
      # The init batch is still scored below — no data is skipped.
      state = self.init_state(*batch, mode=ModeKeys.EVAL)
    self.last_eval_state = state
    eval_fn = self._compile_eval_step()
    totals: Dict[str, float] = {}
    count = 0
    last_batch = None
    for _ in range(eval_steps):
      if batch is None:
        try:
          batch = next(iterator)
        except StopIteration:
          break
      features, labels = batch
      batch = None
      device_batch = self._put_batch(
          {'features': features.to_dict(),
           'labels': labels.to_dict() if labels is not None else None},
          channel='eval')
      metrics = jax.device_get(
          eval_fn(state, device_batch['features'], device_batch['labels']))
      for key, value in metrics.items():
        totals[key] = totals.get(key, 0.0) + float(np.mean(value))
      count += 1
      last_batch = (features, labels)
    averaged = {k: v / max(count, 1) for k, v in totals.items()}
    writer = self.eval_metrics_writer
    if writer is not None:
      step = int(jax.device_get(state.step))
      writer.write_scalars(step, averaged)
      self._write_model_summaries(writer, state, last_batch, step)
      writer.flush()
    return averaged

  def _compile_summary_step(self):
    """Jitted (preprocess + forward) for add_summaries, like eval/predict."""
    if getattr(self, '_summary_step_fn', None) is not None:
      return self._summary_step_fn
    model = self.model
    use_avg = self.use_avg_params_for_eval

    def step(state, features, labels):
      features, labels = model.preprocessor.preprocess(
          SpecStruct(**features),
          SpecStruct(**labels) if labels is not None else None,
          ModeKeys.EVAL, rng=None)
      variables = state.variables(use_avg_params=use_avg)
      outputs, _ = model.inference_network_fn(
          variables, features, labels, ModeKeys.EVAL, None)
      return dict(features), (dict(labels) if labels is not None else None), \
          dict(outputs)

    batch = self._batch_sharding()
    self._summary_step_fn = jax.jit(
        step, in_shardings=(self._state_sharding, batch, batch))
    return self._summary_step_fn

  def _write_model_summaries(self, writer, state, batch, step: int) -> None:
    """Model-provided rich summaries for one eval batch (ref add_summaries).

    Runs one jitted forward pass on the last eval batch and hands host
    arrays to ``model.add_summaries``; whatever comes back lands in the
    eval events.
    """
    if batch is None or self.model.add_summaries.__func__ is \
        ModelInterface.add_summaries:
      return  # default no-op implementation: skip the extra forward pass
    try:
      raw_features, raw_labels = batch
      device_batch = self._put_batch(
          {'features': raw_features.to_dict(),
           'labels': raw_labels.to_dict() if raw_labels is not None
           else None},
          channel='summary')
      features, labels, outputs = self._compile_summary_step()(
          state, device_batch['features'], device_batch['labels'])
      host = jax.device_get
      summaries = self.model.add_summaries(
          host(features),
          host(labels) if labels is not None else None,
          host(outputs), ModeKeys.EVAL)
      if not summaries:
        return
      if summaries.get('scalars'):
        writer.write_scalars(step, summaries['scalars'])
      if summaries.get('images'):
        writer.write_images(step, summaries['images'])
      if summaries.get('histograms'):
        writer.write_histograms(step, summaries['histograms'])
    except Exception as e:  # noqa: BLE001 — summaries never fail an eval
      _log('add_summaries failed: %s', e)

  def predict(self, state: TrainState, features: SpecStruct
              ) -> Dict[str, np.ndarray]:
    """Numpy-in / numpy-out serving forward pass."""
    device_features = sharding_lib.shard_batch(
        SpecStruct(**features).to_dict()
        if not isinstance(features, SpecStruct) else features.to_dict(),
        self.mesh)
    return jax.device_get(self._compile_predict_step()(state,
                                                       device_features))

  # -- checkpoint/export ----------------------------------------------------

  def save_checkpoint(self, state: TrainState, force: bool = False) -> None:
    step = int(jax.device_get(state.step))
    # Settle our own in-flight async save first: reload() replaces orbax's
    # cached step list (which includes in-flight saves) with the on-disk
    # view (which does not), so reloading mid-commit would let the dedupe
    # below miss our own save and race it. This wait is also where a
    # transient failure of the PREVIOUS async commit surfaces — absorb it
    # (one lost intermediate checkpoint, logged) and let this save commit
    # the current, newer state instead of killing the run.
    try:
      self.checkpoint_manager.wait_until_finished()
      self._async_commit_failures = 0
    except Exception as e:  # noqa: BLE001 — async commit of an older step
      self._async_commit_failures = getattr(
          self, '_async_commit_failures', 0) + 1
      if self._async_commit_failures >= 3:
        # The filesystem is not blipping, it is down: losing every
        # intermediate checkpoint silently is worse than failing the run.
        raise
      _log('Async commit of a previous checkpoint failed (%s); '
           'continuing with the save of step %d (%d consecutive '
           'failure(s) tolerated before raising).', e, step,
           self._async_commit_failures)
    # Re-read disk before the dedupe check: a concurrent trainer (or a
    # previous incarnation of this one, pre-preemption) may have committed
    # this step already — re-saving would race its commit.
    self.checkpoint_manager.reload()
    if step in self.checkpoint_manager.all_steps():
      return
    if self.checkpoint_manager.save(step, state, force=force):
      # The t2r_assets contract: feature/label specs + global step live
      # next to the weights (ref utils/train_eval.py:296-370).
      assets_lib.write_t2r_assets_to_file(
          self.model.get_feature_specification(ModeKeys.TRAIN),
          self.model.get_label_specification(ModeKeys.TRAIN),
          step, os.path.join(self.model_dir,
                             assets_lib.EXTRA_ASSETS_DIRECTORY,
                             assets_lib.T2R_ASSETS_FILENAME))

  @property
  def last_throughput(self):
    return self._throughput

  def close(self) -> None:
    self.checkpoint_manager.wait_until_finished()
    self.checkpoint_manager.close()
    for writer in (self._train_writer, self._eval_writer, self._telemetry):
      if writer is not None:
        writer.close()
    if self._shared_telemetry is not None:
      # Shared stream: flush but never close — its owner (the elastic
      # driver) outlives this per-epoch trainer.
      self._shared_telemetry.flush()
    self._train_writer = self._eval_writer = self._telemetry = None


def _maybe_snapshot_config(model_dir: str,
                           filename: str = 'config_snapshot.gin',
                           operative: bool = False) -> None:
  """Writes the active config bindings into model_dir (the reference's
  GinConfigSaverHook, ref models/abstract_model.py:762-764)."""
  try:
    from tensor2robot_tpu.config import ginlike
    text = (ginlike.operative_config_str() if operative
            else ginlike.config_str())
    if text.strip():
      with open(os.path.join(model_dir, filename), 'w') as f:
        f.write(text)
  except Exception as e:  # noqa: BLE001 — snapshots must never kill a run
    _log('Config snapshot (%s) failed: %s', filename, e)


def train_eval_model(t2r_model: AbstractT2RModel,
                     model_dir: str,
                     input_generator_train: Optional[AbstractInputGenerator] = None,
                     input_generator_eval: Optional[AbstractInputGenerator] = None,
                     max_train_steps: int = 1000,
                     eval_steps: int = 100,
                     eval_throttle_steps: int = 500,
                     create_exporters_fn: Optional[Callable] = None,
                     train_hook_builders: Sequence[Any] = (),
                     mesh: Optional[Mesh] = None,
                     use_fsdp: bool = False,
                     tp_rules: Optional[Sequence[Tuple[str, Any]]] = None,
                     keep_checkpoint_max: int = 5,
                     save_checkpoints_steps: int = 500,
                     async_checkpoints: bool = True,
                     seed: int = 0,
                     eval_timeout_secs: float = 30.0,
                     write_metrics: bool = True,
                     eval_name: Optional[str] = None,
                     profile_steps: Optional[Sequence[int]] = None,
                     auto_profile: bool = True,
                     tuned_config: Optional[Any] = None,
                     use_compiled_artifacts: bool = False,
                     artifact_workload: Optional[str] = None
                     ) -> Dict[str, Any]:
  """Main entry point (ref utils/train_eval.py:404).

  Modes, mirroring the reference's Estimator dispatch:
    * train+eval: alternate train phases (``eval_throttle_steps`` apart)
      with ``eval_steps``-batch evals, exporters after each eval.
    * train-only (no eval generator): straight run to max_train_steps.
    * eval-only (no train generator): continuous eval — poll for new
      checkpoints until timeout (ref :552-594).
  Returns {'state', 'eval_metrics', 'trainer'}.
  """
  if t2r_model.is_device_tpu:
    # Host pipeline feeds bf16 directly (ref TPUPreprocessorWrapper).
    preprocessor = t2r_model.preprocessor
    if not isinstance(preprocessor, Bfloat16PreprocessorWrapper):
      t2r_model.set_preprocessor(Bfloat16PreprocessorWrapper(preprocessor))

  if eval_name is None and input_generator_eval is not None:
    # Multi-eval jobs route their events to eval_<name> dirs keyed by
    # TF_CONFIG.multi_eval_name (ref utils/train_eval.py:522-547).
    eval_name = getattr(input_generator_eval, 'multi_eval_name', None)
  trainer = Trainer(
      t2r_model, model_dir, mesh=mesh, use_fsdp=use_fsdp,
      tp_rules=tp_rules, seed=seed,
      keep_checkpoint_max=keep_checkpoint_max,
      save_checkpoints_steps=save_checkpoints_steps,
      async_checkpoints=async_checkpoints,
      write_metrics=write_metrics,
      eval_name=eval_name,
      profile_steps=profile_steps,
      auto_profile=auto_profile,
      tuned_config=tuned_config,
      use_compiled_artifacts=use_compiled_artifacts,
      artifact_workload=artifact_workload,
      # An eval-only job reads checkpoints a separate trainer process is
      # writing: it must never rename (quarantine) step dirs there.
      owns_checkpoint_dir=input_generator_train is not None)
  _maybe_snapshot_config(model_dir)

  hooks: List[Any] = []
  for builder in train_hook_builders:
    hooks.extend(builder.create_hooks(t2r_model, trainer))

  exporters = (create_exporters_fn(t2r_model) if create_exporters_fn
               else [])

  state = None
  eval_metrics: Dict[str, float] = {}

  def _run_exporters(current_state, metrics):
    for exporter in exporters:
      exporter.export(trainer, current_state, metrics)

  try:
    if input_generator_train is not None and input_generator_eval is not None:
      target = 0
      while target < max_train_steps:
        target = min(target + eval_throttle_steps, max_train_steps)
        state = trainer.train(input_generator_train, target, state=state,
                              hooks=hooks)
        eval_metrics = trainer.evaluate(input_generator_eval, eval_steps,
                                        state=state)
        _log('eval @ step %d: %s', target, eval_metrics)
        _run_exporters(state, eval_metrics)
    elif input_generator_train is not None:
      state = trainer.train(input_generator_train, max_train_steps,
                            hooks=hooks)
    elif input_generator_eval is not None:
      for step in checkpointing.checkpoints_iterator(
          model_dir, timeout_secs=eval_timeout_secs):
        try:
          # state=None: evaluate re-restores the newest checkpoint itself
          # (falling back to an older committed step when the newest is
          # half-written or GC'd, Trainer.init_state).
          eval_metrics = trainer.evaluate(input_generator_eval, eval_steps)
        except CHECKPOINT_SKIP_ERRORS as e:
          # No committed step was restorable right now — a concurrent
          # trainer may still be mid-commit; keep polling instead of
          # dying. The narrow tuple matters: a data-layer OSError from
          # the eval pipeline itself (missing dataset, corruption budget)
          # is NOT a checkpoint problem and propagates.
          _log('Continuous eval: checkpoint %d unrestorable (%s); '
               'skipping.', step, e)
          continue
        _log('continuous eval @ ckpt %d: %s', step, eval_metrics)
        state = trainer.last_eval_state
        _run_exporters(state, eval_metrics)
    else:
      raise ValueError('Provide at least one of train/eval input generators.')
  finally:
    _maybe_snapshot_config(model_dir, 'operative_config.gin', operative=True)
    trainer.close()
  return {'state': state, 'eval_metrics': eval_metrics, 'trainer': trainer}
