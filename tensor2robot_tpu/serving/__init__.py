"""Batched, AOT-compiled, SLO-tracked policy inference (ISSUE 8).

The serving subsystem the predictors feed: ``PolicyServer`` coalesces
concurrent ``SelectAction`` requests into padded megabatches
(`batcher.py`), sheds load when the queue saturates (`admission.py`),
executes through an executable that was AOT-compiled at startup from the
``tuning/`` cache winner — and persisted, so warm restarts skip even the
startup compile (`artifact.py`) — hot-swaps checkpoints via atomically
versioned parameter snapshots with zero dropped requests, and reports
per-request latency against an explicit SLO into the telemetry layer
(`server.py`; ``t2r_telemetry doctor`` + ``bin/check_serving_slo`` read
it back). ``bin/t2r_serve`` is the entry point; `frontend.py` is its
stdlib HTTP/JSON door. Contract + quickstart: docs/serving_contract.md.
"""

from tensor2robot_tpu.serving.admission import (
    AdmissionController,
    RequestRejected,
    SERVING_REJECTED_COUNTER,
)
from tensor2robot_tpu.serving.artifact import (
    ServingExecutable,
    artifact_path_for_key,
    load_or_compile,
)
from tensor2robot_tpu.serving.batcher import (
    DeadlineBatcher,
    PendingRequest,
    pad_batch,
    split_outputs,
)
from tensor2robot_tpu.serving.fleet import (
    SERVING_FLEET_RECORD_KIND,
    SERVING_FLEET_SCHEMA,
    ServingFleet,
    ServingFleetConfig,
    replica_host_meta,
    router_host_meta,
)
from tensor2robot_tpu.serving.router import (
    FleetRouter,
    HttpReplicaHandle,
    LocalReplicaHandle,
    ReplicaHandle,
    RoutedResult,
    RouterConfig,
)
from tensor2robot_tpu.serving.server import (
    PolicyServer,
    ServeResult,
    ServingConfig,
    SERVING_RECORD_KIND,
)

__all__ = [
    'AdmissionController',
    'DeadlineBatcher',
    'FleetRouter',
    'HttpReplicaHandle',
    'LocalReplicaHandle',
    'PendingRequest',
    'PolicyServer',
    'ReplicaHandle',
    'RequestRejected',
    'RoutedResult',
    'RouterConfig',
    'SERVING_FLEET_RECORD_KIND',
    'SERVING_FLEET_SCHEMA',
    'SERVING_RECORD_KIND',
    'SERVING_REJECTED_COUNTER',
    'ServeResult',
    'ServingConfig',
    'ServingExecutable',
    'ServingFleet',
    'ServingFleetConfig',
    'artifact_path_for_key',
    'load_or_compile',
    'pad_batch',
    'split_outputs',
    'replica_host_meta',
    'router_host_meta',
]
