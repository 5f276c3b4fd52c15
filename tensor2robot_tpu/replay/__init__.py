"""Sharded, packed-wire distributed replay (ISSUE 11, ROADMAP item 2).

The organ between fleet-scale collection and the learner: episodes
arrive as per-example packed records (`wire.py` — the ``coef_packed``
wire of PR 9, per example instead of per batch), stay packed at rest in
per-shard ring/reservoir stores (`store.py`, ~14k examples/GB of host
RAM), and leave as megabatches byte-identical in signature to a
native-loader disk batch — assembled by a sampling front-end built on
the serving batcher + admission machinery (`service.py`), shipped over
a stdlib HTTP door (`frontend.py`) or in-process, retried with backoff
on the client (`client.py`), and fed to the trainer through
``SparseCoefFeed``/``PipelinedFeed`` unchanged (`feed.py`). Sampling is
uniform or prioritized (`sampling.py`); corrupt appends charge
per-shard quarantine budgets; per-shard occupancy/append/sample/evict
rates land as ``t2r.replay.v1`` telemetry the doctor (and the jax-free
``bin/check_replay_doctor`` gate) diagnose offline.

``bin/t2r_replay`` is the entry point; ``--replay_endpoint`` on
bin/run_t2r_trainer points a learner at it. Contract + quickstart:
docs/replay.md. Everything here imports without jax.
"""

from tensor2robot_tpu.replay.client import (
    LocalReplayClient,
    ReplayClient,
    ReplayUnavailable,
)
from tensor2robot_tpu.replay.feed import (
    ReplayBatchIterator,
    ReplayInputGenerator,
)
from tensor2robot_tpu.replay.sampling import (
    POLICIES,
    PrioritizedPolicy,
    SamplePolicy,
    UniformPolicy,
    make_policy,
)
from tensor2robot_tpu.replay.service import (
    REPLAY_RECORD_KIND,
    REPLAY_RECORD_SCHEMA,
    ReplayConfig,
    ReplayEmpty,
    ReplayService,
    SampleBatch,
)
from tensor2robot_tpu.replay.store import RETENTIONS, ShardStore
from tensor2robot_tpu.replay.wire import (
    ReplayWireError,
    assemble_batch,
    decode_example,
    encode_example,
    split_batch,
)

__all__ = [
    'LocalReplayClient',
    'POLICIES',
    'PrioritizedPolicy',
    'REPLAY_RECORD_KIND',
    'REPLAY_RECORD_SCHEMA',
    'RETENTIONS',
    'ReplayBatchIterator',
    'ReplayClient',
    'ReplayConfig',
    'ReplayEmpty',
    'ReplayInputGenerator',
    'ReplayService',
    'ReplayUnavailable',
    'ReplayWireError',
    'SampleBatch',
    'SamplePolicy',
    'ShardStore',
    'UniformPolicy',
    'assemble_batch',
    'decode_example',
    'encode_example',
    'make_policy',
    'split_batch',
]
