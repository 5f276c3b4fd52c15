"""Offline compile-config autotuning for jitted train steps.

A systematic sweep of ``xla_tpu_*`` scheduler/vmem/fusion flags and conv
``dimension_numbers``/layout variants on a train step, as a reusable
tool:

  * ``search_space``      — curated, bounded candidate sets per backend
                            (compiler options + model layout overrides);
  * ``autotuner``         — compile each candidate via per-compile
                            ``compiler_options``, time it with warmup +
                            chained block-free dispatch (one sync at the
                            end, so dispatch overlap is measured rather
                            than lost), pick the winner deterministically;
  * ``cache``             — persist the winner to a JSON config cache
                            keyed by (workload, abstract shapes/dtypes,
                            device_kind, jax version) so production runs
                            pay for the sweep once.

``trainer/train_eval.py`` (the ``tuned_config`` arg) loads cache entries
at startup and applies them to the train-step compile; forensics reports
carry the active config id so a regression is attributable to the config
that produced it.
"""

from tensor2robot_tpu.tuning.autotuner import (
    CandidateResult,
    SweepResult,
    measure_chained,
    sweep,
)
from tensor2robot_tpu.tuning.cache import (
    ConfigCache,
    abstract_signature,
    cache_key,
    default_cache_path,
)
from tensor2robot_tpu.tuning.search_space import (
    CompileConfig,
    candidate_configs,
)

__all__ = [
    'CandidateResult',
    'CompileConfig',
    'ConfigCache',
    'SweepResult',
    'abstract_signature',
    'cache_key',
    'candidate_configs',
    'default_cache_path',
    'measure_chained',
    'sweep',
]
