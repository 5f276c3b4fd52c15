"""AOT serving executables: compile at startup, never at request time.

Since ISSUE 13 this module is a THIN ADAPTER over the unified
``tensor2robot_tpu/compile`` artifact pipeline (ROADMAP item 5 — this
file was its first slice, now generalized): the server's batch program
resolves through the same ``CompiledArtifact`` store the trainer, the
autotuner sweep, the RL acting step, and forensics use. What stays
serving-specific:

  * the tuning-cache WINNER resolution happens here (through the shared
    ``resolve_cache_winner`` guard — winners carrying model overrides
    or ``winner_ok=False`` placeholder entries are refused, never
    half-applied), so a re-swept cache whose winner moved forces one
    fresh startup compile under the new config instead of silently
    serving the old program;
  * the cache entry is stamped with the persisted executable's path
    (``'serialized_executable'``), keeping the tuning evidence and the
    program it picked in one place;
  * artifacts are keyed WITHOUT the lowered-program sha
    (``program_key=False``): serving workload names pin the program
    (``serving_qtopt_cem_b8``), and a warm restart must deserialize
    without paying even the trace.

The contract is unchanged: a warm restart deserializes and compiles
NOTHING; a cold start (or a stale/corrupt artifact) falls back to one
AOT compile and re-persists; either way there is nothing left to
compile when the first request arrives.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from tensor2robot_tpu.compile import artifact as artifact_lib
from tensor2robot_tpu.reliability.logutil import log_warning
from tensor2robot_tpu.tuning import cache as cache_lib

__all__ = ['ServingExecutable', 'artifact_path_for_key', 'load_or_compile',
           'ARTIFACT_SCHEMA', 'ARTIFACT_DIRNAME']

# The unified schema/dirname (kept exported: bin/t2r_serve and tests
# name them through this module).
ARTIFACT_SCHEMA = artifact_lib.ARTIFACT_SCHEMA
ARTIFACT_DIRNAME = artifact_lib.ARTIFACT_DIRNAME


@dataclasses.dataclass
class ServingExecutable:
  """One ready-to-call serving program + its provenance.

  ``from_cache`` True means the executable was DESERIALIZED (warm
  restart, zero XLA compiles this startup); False means one startup AOT
  compile happened. ``config_id`` names the tuning winner applied
  ('baseline' when the workload was never tuned or the winner carries
  model overrides the serving layer cannot re-apply).
  """

  executable: Any
  key: str
  workload: str
  config_id: str
  from_cache: bool
  path: str


def artifact_path_for_key(cache_path: str, key: str,
                          config_id: str = 'baseline') -> str:
  """Where the unified store keeps this key's executable — alongside
  the cache file, so one directory carries both the tuning evidence and
  the executable it picked."""
  return artifact_lib.ArtifactStore(cache_path).path_for(key, config_id)


def load_or_compile(workload: str,
                    jitted,
                    example_args,
                    cache: Optional[cache_lib.ConfigCache] = None,
                    cache_path: Optional[str] = None,
                    persist: bool = True,
                    telemetry: Optional[Any] = None) -> ServingExecutable:
  """The server-startup path: deserialize, else AOT-compile + persist.

  Args:
    workload: cache-key name, e.g. ``serving_qtopt_cem_b8``.
    jitted: the ``jax.jit`` object for the batch program.
    example_args: concrete or abstract (ShapeDtypeStruct) argument
      pytree — fixes the ONE shape the executable serves.
    cache / cache_path: the tuning cache holding this workload's winner;
      defaults to the process tuning cache.
    persist: serialize a freshly-compiled executable back to disk (and
      stamp its path into the cache entry when one exists).
    telemetry: optional TelemetryLogger for ``kind='compile'`` records.
  """
  import jax

  if cache is None:
    cache = cache_lib.ConfigCache(cache_path)
  device_kind = getattr(jax.devices()[0], 'device_kind', 'unknown')
  signature = cache_lib.abstract_signature(example_args)
  key = cache_lib.cache_key(workload, signature, device_kind)

  # Resolve the CURRENT winner first, through the shared guard: a
  # persisted executable is only valid under the config the cache names
  # today, and a winner the trainer would refuse (model overrides,
  # winner_ok=False) is refused here identically.
  entry = cache.lookup(key)
  winner, _ = artifact_lib.resolve_cache_winner(entry)

  artifact = artifact_lib.load_or_compile(
      workload, jitted, example_args, config=winner, cache=cache,
      persist=persist, program_key=False, telemetry=telemetry)
  if not artifact.from_cache and entry is not None:
    previous_config = entry.get('serialized_executable_config_id')
    if previous_config is not None and \
        previous_config != artifact.config_id:
      # The startup compile was caused by WINNER DRIFT, not a cold key:
      # a re-swept cache moved the winner, superseding the previously
      # stamped executable. Judged by the STAMPED config id — never by
      # path comparison, which a failed persist, a relocated cache dir,
      # or a path-scheme migration would each misfire. A surprise
      # multi-second warm-restart compile must be attributable from the
      # logs alone.
      log_warning(
          'Serving workload %r recompiled under config %r: the tuning '
          'cache winner moved (previously persisted under %r; '
          'superseded executable: %s).', workload, artifact.config_id,
          previous_config, entry.get('serialized_executable'))
    if artifact.path:
      # The cache entry gains a pointer to its executable (+ the config
      # it was built under) — the tuning evidence and the program it
      # picked stay joined.
      entry = dict(entry)
      entry['serialized_executable'] = artifact.path
      entry['serialized_executable_config_id'] = artifact.config_id
      cache.store(key, entry)
  return ServingExecutable(executable=artifact.executable,
                           key=artifact.key, workload=workload,
                           config_id=artifact.config_id,
                           from_cache=artifact.from_cache,
                           path=artifact.path)
