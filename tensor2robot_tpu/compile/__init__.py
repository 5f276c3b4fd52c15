"""Unified compiled-artifact pipeline (ROADMAP item 5).

One ``CompiledArtifact`` abstraction — lowered-program hash + compiler
options + layouts + post-optimization fingerprint + serialized
executable — behind an atomic on-disk store keyed like the tuning
cache, so trainers, the autotuner sweep, serving, the RL acting step,
and forensics all cold-start from (and persist to) the same place.
Import-light: jax loads lazily inside functions, never at import.
"""

from tensor2robot_tpu.compile.artifact import (
    ARTIFACT_DIRNAME,
    ARTIFACT_HITS_COUNTER,
    ARTIFACT_MISSES_COUNTER,
    ARTIFACT_SCHEMA,
    COMPILE_RECORD_KIND,
    DRIFT_COUNTER,
    FINGERPRINT_DRIFT,
    ArtifactStore,
    CompiledArtifact,
    artifact_key,
    compile_lowered,
    load_or_compile,
    program_sha,
    resolve_cache_winner,
)

__all__ = [
    'ARTIFACT_DIRNAME',
    'ARTIFACT_HITS_COUNTER',
    'ARTIFACT_MISSES_COUNTER',
    'ARTIFACT_SCHEMA',
    'COMPILE_RECORD_KIND',
    'DRIFT_COUNTER',
    'FINGERPRINT_DRIFT',
    'ArtifactStore',
    'CompiledArtifact',
    'artifact_key',
    'compile_lowered',
    'load_or_compile',
    'program_sha',
    'resolve_cache_winner',
]
