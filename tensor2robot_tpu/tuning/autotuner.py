"""The sweep engine: compile each candidate, time it, persist the winner.

Measurement method — chained block-free dispatch: each repetition
dispatches ``n_steps`` steps back to back and synchronizes ONCE at the
end, so host->device dispatch overlaps device compute exactly as it does
in the real training loop. Timing every step individually with
``block_until_ready`` would serialize dispatch against compute and
charge the per-dispatch round trip (most of the step for ms-scale
programs) to every candidate equally — hiding exactly the
scheduler-flag effects the sweep exists to find. The spread statistic is
max-min over the best ``reps - 1`` repetitions (one stalled repetition
cannot set it).

Candidates that fail to COMPILE (e.g. a curated flag the local jaxlib
does not know) are recorded with their error and excluded from winner
selection — a curated search space may safely name flags newer than the
installed toolchain. Winner selection is deterministic: lowest median,
ties broken by candidate order — except that a candidate whose
post-optimization HLO fingerprint equals the baseline's compiled to the
IDENTICAL program and can never beat baseline (its delta is noise by
construction).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from tensor2robot_tpu.tuning import cache as cache_lib
from tensor2robot_tpu.tuning import search_space

__all__ = ['StepCase', 'CandidateResult', 'SweepResult',
           'robust_median_spread', 'measure_chained', 'compile_with_config',
           'sweep']

_logv = None


def _log(msg: str, *args) -> None:
  global _logv
  if _logv is None:
    from absl import logging as _absl_logging  # deferred: absl optional
    _logv = _absl_logging.info
  _logv(msg, *args)


@dataclasses.dataclass
class StepCase:
  """What ``build(config)`` hands the sweep for one candidate.

  Attributes:
    jitted: the ``jax.jit`` object for the step (donation and shardings
      already applied by the caller).
    args: concrete example arguments for lower/compile and timing.
    advance: ``(out, args) -> args`` threading one call's output into the
      next call's arguments — REQUIRED when the step donates a buffer
      (the donated input is dead after the call); defaults to reusing
      ``args`` unchanged.
  """

  jitted: Any
  args: Tuple
  advance: Optional[Callable[[Any, Tuple], Tuple]] = None


@dataclasses.dataclass
class CandidateResult:
  config: search_space.CompileConfig
  compile_ok: bool
  error: str = ''
  compile_s: float = 0.0
  median_s: float = float('inf')
  spread_s: float = 0.0
  steps_per_s: float = 0.0
  # Post-optimization HLO fingerprint (hlo_analysis.program_fingerprint):
  # a candidate whose fingerprint equals the baseline's compiled to the
  # IDENTICAL program — its timing delta is noise and the flag is a
  # measured no-op for this workload.
  hlo_fingerprint: str = ''

  def record(self) -> Dict[str, Any]:
    return {
        'compile_ok': self.compile_ok,
        'error': self.error,
        'compile_s': round(self.compile_s, 3),
        'median_s': self.median_s if self.median_s != float('inf') else -1.0,
        'spread_s': self.spread_s,
        'steps_per_s': round(self.steps_per_s, 2),
        'hlo_fingerprint': self.hlo_fingerprint,
        'notes': self.config.notes,
    }


@dataclasses.dataclass
class SweepResult:
  workload: str
  key: str
  cache_hit: bool
  winner: Optional[search_space.CompileConfig]
  results: List[CandidateResult]
  entry: Dict[str, Any]


def robust_median_spread(times: Sequence[float]) -> Tuple[float, float]:
  """(median, max-min over the best ``len-1``) of raw repetition times.

  The dispersion statistic behind the sweep's ``spread_s``. Dropping the
  single worst repetition before taking the range keeps one stalled
  repetition from setting the field, while a genuinely unstable
  measurement (2+ slow reps) still reports a large spread.
  """
  times = sorted(times)
  median = times[len(times) // 2]
  kept = times[:-1] if len(times) > 2 else times
  spread = kept[-1] - kept[0] if len(kept) > 1 else 0.0
  return median, spread


def measure_chained(step_once: Callable[[], Any],
                    sync: Callable[[Any], Any],
                    n_steps: int,
                    reps: int,
                    timer: Callable[[], float] = time.perf_counter
                    ) -> Tuple[float, float]:
  """(median_s, robust_spread_s) over ``reps`` chains of ``n_steps``.

  ``step_once`` dispatches one step WITHOUT blocking and returns the
  output to chain/sync on; ``sync`` blocks on it. Spread per
  :func:`robust_median_spread` (one stalled repetition is dropped).
  """
  times = []
  for _ in range(max(1, reps)):
    t0 = timer()
    out = None
    for _ in range(max(1, n_steps)):
      out = step_once()
    sync(out)
    times.append(timer() - t0)
  return robust_median_spread(times)


def compile_with_config(jitted, args,
                        config: Optional[search_space.CompileConfig]):
  """AOT-compiles ``jitted`` for ``args`` under a config's XLA options.

  Lowers, then delegates to ``compile/artifact.compile_lowered`` — the
  ONE options-to-compile site every consumer (this helper, the sweep,
  the artifact store) shares. Returns the compiled executable (callable
  with the same arguments).
  """
  from tensor2robot_tpu.compile import artifact as artifact_lib

  return artifact_lib.compile_lowered(
      jitted.lower(*args),
      dict(config.compiler_options) if config else {})


def _default_sync(out):
  import jax

  return jax.block_until_ready(out)


def sweep(workload: str,
          build: Callable[[search_space.CompileConfig], StepCase],
          candidates: Optional[Sequence[search_space.CompileConfig]] = None,
          example_args: Optional[Any] = None,
          cache: Optional[cache_lib.ConfigCache] = None,
          cache_path: Optional[str] = None,
          n_steps: int = 8,
          reps: int = 3,
          warmup_steps: int = 2,
          timer: Callable[[], float] = time.perf_counter,
          sync: Optional[Callable[[Any], Any]] = None,
          force: bool = False,
          persist_artifacts: bool = True) -> SweepResult:
  """Runs (or short-circuits via cache) one compile-config sweep.

  Args:
    workload: cache-key name ('qtopt_critic_b512', ...).
    build: ``config -> StepCase``. Called once per candidate — model
      layout overrides happen here (the caller rebuilds its model from
      ``config.model_overrides``); compiler options are applied by the
      sweep itself at its lower+compile step.
    candidates: search space; defaults to
      ``search_space.candidate_configs()`` for the live backend.
    example_args: pytree whose shapes/dtypes key the cache. Defaults to
      the baseline candidate's ``StepCase.args`` — pass it explicitly to
      guarantee a cache HIT performs zero builds/compiles.
    cache / cache_path: where winners persist. ``cache=None`` with
      ``cache_path=None`` uses the default path; pass
      ``cache=ConfigCache(path)`` to pin a file.
    n_steps/reps/warmup_steps: chained-dispatch timing shape.
    timer/sync: injectable for tests (a stubbed timer makes winner
      selection a pure function of its scripted values).
    force: re-sweep even on a cache hit.
    persist_artifacts: serialize every successfully-measured candidate's
      executable into the unified ``CompiledArtifact`` store next to
      the cache (tensor2robot_tpu/compile) — the sweep already paid for
      each AOT compile, so persisting them makes the winner's
      executable FREE at train time (the trainer's artifact cold-start
      path loads it by the same workload/shapes/config key).

  Returns a :class:`SweepResult`; ``.winner`` is None only when every
  candidate failed to compile.
  """
  import jax

  if candidates is None:
    candidates = search_space.candidate_configs()
  candidates = list(candidates)
  if not candidates:
    raise ValueError('sweep needs at least one candidate config.')
  if sync is None:
    sync = _default_sync
  if cache is None:
    cache = cache_lib.ConfigCache(cache_path)

  device_kind = getattr(jax.devices()[0], 'device_kind', 'unknown')
  built_baseline: Optional[StepCase] = None
  if example_args is None:
    built_baseline = build(candidates[0])
    example_args = built_baseline.args
  signature = cache_lib.abstract_signature(example_args)
  key = cache_lib.cache_key(workload, signature, device_kind)

  if not force:
    entry = cache.lookup(key)
    if entry is not None:
      # winner_ok=False entries (every candidate failed to compile) hit
      # the cache — the sweep is not re-run every startup — but report
      # winner=None, honoring the '.winner is None only when all
      # candidates failed' contract; the stored config is a placeholder.
      winner = None
      if entry.get('winner_ok', True):
        winner = search_space.CompileConfig.from_dict(entry['winner'])
      _log('Tuning cache HIT for %s (%s): %s', workload, key,
           winner.config_id if winner else '<no-winner>')
      return SweepResult(workload=workload, key=key, cache_hit=True,
                         winner=winner, results=[], entry=entry)

  results: List[CandidateResult] = []
  for i, config in enumerate(candidates):
    result = CandidateResult(config=config, compile_ok=False)
    results.append(result)
    try:
      if i == 0 and built_baseline is not None:
        case = built_baseline
      else:
        case = build(config)
      t0 = time.perf_counter()
      # Lowered kept explicitly (not via compile_with_config): its text
      # hash is the program-identity component of the candidate's
      # artifact key — model_overrides candidates compile a DIFFERENT
      # program and must persist under a different key.
      from tensor2robot_tpu.compile import artifact as artifact_lib
      lowered = case.jitted.lower(*case.args)
      options = dict(config.compiler_options) if config else {}
      compiled = artifact_lib.compile_lowered(lowered, options)
      result.compile_s = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 — unknown flag, OOM, ...
      result.error = '{}: {}'.format(type(e).__name__, str(e)[:300])
      _log('Candidate %s failed to compile: %s', config.config_id,
           result.error)
      continue
    try:
      from tensor2robot_tpu.parallel import hlo_analysis
      result.hlo_fingerprint = hlo_analysis.program_fingerprint(compiled)
    except Exception:  # noqa: BLE001 — as_text unavailable on some paths
      pass
    advance = case.advance or (lambda out, args: args)
    state = {'args': case.args}

    def step_once(compiled=compiled, advance=advance, state=state):
      out = compiled(*state['args'])
      state['args'] = advance(out, state['args'])
      return out

    try:
      out = None
      for _ in range(max(0, warmup_steps)):
        out = step_once()
      if out is not None:
        sync(out)
      result.median_s, result.spread_s = measure_chained(
          step_once, sync, n_steps=n_steps, reps=reps, timer=timer)
      result.compile_ok = True
      result.steps_per_s = n_steps / max(result.median_s, 1e-12)
      _log('Candidate %s: %.2f steps/s (median %.4fs, spread %.4fs)',
           config.config_id, result.steps_per_s, result.median_s,
           result.spread_s)
      if persist_artifacts:
        # The sweep already paid for this AOT compile; persisting it
        # makes the eventual winner's executable a zero-compile load at
        # train time. Best-effort: a backend without serialization
        # still sweeps normally.
        try:
          from tensor2robot_tpu.compile import artifact as artifact_lib

          store = artifact_lib.ArtifactStore(cache.path)
          lowered_sha = artifact_lib.program_sha(lowered.as_text())
          artifact_key = artifact_lib.artifact_key(
              workload, signature, device_kind, lowered_sha=lowered_sha)
          store.persist(workload, artifact_key, config.config_id,
                        options, compiled, lowered_sha=lowered_sha,
                        fingerprint=result.hlo_fingerprint or None)
        except Exception as e:  # noqa: BLE001
          _log('Could not persist candidate %s artifact: %s',
               config.config_id, e)
    except Exception as e:  # noqa: BLE001 — runtime failure mid-timing
      result.error = '{}: {}'.format(type(e).__name__, str(e)[:300])
      result.compile_ok = False
      _log('Candidate %s failed at runtime: %s', config.config_id,
           result.error)

  ok = [r for r in results if r.compile_ok]
  # The fingerprint GOVERNS selection, not just the record: a candidate
  # that compiled to the baseline's identical program cannot beat it —
  # its timing delta is noise by construction, and caching it as the
  # winner would publish a provably inert flag as a live lever.
  base_fp = (results[0].hlo_fingerprint
             if results and results[0].compile_ok else '')
  contenders = [r for r in ok
                if r is results[0] or not base_fp
                or not r.hlo_fingerprint
                or r.hlo_fingerprint != base_fp]
  winner = min(contenders, key=lambda r: r.median_s).config \
      if contenders else None
  entry = {
      'schema_workload': workload,
      'device_kind': device_kind,
      'jax_version': jax.__version__,
      'signature_sha': key.rsplit('|', 1)[-1],
      'n_steps': n_steps,
      'reps': reps,
      'winner': (winner or candidates[0]).to_dict(),
      'winner_ok': winner is not None,
      'candidates': {r.config.config_id: r.record() for r in results},
  }
  cache.store(key, entry)
  return SweepResult(workload=workload, key=key, cache_hit=False,
                     winner=winner, results=results, entry=entry)
