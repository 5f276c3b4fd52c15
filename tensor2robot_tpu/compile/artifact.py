"""One ``CompiledArtifact`` pipeline: persisted executables for every
compile site (ROADMAP item 5).

Five subsystems independently lower/compile/fingerprint the same step
functions — trainer jit, the autotuner AOT sweep, serving startup,
the RL acting step, and forensics' HLO relowering — and every process
pays the same multi-second XLA compile on every cold start. This module
is the one abstraction they all resolve through:

  * **CompiledArtifact** — a ready-to-call executable plus its full
    provenance: the lowered (StableHLO) program hash, the compiler
    options it was built under, in/out layouts, the post-optimization
    HLO text + fingerprint, and the
    ``jax.experimental.serialize_executable`` payload.
  * **ArtifactStore** — an atomic (tmp + rename) on-disk store living
    next to the tuning cache (``<cache dir>/artifacts/``), keyed like
    the tuning cache — ``workload | device_kind | jax-version |
    shapes-sha`` — extended with the candidate ``config_id`` and (for
    program-keyed callers) the lowered-program sha, so two different
    models sharing argument shapes can NEVER load each other's
    executable.
  * **load_or_compile** — the one cold-start path: deserialize the
    persisted executable when the key matches (zero backend compiles —
    deserialization fires no ``jax/compiles`` events, measured), else
    one AOT compile that is persisted for next time. A miss, a stale
    payload (jax upgrade, different chip), or a corrupt file each
    degrade to the stock compile — never to a dead process.

**Fingerprint drift** is the first-class signal this unification buys:
when the store holds a readable payload for the exact key being compiled
and the fresh program's post-optimization fingerprint differs, the same
(workload, shapes, chip, jax version, config) tuple no longer lowers to
the same program — a toolchain moved underneath a pinned version string,
or lowering went nondeterministic. That is a
``compile/fingerprint_drift`` counter increment, one ``anomaly``
telemetry record naming the workload, and a doctor WARNING/CRITICAL —
instead of something the watchdog infers from a recompile gauge after
the fact.

Import-light by contract: jax is imported inside functions only, so the
jax-free readers (doctor, ``bin/check_artifact_doctor``) can import the
schema/key vocabulary below.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Dict, Optional, Tuple

from tensor2robot_tpu.observability import registry as registry_lib
from tensor2robot_tpu.reliability.logutil import log_warning

__all__ = [
    'ARTIFACT_SCHEMA', 'ARTIFACT_DIRNAME', 'COMPILE_RECORD_KIND',
    'FINGERPRINT_DRIFT', 'ARTIFACT_HITS_COUNTER', 'ARTIFACT_MISSES_COUNTER',
    'DRIFT_COUNTER', 'CompiledArtifact',
    'ArtifactStore', 'artifact_key', 'program_sha', 'compile_lowered',
    'resolve_cache_winner', 'load_or_compile',
]

ARTIFACT_SCHEMA = 't2r.compiled_artifact.v1'
ARTIFACT_DIRNAME = 'artifacts'

# Telemetry vocabulary (jax-free — doctor/CLI/CI gates import these).
COMPILE_RECORD_KIND = 'compile'
FINGERPRINT_DRIFT = 'fingerprint_drift'
ARTIFACT_HITS_COUNTER = 'compile/artifact_hits'
ARTIFACT_MISSES_COUNTER = 'compile/artifact_misses'
DRIFT_COUNTER = 'compile/fingerprint_drift'


@dataclasses.dataclass
class CompiledArtifact:
  """One ready-to-call executable + the provenance it was built under.

  ``from_cache`` True means the executable was DESERIALIZED from the
  store (zero backend compiles this load); False means one AOT compile
  happened (and was persisted when ``persist``). ``hlo_text`` is the
  POST-OPTIMIZATION compiled HLO — what forensics' collective analysis
  consumes, so a capture can be attributed without relowering (one
  extra XLA compile) or calling into a deserialized executable.
  """

  executable: Any
  key: str
  workload: str
  config_id: str
  from_cache: bool
  path: str
  fingerprint: str = ''
  hlo_text: Optional[str] = None
  compiler_options: Optional[Dict[str, Any]] = None
  compile_s: float = 0.0
  outcome: str = 'compiled'
  drift: bool = False


def program_sha(lowered_text: str) -> str:
  """Short stable sha of a LOWERED (StableHLO) program.

  The program-identity component of the artifact key: two different
  models whose step arguments share shapes lower to different programs,
  and this hash is what keeps their artifacts from colliding. Lowering
  is a trace, not an XLA compile — it fires no ``jax/compiles`` events,
  so program-keyed cold starts stay zero-compile.
  """
  return hashlib.sha1(lowered_text.encode('utf-8')).hexdigest()[:16]


def artifact_key(workload: str, signature: str, device_kind: str,
                 jax_version: Optional[str] = None,
                 lowered_sha: Optional[str] = None) -> str:
  """``workload|device_kind|jax-<v>|<shapes-sha>[|hlo-<sha>]`` — the
  tuning-cache key tuple, optionally extended with the lowered-program
  hash for callers whose workload name alone does not pin the program."""
  from tensor2robot_tpu.tuning import cache as cache_lib

  key = cache_lib.cache_key(workload, signature, device_kind,
                            jax_version=jax_version)
  if lowered_sha:
    key += '|hlo-' + lowered_sha
  return key


def compile_lowered(lowered, options: Optional[Dict[str, Any]] = None):
  """The ONE place compiler options meet ``lowered.compile``.

  Every consumer that already holds a ``lowered`` object — this
  module's ``load_or_compile``, the autotuner sweep, and the legacy
  trainer hook via ``autotuner.compile_with_config`` — compiles through
  here, so a change to HOW options are applied cannot silently diverge
  the sweep's measured candidates from the executables later loaded by
  key.
  """
  options = dict(options or {})
  if options:
    return lowered.compile(compiler_options=options)
  return lowered.compile()


def resolve_cache_winner(entry) -> Tuple[Optional[Any], str]:
  """The ONE stale-winner guard for every artifact consumer.

  ``entry`` is a tuning-cache entry (or None). Returns
  ``(config, reason)`` where ``config`` is the applicable
  ``CompileConfig`` or None (baseline compile) and ``reason`` names why:

    * ``'no_entry'`` — never tuned (cache miss);
    * ``'winner_ok_false'`` — the sweep measured NOTHING (every
      candidate failed to compile); the stored config is a placeholder,
      not a winner;
    * ``'model_overrides'`` — the measured winner included layout
      overrides, which apply only at model construction; compiling just
      its flags here would run an unmeasured hybrid attributed to a
      config that never ran (the trainer's PR-5 refusal, now shared);
    * ``'invalid_winner'`` — the stored winner dict does not parse;
    * ``'ok'`` — ``config`` is applicable as-is.

  Both the trainer's cache hook and the serving/artifact load path call
  this, so the half-apply rules can never drift apart again.
  """
  from tensor2robot_tpu.tuning import search_space

  if not entry:
    return None, 'no_entry'
  if not entry.get('winner_ok', True):
    return None, 'winner_ok_false'
  try:
    winner = search_space.CompileConfig.from_dict(entry['winner'])
  except (KeyError, TypeError, ValueError):
    return None, 'invalid_winner'
  if winner.model_overrides:
    return None, 'model_overrides'
  return winner, 'ok'


class ArtifactStore:
  """Atomic on-disk store of serialized executables next to the cache.

  One directory (``<cache dir>/artifacts/``) carries the tuning
  evidence AND every executable compiled under it. Files are one pickle
  per (key, config_id) pair, written tmp + rename so two processes
  racing ``load_or_compile`` on the same key produce one valid file and
  never a torn one (the tuning-cache discipline).

  The store is SIZE-CAPPED (``max_bytes``, default 4 GiB — the same
  bounded-on-disk discipline as telemetry rotation): superseded
  artifacts — old jax versions, re-swept candidates whose winner moved,
  changed shapes — are keyed to paths nothing loads anymore, so
  without a cap a long-lived dev/CI machine accumulates orphaned
  multi-MB executables forever. Each persist prunes oldest-first by
  mtime past the cap, and each HIT touches its file, so mtime is a
  live LRU signal and an actively-loaded artifact outlives dead ones.
  """

  def __init__(self, cache_path: Optional[str] = None,
               max_bytes: int = 4 * 2**30):
    if cache_path is None:
      from tensor2robot_tpu.tuning import cache as cache_lib

      cache_path = cache_lib.default_cache_path()
    self.cache_path = cache_path
    self.max_bytes = int(max_bytes)
    self.directory = os.path.join(os.path.dirname(cache_path) or '.',
                                  ARTIFACT_DIRNAME)

  def _prune(self, keep_path: str) -> None:
    """Evicts oldest-mtime artifacts until the store fits max_bytes.

    ``keep_path`` (the file just written) is never evicted — a single
    artifact larger than the whole cap must still persist. Best-effort:
    a racing process deleting the same file is fine.
    """
    try:
      entries = []
      for name in os.listdir(self.directory):
        if not name.endswith('.pkl'):
          continue
        path = os.path.join(self.directory, name)
        try:
          stat = os.stat(path)
        except OSError:
          continue
        entries.append((stat.st_mtime, stat.st_size, path))
      total = sum(size for _, size, _ in entries)
      if total <= self.max_bytes:
        return
      for _, size, path in sorted(entries):
        if path == keep_path:
          continue
        try:
          os.unlink(path)
        except OSError:
          continue
        total -= size
        if total <= self.max_bytes:
          return
    except OSError:  # noqa: PERF203 — directory vanished mid-walk
      pass

  def path_for(self, key: str, config_id: str = 'baseline') -> str:
    digest = hashlib.sha1('{}|{}'.format(key, config_id).encode(
        'utf-8')).hexdigest()[:20]
    return os.path.join(self.directory, digest + '.pkl')

  def read_payload(self, path: str) -> Optional[Dict[str, Any]]:
    """The raw payload dict, or None on missing/corrupt/foreign files."""
    if not os.path.exists(path):
      return None
    try:
      with open(path, 'rb') as f:
        payload = pickle.load(f)
      if not isinstance(payload, dict) or \
          payload.get('schema') != ARTIFACT_SCHEMA:
        return None
      return payload
    except Exception as e:  # noqa: BLE001 — torn/corrupt artifact
      log_warning('Artifact %s unreadable (%s); treating as a miss.',
                  path, e)
      return None

  def persist(self, workload: str, key: str, config_id: str,
              compiler_options: Optional[Dict[str, Any]],
              compiled, lowered_sha: Optional[str] = None,
              fingerprint: Optional[str] = None,
              hlo_text: Optional[str] = None) -> str:
    """Serializes one compiled executable; '' when the backend cannot.

    Best-effort by contract (a backend without PJRT serialization still
    trains/serves, it just cold-compiles next time). The payload is
    self-describing: everything ``load`` validates rides inside it.
    """
    try:
      import jax
      from jax.experimental import serialize_executable

      if hlo_text is None:
        try:
          hlo_text = compiled.as_text()
        except Exception:  # noqa: BLE001 — text is evidence, not contract
          hlo_text = None
      if fingerprint is None and hlo_text:
        from tensor2robot_tpu.parallel import hlo_analysis

        fingerprint = hlo_analysis.program_fingerprint(hlo_text)
      serialized, in_tree, out_tree = \
          serialize_executable.serialize(compiled)
      payload = {
          'schema': ARTIFACT_SCHEMA,
          'key': key,
          'workload': workload,
          'config_id': config_id,
          'compiler_options': dict(compiler_options or {}),
          'device_kind': getattr(jax.devices()[0], 'device_kind',
                                 'unknown'),
          'jax_version': jax.__version__,
          'lowered_sha': lowered_sha,
          'fingerprint': fingerprint or '',
          'hlo_text': hlo_text,
          'in_layouts': str(compiled.input_formats),
          'out_layouts': str(compiled.output_formats),
          # The devices the program was compiled for, in assignment
          # order: load() binds the executable to exactly these.
          'device_ids': [
              d.id for d in
              compiled._executable._unloaded_executable.device_list],
          'serialized': serialized,
          'in_tree': in_tree,
          'out_tree': out_tree,
      }
      path = self.path_for(key, config_id)
      os.makedirs(self.directory, exist_ok=True)
      fd, tmp = tempfile.mkstemp(dir=self.directory, suffix='.tmp')
      try:
        with os.fdopen(fd, 'wb') as f:
          pickle.dump(payload, f)
        os.replace(tmp, path)
      finally:
        if os.path.exists(tmp):
          os.unlink(tmp)
      self._prune(keep_path=path)
      return path
    except Exception as e:  # noqa: BLE001 — e.g. backend without PJRT
      log_warning('Could not persist compiled artifact for %s: %s',
                  workload, e)
      return ''

  def load(self, key: str, config_id: str = 'baseline'
           ) -> Tuple[Optional[Any], Optional[Dict[str, Any]], str]:
    """``(executable, payload, reason)`` for one key.

    ``executable`` is the deserialized ready-to-call program or None;
    ``payload`` is the readable payload even when deserialization
    failed (the drift-detection evidence: its ``fingerprint`` is what
    the fresh compile is compared against); ``reason`` one of
    ``'hit' | 'miss' | 'stale' | 'exec_load_failed'``.
    """
    path = self.path_for(key, config_id)
    payload = self.read_payload(path)
    if payload is None:
      return None, None, 'miss'
    import jax

    device_kind = getattr(jax.devices()[0], 'device_kind', 'unknown')
    if (payload.get('key') != key
        or payload.get('config_id') != config_id
        or payload.get('device_kind') != device_kind
        or payload.get('jax_version') != jax.__version__):
      # The key embeds device/jax already; these field checks catch a
      # tampered or hash-collided payload — stale, recompile.
      return None, payload, 'stale'
    # deserialize_and_load binds to EVERY backend device unless told
    # otherwise; a program compiled for one device then rejects its
    # arguments on a host with more. A payload naming a device this
    # process lacks (or none: written before the field existed) is stale.
    by_id = {d.id: d for d in jax.devices()}
    device_ids = payload.get('device_ids')
    if not device_ids or any(i not in by_id for i in device_ids):
      return None, payload, 'stale'
    try:
      from jax.experimental import serialize_executable

      executable = serialize_executable.deserialize_and_load(
          payload['serialized'], payload['in_tree'], payload['out_tree'],
          execution_devices=[by_id[i] for i in device_ids])
      try:
        os.utime(path)  # LRU touch: a loaded artifact outlives dead ones
      except OSError:
        pass
      return executable, payload, 'hit'
    except Exception as e:  # noqa: BLE001 — jaxlib that cannot load it
      log_warning('Artifact %s failed to deserialize (%s); recompiling.',
                  path, e)
      return None, payload, 'exec_load_failed'


def _record_compile(telemetry, registry, workload: str, key: str,
                    config_id: str, outcome: str, reason: str,
                    compile_s: float, fingerprint: str, drift: bool,
                    path: str) -> None:
  """Counters always; one ``kind='compile'`` record (+ one ``anomaly``
  on drift) when a telemetry logger rides along."""
  counter = (ARTIFACT_HITS_COUNTER if outcome == 'hit'
             else ARTIFACT_MISSES_COUNTER)
  registry.counter_family(counter, ('workload',)).series(workload).inc()
  if drift:
    registry.counter(DRIFT_COUNTER).inc()
  if telemetry is None:
    return
  try:
    telemetry.log(COMPILE_RECORD_KIND, workload=workload, key=key,
                  config_id=config_id, outcome=outcome, reason=reason,
                  compile_ms=round(compile_s * 1e3, 2),
                  fingerprint=fingerprint, drift=drift, path=path)
    if drift:
      telemetry.log(
          'anomaly', anomaly=FINGERPRINT_DRIFT,
          message='compiled-program fingerprint drifted for workload '
                  '{!r}: same artifact key, different post-optimization '
                  'HLO'.format(workload),
          detail={'workload': workload, 'key': key,
                  'config_id': config_id})
    telemetry.flush()
  except Exception as e:  # noqa: BLE001 — telemetry must not kill a load
    log_warning('compile telemetry record failed: %s', e)


# In-process executable memo: one LOADED executable per artifact file,
# shared by every later load_or_compile of the same key in this process.
# An elastic rebuild at the same world shape (elastic/driver.py builds
# a fresh Trainer per plan epoch) should not re-deserialize a program
# object this process already holds. NOTE the memo only skips the
# DESERIALIZATION: with program_key=True (the trainer default) the key
# itself needs the lowered-program sha, so each load still pays one
# trace before the memo is consulted — a rebind is trace + lookup, not
# a pure dictionary hit. An entry is valid only while its backing FILE is
# the one it was loaded from: every (re-)persist lands via tmp +
# os.replace, which changes the inode, so the (st_ino, st_size) stamp
# detects a re-persist by any process (winner moved, drift) while
# staying immune to the LRU utime touches concurrent hitters apply to a
# live file.
_MEMO_LOCK = threading.Lock()
_LOADED_MEMO: Dict[str, Tuple[Optional[Tuple[int, int]],
                              'CompiledArtifact']] = {}


def _file_stamp(path: str) -> Optional[Tuple[int, int]]:
  try:
    stat = os.stat(path)
    return (stat.st_ino, stat.st_size)
  except OSError:
    return None


def _memo_get(path: str) -> Optional['CompiledArtifact']:
  with _MEMO_LOCK:
    entry = _LOADED_MEMO.get(path)
  if entry is None:
    return None
  stamp, artifact = entry
  if stamp is not None and _file_stamp(path) != stamp:
    with _MEMO_LOCK:
      _LOADED_MEMO.pop(path, None)
    return None
  return artifact


def _memo_put(path: str, artifact: 'CompiledArtifact') -> None:
  if not path:
    return  # never persisted: nothing another process could move
  with _MEMO_LOCK:
    _LOADED_MEMO[path] = (_file_stamp(path), artifact)


def load_or_compile(workload: str,
                    jitted,
                    example_args,
                    config: Optional[Any] = None,
                    cache: Optional[Any] = None,
                    cache_path: Optional[str] = None,
                    store: Optional[ArtifactStore] = None,
                    persist: bool = True,
                    program_key: bool = True,
                    telemetry: Optional[Any] = None,
                    registry: Optional[Any] = None) -> CompiledArtifact:
  """The one cold-start path every compile site resolves through.

  Args:
    workload: artifact-key name (``'qtopt_critic_b512'``,
      ``'serving_qtopt_cem_b8'``, ``'rl_act_16'`` ...).
    jitted: the ``jax.jit`` object for the step (shardings/donation
      already applied by the caller).
    example_args: concrete or abstract (ShapeDtypeStruct) argument
      pytree — fixes the ONE shape the executable serves.
    config: an applicable tuning ``CompileConfig`` (pass the result of
      :func:`resolve_cache_winner` for cache-resolved winners — the
      shared guard has already refused half-applicable ones) or None
      for the baseline compile.
    cache / cache_path / store: where artifacts persist; defaults to
      the process tuning cache's directory.
    persist: serialize a freshly-compiled executable back to the store.
    program_key: include the lowered-program sha in the key. Costs one
      trace (never an XLA compile) per load and makes the key collision
      -proof across models sharing shapes — the default for the trainer
      and the RL acting step. Serving passes False: its workload names
      pin the program and its warm restart must not pay the trace.
    telemetry: optional TelemetryLogger for ``kind='compile'`` records
      (and the ``fingerprint_drift`` anomaly record).
  """
  import jax

  registry = registry or registry_lib.get_registry()
  if store is None:
    if cache is not None:
      store = ArtifactStore(cache.path)
    else:
      store = ArtifactStore(cache_path)
  from tensor2robot_tpu.tuning import cache as cache_lib

  device_kind = getattr(jax.devices()[0], 'device_kind', 'unknown')
  signature = cache_lib.abstract_signature(example_args)
  lowered = None
  lowered_sha = None
  if program_key:
    lowered = jitted.lower(*example_args)
    lowered_sha = program_sha(lowered.as_text())
  key = artifact_key(workload, signature, device_kind,
                     lowered_sha=lowered_sha)
  config_id = config.config_id if config is not None else 'baseline'
  options = dict(config.compiler_options) if config is not None else {}

  memo_path = store.path_for(key, config_id)
  memoized = _memo_get(memo_path)
  if memoized is not None:
    # Same process, same key, unchanged file: hand back the executable
    # object already loaded — zero compiles, zero deserializations
    # (the program-keyed trace above is still paid; see the memo note).
    # ``drift`` resets: it describes the LOAD EVENT that set it (a
    # fresh compile disagreeing with a stored fingerprint), not the
    # executable — replaying it would keep a recovered workload
    # drift-flagged forever.
    artifact = dataclasses.replace(memoized, from_cache=True,
                                   outcome='hit', drift=False)
    _record_compile(telemetry, registry, workload, key, config_id,
                    'hit', 'memo', 0.0, artifact.fingerprint, False,
                    memo_path)
    return artifact

  executable, payload, reason = store.load(key, config_id)
  if executable is not None:
    artifact = CompiledArtifact(
        executable=executable, key=key, workload=workload,
        config_id=config_id, from_cache=True,
        path=store.path_for(key, config_id),
        fingerprint=payload.get('fingerprint', ''),
        hlo_text=payload.get('hlo_text'),
        compiler_options=payload.get('compiler_options'),
        outcome='hit')
    _record_compile(telemetry, registry, workload, key, config_id,
                    'hit', reason, 0.0, artifact.fingerprint, False,
                    artifact.path)
    _memo_put(artifact.path, artifact)
    return artifact

  # Miss / stale / dead executable: one AOT compile, then persist.
  if lowered is None:
    lowered = jitted.lower(*example_args)
  t0 = time.perf_counter()
  compiled = compile_lowered(lowered, options)
  compile_s = time.perf_counter() - t0
  try:
    hlo_text = compiled.as_text()
  except Exception:  # noqa: BLE001 — text is evidence, not contract
    hlo_text = None
  fingerprint = ''
  if hlo_text:
    try:
      from tensor2robot_tpu.parallel import hlo_analysis

      fingerprint = hlo_analysis.program_fingerprint(hlo_text)
    except Exception:  # noqa: BLE001
      pass

  # Fingerprint drift: the store held a READABLE payload for this exact
  # key+config (same shapes, chip, jax version) whose post-optimization
  # fingerprint differs from what the toolchain just built. The key said
  # "same program"; the compiler disagreed — first-class signal.
  drift = bool(
      payload is not None and reason == 'exec_load_failed'
      and payload.get('fingerprint') and fingerprint
      and payload['fingerprint'] != fingerprint)
  if drift:
    log_warning(
        'Fingerprint drift for workload %r (key %s): stored %s, '
        'freshly compiled %s — same key now lowers to a different '
        'program.', workload, key, payload.get('fingerprint'),
        fingerprint)

  path = ''
  if persist:
    path = store.persist(workload, key, config_id, options, compiled,
                         lowered_sha=lowered_sha, fingerprint=fingerprint,
                         hlo_text=hlo_text)
  artifact = CompiledArtifact(
      executable=compiled, key=key, workload=workload,
      config_id=config_id, from_cache=False, path=path,
      fingerprint=fingerprint, hlo_text=hlo_text,
      compiler_options=options, compile_s=compile_s,
      outcome='compiled', drift=drift)
  _record_compile(telemetry, registry, workload, key, config_id,
                  'compiled', reason, compile_s, fingerprint, drift,
                  path)
  _memo_put(path, artifact)
  return artifact
