"""Persistent per-workload compile-config cache.

One JSON file maps cache keys to winning configs plus the sweep evidence
that picked them. The key is the tuple that changes the compiled program
or its performance profile:

  (workload name, abstract shapes/dtypes of the step arguments,
   device_kind, jax version)

so a batch-size change, a different chip generation, or a jax upgrade
each re-tunes instead of silently applying a stale winner, while an
identical workload gets a cache HIT and never pays for the sweep again.

File writes are atomic (tmp + rename) and last-writer-wins — the cache
is advisory perf metadata, not coordination state.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from typing import Any, Dict, Optional

import numpy as np

__all__ = ['ConfigCache', 'abstract_signature', 'cache_key',
           'default_cache_path', 'CACHE_PATH_ENV']

CACHE_PATH_ENV = 'T2R_TUNING_CACHE'
CACHE_SCHEMA = 't2r.tuning.v1'


def default_cache_path() -> str:
  """$T2R_TUNING_CACHE, else ``<compile-cache root>/t2r/tuning_cache.json``
  (``runtime.cache_root``: beside JAX's persistent cache, so one
  directory placed from outside carries every compiled program)."""
  env = os.environ.get(CACHE_PATH_ENV)
  if env:
    return env
  from tensor2robot_tpu import runtime

  return os.path.join(runtime.cache_root(), 't2r', 'tuning_cache.json')


def _leaf_signature(leaf) -> str:
  shape = tuple(getattr(leaf, 'shape', ()) or ())
  dtype = getattr(leaf, 'dtype', None)
  dtype_name = np.dtype(dtype).name if dtype is not None else type(
      leaf).__name__
  return '{}{}'.format(dtype_name, list(shape))


def abstract_signature(args) -> str:
  """Canonical string of the step arguments' shapes/dtypes.

  ``args`` is any pytree of arrays / ShapeDtypeStructs (jax required
  only if jax types are present — plain numpy works too, so cache tests
  never need a device).
  """
  import jax

  leaves_with_paths = jax.tree_util.tree_flatten_with_path(args)[0]
  parts = []
  for path, leaf in leaves_with_paths:
    key = ''.join(str(p) for p in path)
    parts.append('{}={}'.format(key, _leaf_signature(leaf)))
  return ';'.join(parts)


def cache_key(workload: str, signature: str, device_kind: str,
              jax_version: Optional[str] = None) -> str:
  """Stable key string; the signature is hashed so keys stay readable."""
  if jax_version is None:
    import jax
    jax_version = jax.__version__
  digest = hashlib.sha1(signature.encode('utf-8')).hexdigest()[:16]
  return '{}|{}|jax-{}|{}'.format(workload, device_kind, jax_version,
                                  digest)


class ConfigCache:
  """Load/store winner entries in one JSON cache file."""

  def __init__(self, path: Optional[str] = None):
    self.path = path or default_cache_path()

  def _read_all(self) -> Dict[str, Any]:
    try:
      with open(self.path, encoding='utf-8') as f:
        data = json.load(f)
    except (OSError, ValueError):
      return {}
    if not isinstance(data, dict) or data.get('schema') != CACHE_SCHEMA:
      return {}
    entries = data.get('entries')
    return entries if isinstance(entries, dict) else {}

  def lookup(self, key: str) -> Optional[Dict[str, Any]]:
    """The stored entry for ``key`` (winner config + sweep table), or
    None — a miss, meaning this (workload, shapes, chip, jax) tuple has
    never been tuned and the caller should sweep."""
    return self._read_all().get(key)

  def store(self, key: str, entry: Dict[str, Any]) -> str:
    """Atomically merges ``{key: entry}`` into the cache file."""
    entries = self._read_all()
    entry = dict(entry)
    entry.setdefault('stored_unix_s', time.time())  # wall-clock: record
    entries[key] = entry
    payload = {'schema': CACHE_SCHEMA, 'entries': entries}
    directory = os.path.dirname(self.path) or '.'
    os.makedirs(directory, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=directory, suffix='.tmp')
    try:
      with os.fdopen(fd, 'w', encoding='utf-8') as f:
        json.dump(payload, f, indent=2, sort_keys=True)
      os.replace(tmp, self.path)
    finally:
      if os.path.exists(tmp):
        os.unlink(tmp)
    return self.path
