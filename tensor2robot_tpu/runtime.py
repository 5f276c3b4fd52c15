"""What this process runs on, and where its compiled programs are kept.

Two questions every entry point asks, each answered in ONE place:

  * :func:`on_tpu` — the only "am I on the device" predicate. Kernels
    choose compiled-vs-interpret from it, ``attention_mode='auto'`` and
    the tuning search space choose their TPU branch from it, and the
    entry points choose bf16 models and full sizes from it. The system is
    written for the TPU: any other platform (the CPU of the sandbox and
    of the tests) is "not the device".
  * :func:`enable_compile_cache` — JAX's persistent compilation cache and
    the repo's own stores (tuning cache, ``CompiledArtifact`` pickles)
    under one root that can be placed from outside:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
    The directory is part of the cache key, so it is never derived from
    ``tempfile``, a pid or the time.

jax is imported inside the functions: ``tuning/cache.py`` and the
jax-free readers import this module for :func:`cache_root` alone.
"""

from __future__ import annotations

import os

__all__ = ['CACHE_DIR_ENV', 'on_tpu', 'cache_root', 'enable_compile_cache']

CACHE_DIR_ENV = 'JAX_COMPILATION_CACHE_DIR'

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def on_tpu() -> bool:
  """True iff JAX's default backend is the TPU."""
  import jax

  return jax.default_backend() == 'tpu'


def cache_root() -> str:
  """``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``."""
  return os.environ.get(CACHE_DIR_ENV) or os.path.join(_CHECKOUT,
                                                       '.jax_cache')


def enable_compile_cache() -> str:
  """Turns on JAX's persistent compilation cache; returns its directory.

  Call before the first compile. With ``$JAX_COMPILATION_CACHE_DIR`` set,
  JAX already reads the directory from the environment and nothing is set
  in code; otherwise the fixed in-checkout path is configured.
  """
  root = cache_root()
  if not os.environ.get(CACHE_DIR_ENV):
    import jax

    jax.config.update('jax_compilation_cache_dir', root)
  return root
