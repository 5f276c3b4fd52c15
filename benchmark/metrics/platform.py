"""Layer "platform and compiled-program store": compile requests and
persistent-cache hits before the window (set-up), and compiles inside the
window, which must be 0. From the program's ``jax/compiles`` and
``jax/compilation_cache_hits`` listeners."""


def _at(obs, when, name):
  counters = obs.get('counters')
  return counters[when].get(name) if counters else None


def window_compiles(obs):
  before, after = (_at(obs, w, 'jax/compiles') for w in ('before', 'after'))
  return None if before is None else after - before


METRICS = {
    'compile_requests': lambda obs: _at(obs, 'before', 'jax/compiles'),
    'compile_cache_hits': lambda obs: _at(obs, 'before',
                                          'jax/compilation_cache_hits'),
    'window_compiles': window_compiles,
}
