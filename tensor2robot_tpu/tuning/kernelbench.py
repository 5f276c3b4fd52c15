"""Kernel microbench rig: XLA-fused baseline vs registered candidates.

ROADMAP item 1 says the next headline wins come from inside the device
step, and the roofline observatory (observability/roofline.py) names
WHICH op families are memory-bound — but landing a kernel against that
evidence needs a rig that times a candidate against the XLA baseline
under the SAME harness every published number already uses. This is
that rig:

  * A tiny registry of kernel entries. Each entry builds, for the
    current backend, a ``(candidate, baseline, flops, shape, dtype)``
    case — ``layers/pallas_wgrad.py`` (the round-4 measured record:
    23.7 ms vs XLA's 10.3 ms at [512,79,79,64] bf16 on v5e) is the
    first, so the rig reproduces a known verdict out of the box and a
    future kernel attempt starts by beating a number, not a feeling.
  * Timing is ``tuning/autotuner.measure_chained`` — chained dispatch,
    one block per repetition, ``robust_median_spread`` dispersion — the
    identical block-free discipline bench.py and the compile-config
    sweep publish with, so kernelbench rows are comparable with both.
  * Results are schema-locked ``KERNEL_BENCH_KEYS`` rows persisted
    (appended, bounded history) to ``kernelbench.json`` NEXT TO the
    tuning cache, so cross-round regressions are a file diff:
    ``bin/t2r_kernelbench`` is the CLI.

CPU backends run candidates in Pallas interpret mode at small default
shapes — the schema and the speedup_vs_xla plumbing are exercised
end-to-end everywhere, while % peak honestly degrades to the -1.0
sentinel when the device kind has no peaks-table entry.

Import-time jax-free (jax loads inside builders/run) so the gate
``bin/check_roofline_doctor`` can schema-lock ``KERNEL_BENCH_KEYS``
on any box.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from tensor2robot_tpu import runtime
from tensor2robot_tpu.tuning import autotuner
from tensor2robot_tpu.tuning import cache as cache_lib

__all__ = ['KERNEL_BENCH_KEYS', 'KERNEL_BENCH_SCHEMA', 'REGISTRY',
           'default_results_path', 'register', 'run', 'read_results']

KERNEL_BENCH_SCHEMA = 't2r.kernelbench.v1'

# One row per timed kernel; every row carries every key (numeric
# failures hold the -1.0 sentinel, the self-check convention bench.py
# established with E2E_WIRE_BENCH_KEYS). speedup_vs_xla > 1.0 means the
# candidate BEAT the fused XLA baseline.
KERNEL_BENCH_KEYS = (
    'kernel',
    'device_kind',
    'dtype',
    'shape',
    'ms',
    'ms_spread',
    'xla_ms',
    'xla_ms_spread',
    'gflops',
    'gflop_per_s',
    'xla_gflop_per_s',
    'pct_peak',
    'speedup_vs_xla',
)

_HISTORY_CAP = 50  # runs kept in kernelbench.json

# name -> builder(shape, dtype) returning the case dict below.
REGISTRY: Dict[str, Callable] = {}


def register(name: str):
  """Decorator adding a kernel case builder to the rig's registry.

  A builder takes ``(shape, dtype)`` (either may be None for the
  backend's default) and returns::

      {'candidate': zero-arg fn dispatching the candidate kernel,
       'baseline':  zero-arg fn dispatching the fused-XLA reference,
       'flops':     analytic flops of ONE invocation,
       'shape':     the concrete shape tuple used,
       'dtype':     the concrete dtype name used}

  Both fns must dispatch WITHOUT blocking and return the output (the
  chained harness syncs once per repetition).
  """
  def deco(fn):
    REGISTRY[name] = fn
    return fn
  return deco


def default_results_path() -> str:
  """kernelbench.json next to the tuning cache (same env override)."""
  return os.path.join(os.path.dirname(cache_lib.default_cache_path()),
                      'kernelbench.json')


@register('pallas_wgrad')
def _build_pallas_wgrad(shape: Optional[Tuple[int, ...]] = None,
                        dtype: Optional[str] = None) -> Dict[str, object]:
  """The 5x5 conv weight-gradient record kernel vs XLA's emitter.

  Device default is the measured-record configuration from the
  pallas_wgrad docstring ([512,79,79,64] bf16, 654 GFLOP); CPU runs
  interpret mode at a small shape (the rig is about plumbing there, not
  performance).
  """
  import jax
  import jax.numpy as jnp

  from tensor2robot_tpu.layers import pallas_wgrad

  on_cpu = not runtime.on_tpu()
  if shape is None:
    shape = (2, 8, 8, 8) if on_cpu else (512, 79, 79, 64)
  if dtype is None:
    dtype = 'float32' if on_cpu else 'bfloat16'
  b, h, w, c = shape
  batch_tile = 2 if b % 2 == 0 else 1
  rng = jax.random.PRNGKey(0)
  x = jax.random.normal(rng, shape, jnp.float32).astype(dtype)
  dy = jax.random.normal(jax.random.fold_in(rng, 1), shape,
                         jnp.float32).astype(dtype)

  def candidate():
    return pallas_wgrad.conv5x5_wgrad(x, dy, batch_tile=batch_tile,
                                      interpret=on_cpu)

  @jax.jit
  def _xla_wgrad(x_, dy_):
    def conv(w_):
      return jax.lax.conv_general_dilated(
          x_, w_, (1, 1), 'SAME',
          dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
    _, vjp = jax.vjp(
        conv, jnp.zeros((pallas_wgrad.KH, pallas_wgrad.KW, c, c),
                        x_.dtype))
    return vjp(dy_)[0]

  def baseline():
    return _xla_wgrad(x, dy)

  flops = 2.0 * b * h * w * c * c * pallas_wgrad.KH * pallas_wgrad.KW
  return {'candidate': candidate, 'baseline': baseline, 'flops': flops,
          'shape': tuple(shape), 'dtype': str(dtype)}


def _time_ms(fn, n_steps: int, reps: int) -> Tuple[float, float]:
  import jax

  # Warm up: compile + first dispatch stay out of the timed chains.
  jax.block_until_ready(fn())
  median_s, spread_s = autotuner.measure_chained(
      fn, jax.block_until_ready, n_steps, reps)
  return median_s / max(n_steps, 1) * 1e3, spread_s / max(n_steps, 1) * 1e3


def run(kernels: Optional[Sequence[str]] = None,
        shape: Optional[Tuple[int, ...]] = None,
        dtype: Optional[str] = None,
        n_steps: int = 4,
        reps: int = 3,
        out_path: Optional[str] = None,
        persist: bool = True) -> Dict[str, object]:
  """Times the selected kernels vs their XLA baselines; one run record.

  Returns ``{'schema', 'device_kind', 'n_steps', 'reps', 'results'}``
  where every results row carries every ``KERNEL_BENCH_KEYS`` key. A
  kernel whose build or timing raises still produces a row — numeric
  fields at -1.0 and the error message attached — so a broken candidate
  is a visible regression, not a silently missing line.
  """
  from tensor2robot_tpu.observability import roofline as roofline_lib
  from tensor2robot_tpu.observability import signals as signals_lib

  device_kind = str(signals_lib.host_identity().get('device_kind',
                                                    'unknown'))
  peaks = roofline_lib.device_peaks(device_kind)
  names = list(kernels) if kernels else sorted(REGISTRY)
  results: List[Dict[str, object]] = []
  for name in names:
    row: Dict[str, object] = {key: -1.0 for key in KERNEL_BENCH_KEYS}
    row.update(kernel=name, device_kind=device_kind, dtype='', shape=[])
    try:
      builder = REGISTRY[name]
      case = builder(shape=shape, dtype=dtype)
      ms, ms_spread = _time_ms(case['candidate'], n_steps, reps)
      xla_ms, xla_ms_spread = _time_ms(case['baseline'], n_steps, reps)
      flops = float(case['flops'])
      row.update(
          dtype=case['dtype'],
          shape=list(case['shape']),
          ms=round(ms, 4),
          ms_spread=round(ms_spread, 4),
          xla_ms=round(xla_ms, 4),
          xla_ms_spread=round(xla_ms_spread, 4),
          gflops=round(flops / 1e9, 6),
          gflop_per_s=round(flops / (ms / 1e3) / 1e9, 2) if ms > 0
          else -1.0,
          xla_gflop_per_s=round(flops / (xla_ms / 1e3) / 1e9, 2)
          if xla_ms > 0 else -1.0,
          pct_peak=round(flops / (ms / 1e3) / (peaks[0] * 1.0), 6)
          if (peaks and ms > 0) else -1.0,
          speedup_vs_xla=round(xla_ms / ms, 4) if ms > 0 else -1.0,
      )
    except Exception as e:  # noqa: BLE001 — a broken kernel is a result
      row['error'] = '{}: {}'.format(type(e).__name__, e)
    missing = [key for key in KERNEL_BENCH_KEYS if key not in row]
    if missing:
      row['schema_missing'] = missing
    results.append(row)
  record: Dict[str, object] = {
      'schema': KERNEL_BENCH_SCHEMA,
      'device_kind': device_kind,
      'n_steps': int(n_steps),
      'reps': int(reps),
      'results': results,
  }
  if persist:
    record['path'] = write_results(record, out_path)
  return record


def write_results(record: Dict[str, object],
                  out_path: Optional[str] = None) -> str:
  """Appends one run record to kernelbench.json (atomic, bounded)."""
  path = out_path or default_results_path()
  runs = read_results(path)
  runs.append(record)
  runs = runs[-_HISTORY_CAP:]
  os.makedirs(os.path.dirname(path) or '.', exist_ok=True)
  tmp = path + '.tmp'
  with open(tmp, 'w', encoding='utf-8') as f:
    json.dump(runs, f, indent=2, sort_keys=True)
  os.replace(tmp, path)
  return path


def read_results(path: Optional[str] = None) -> List[Dict[str, object]]:
  """All persisted run records (oldest first); [] when absent/torn."""
  path = path or default_results_path()
  try:
    with open(path, encoding='utf-8') as f:
      runs = json.load(f)
    return runs if isinstance(runs, list) else []
  except (OSError, ValueError):
    return []


def format_results(record: Dict[str, object]) -> str:
  """Human table for the CLI: one line per kernel row."""
  lines = ['kernelbench [{}] n_steps={} reps={}'.format(
      record.get('device_kind'), record.get('n_steps'),
      record.get('reps'))]
  for row in record.get('results') or []:
    if row.get('error'):
      lines.append('  {:<16} ERROR {}'.format(row.get('kernel'),
                                              row.get('error')))
      continue
    pct = row.get('pct_peak')
    lines.append(
        '  {:<16} {:>9.3f} ms (±{:.3f})  xla {:>9.3f} ms  '
        '{:>9.1f} GFLOP/s  {}  speedup_vs_xla {:.2f}x'.format(
            row.get('kernel'), row.get('ms'), row.get('ms_spread'),
            row.get('xla_ms'), row.get('gflop_per_s'),
            '{:.1%} peak'.format(pct) if isinstance(pct, float) and
            pct >= 0 else 'peak n/a',
            row.get('speedup_vs_xla')))
  return '\n'.join(lines)
