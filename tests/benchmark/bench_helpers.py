"""Shared by the benchmark's tests: paths, and the one command as a child
process on the CPU with its caches under a temporary directory."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, 'tests', 'benchmark', 'tiny', 'BENCHMARK.json')
REAL = os.path.join(ROOT, 'BENCHMARK.json')
RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}

if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def run_cell(tmp_path, workload, trace=0, seconds=1.5, manifest=TINY,
             devices=1, cwd=ROOT, command=('benchmark/run.py',)):
  env = dict(os.environ)
  env.pop('PYTHONPATH', None)
  env.update({
      'JAX_PLATFORMS': 'cpu',
      'JAX_COMPILATION_CACHE_DIR': str(tmp_path / 'jax_cache'),
      'XLA_FLAGS': '--xla_force_host_platform_device_count={}'.format(
          devices),
      'TMPDIR': str(tmp_path),
      'BENCH_RUN': 'ignored',
  })
  args = [sys.executable, *command, '--workload', workload, '--seed',
          '3000000019', '--seconds', str(seconds), '--trace', str(trace)]
  if manifest is not None:
    args += ['--manifest', manifest]
  return subprocess.run(args, cwd=cwd, env=env, capture_output=True,
                        text=True, timeout=600)


def last_json_line(stdout):
  lines = [line for line in stdout.splitlines() if line.strip()]
  return json.loads(lines[-1])
