"""The benchmark reads the library, never the reverse.

Nothing under ``tensor2robot_tpu/`` or ``bin/`` imports or names a
benchmark, a test or the chip smoke script, and no document cites a
benchmark script or a kernel rig the repository no longer has: every
statement about speed lives in ``PERF.md`` and ``PERF_LEDGER.jsonl``,
and the one benchmark is ``BENCHMARK.json`` + ``benchmark/``.
"""

import ast
import glob
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, 'tensor2robot_tpu')

# Modules above the library: what it must not import.
ABOVE_THE_LIBRARY = ('bench', 'benchmark', 'tests', 'chip_smoke')
# Names of the benchmark script and result files that are gone.
GONE_FROM_SOURCE = ('bench.py', 'BENCH_r')
GONE_FROM_DOCUMENTS = GONE_FROM_SOURCE + (
    'kernelbench', 'fleet_bench', 'pallas_wgrad')

LIBRARY_ENTRIES = sorted(
    name for name in os.listdir(PACKAGE) if name != '__pycache__')
DOCUMENTS = ['README.md'] + sorted(
    os.path.relpath(path, REPO_ROOT)
    for path in glob.glob(os.path.join(REPO_ROOT, 'docs', '*.md')))


# Source and what ships beside it; scripts under bin/ have no suffix.
SOURCE_SUFFIXES = ('.py', '.cc', '.gin', '.md', '')


def _files(root):
  """Every source file under ``root`` (or ``root`` itself)."""
  if os.path.isfile(root):
    return [root]
  found = []
  for directory, subdirs, names in os.walk(root):
    subdirs[:] = [d for d in subdirs if d != '__pycache__']
    found.extend(os.path.join(directory, name) for name in names
                 if os.path.splitext(name)[1] in SOURCE_SUFFIXES)
  return sorted(found)


def _is_python(path, text):
  return path.endswith('.py') or text.startswith('#!/usr/bin/env python')


def _imported_top_levels(tree):
  for node in ast.walk(tree):
    if isinstance(node, ast.Import):
      for alias in node.names:
        yield alias.name.split('.')[0]
    elif isinstance(node, ast.ImportFrom) and not node.level:
      yield (node.module or '').split('.')[0]


@pytest.mark.parametrize(
    'entry', [os.path.join('tensor2robot_tpu', name)
              for name in LIBRARY_ENTRIES] + ['bin'])
def test_library_does_not_know_a_benchmark(entry):
  files = _files(os.path.join(REPO_ROOT, entry))
  assert files, entry
  offences = []
  for path in files:
    with open(path, encoding='utf-8') as f:
      text = f.read()
    where = os.path.relpath(path, REPO_ROOT)
    offences.extend('{} names {}'.format(where, gone)
                    for gone in GONE_FROM_SOURCE if gone in text)
    if _is_python(path, text):
      offences.extend(
          '{} imports {}'.format(where, module)
          for module in _imported_top_levels(ast.parse(text, path))
          if module in ABOVE_THE_LIBRARY)
  assert not offences, offences


@pytest.mark.parametrize('document', DOCUMENTS)
def test_documents_cite_no_deleted_benchmark(document):
  with open(os.path.join(REPO_ROOT, document), encoding='utf-8') as f:
    text = f.read()
  cited = [gone for gone in GONE_FROM_DOCUMENTS if gone in text]
  assert not cited, '{} cites {}'.format(document, cited)
