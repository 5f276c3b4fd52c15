"""BENCHMARK.json (the real one and the tests' tiny one) is well formed by
the builder's contract, and everything it names is found by name."""

import os
import re

import pytest

import bench_helpers as helpers
from benchmark.harness import cells

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
MANIFESTS = [helpers.REAL, helpers.TINY]
IDS = ['real', 'tiny']


@pytest.fixture(params=MANIFESTS, ids=IDS)
def manifest(request):
  return request.param, cells.load_json(request.param)


def _line(text, limit=200):
  return (isinstance(text, str) and 1 <= len(text) <= limit and
          '\n' not in text and '\t' not in text)


def test_top_level_keys(manifest):
  _, m = manifest
  assert set(m) == {'command', 'paths', 'run_seconds', 'configs',
                    'workloads', 'end_to_end', 'per_layer'}
  assert isinstance(m['run_seconds'], int) and 1 <= m['run_seconds'] <= 51
  assert 1 <= len(m['command']) <= 32 and all(map(_line, m['command']))
  assert 1 <= len(m['paths']) <= 16


def test_the_full_check_fits_with_24_cells():
  m = cells.load_json(helpers.REAL)
  runs = 2 + 14 * 24
  assert runs * (m['run_seconds'] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units(manifest):
  _, m = manifest
  names = []
  for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
    for entry in m[group]:
      assert NAME.match(entry['name']), entry['name']
      names.append((group in ('end_to_end', 'per_layer'), entry['name']))
  assert len(names) == len(set(names)), 'a name is used twice'
  for entry in m['end_to_end'] + m['per_layer']:
    assert UNIT.match(entry['unit']), entry
    assert entry['better'] in ('lower', 'higher')
    assert entry['source'] in SOURCES
  for entry in m['workloads']:
    assert NAME.match(entry['config']) and NAME.match(entry['traffic'])
    assert entry['chips'] in (1, 2, 4)
    assert _line(entry['why'])
  for entry in m['configs']:
    assert _line(entry['source']) and _line(entry['why'])
    assert len(entry['reduced']) <= 16


def test_entries_have_just_the_contracts_keys(manifest):
  _, m = manifest
  for entry in m['configs']:
    assert set(entry) == {'name', 'source', 'file', 'reduced', 'why'}
  for entry in m['workloads']:
    assert set(entry) == {'name', 'config', 'traffic', 'chips', 'why'}
  for entry in m['end_to_end']:
    assert set(entry) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
    assert entry['source'] in ('host_clock', 'device_trace')
    assert 0 < entry['bound'] <= 0.1
  for entry in m['per_layer']:
    assert set(entry) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
    assert _line(entry['layer'])


def test_every_cell_is_found_by_name_and_reports_enough(manifest):
  path, m = manifest
  readers = cells.metric_readers()
  used_configs = set()
  pairs = set()
  for entry in m['workloads']:
    cell = cells.Cell(path, entry['name'])
    used_configs.add(cell.config_name)
    assert (cell.config_name, cell.traffic_name) not in pairs
    pairs.add((cell.config_name, cell.traffic_name))
    assert os.path.exists(os.path.join(
        helpers.ROOT, 'benchmark', 'harness', cell.traffic['kind'] + '.py'))
    end_to_end = cell.metric_names('end_to_end')
    per_layer = cell.metric_names('per_layer')
    assert 'setup_s' in end_to_end and len(end_to_end) >= 2
    assert per_layer
    for name in end_to_end + per_layer:
      assert name in readers, (
          'no reader file declares ' + name)
    moves = {p['name']: p['moves'] for p in m['per_layer']}
    for name in per_layer:
      assert moves[name] in end_to_end, (
          '{} moves {}, which cell {} does not report'.format(
              name, moves[name], cell.name))
  assert used_configs == {c['name'] for c in m['configs']}


def test_metric_workloads_name_cells(manifest):
  _, m = manifest
  cell_names = {w['name'] for w in m['workloads']}
  for entry in m['end_to_end'] + m['per_layer']:
    assert set(entry.get('workloads', cell_names)) <= cell_names


def test_at_most_a_quarter_of_the_cells_take_four_chips():
  m = cells.load_json(helpers.REAL)
  four = sum(w['chips'] == 4 for w in m['workloads'])
  assert four <= max(1, len(m['workloads']) // 4)


def test_real_configuration_files_state_what_the_contract_asks():
  m = cells.load_json(helpers.REAL)
  files = set()
  for entry in m['configs']:
    assert entry['file'].startswith(tuple(p + '/' for p in m['paths']))
    assert entry['file'] not in files
    files.add(entry['file'])
    config = cells.load_json(os.path.join(helpers.ROOT, entry['file']))
    assert config['source'] == entry['source']
    assert config['reduced'] == entry['reduced']
    assert config.get('platform', 'tpu') == 'tpu'
    assert config['assumed']


def test_files_under_paths_are_named_from_allowed_characters():
  m = cells.load_json(helpers.REAL)
  allowed = re.compile(r'^[A-Za-z0-9_.\-/]+$')
  for path in m['paths']:
    for directory, dirs, files in os.walk(os.path.join(helpers.ROOT, path)):
      dirs[:] = [d for d in dirs if d != '__pycache__']
      for filename in files:
        relative = os.path.relpath(os.path.join(directory, filename),
                                   helpers.ROOT)
        assert allowed.match(relative), relative


def test_no_cell_name_is_tested_for_in_code():
  m = cells.load_json(helpers.REAL)
  names = [w['name'] for w in m['workloads']] + [c['name']
                                                 for c in m['configs']]
  code_dir = os.path.join(helpers.ROOT, 'benchmark')
  for directory, _, files in os.walk(code_dir):
    for filename in files:
      if not filename.endswith('.py'):
        continue
      with open(os.path.join(directory, filename), encoding='utf-8') as f:
        code = '\n'.join(line for line in f.read().splitlines()
                         if not line.lstrip().startswith(('#', 'python3 ')))
      code = re.sub(r'"""(?:.|\n)*?"""', '', code)
      for name in names:
        assert name not in code, '{} names {}'.format(filename, name)
