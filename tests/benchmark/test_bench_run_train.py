"""The one command, kind ``train_disk``, end to end at a tiny size on the
CPU: one device, two virtual devices, traced; and the refusals."""

import json
import os
import shutil

import pytest

import bench_helpers as helpers


def _check_line(result, metrics):
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  line = helpers.last_json_line(result.stdout)
  assert set(line) - {'breakdown'} == helpers.RESULT_KEYS
  assert line['correct'] is True and line['failed'] == 0
  assert line['attempted'] > 0
  assert set(line['metrics']) == set(metrics)
  for metric in line['metrics'].values():
    assert set(metric) == {'value', 'unit'}
    assert isinstance(metric['value'], float)
  assert set(line['device']) >= {'platform', 'kind', 'count',
                                 'memory_peak_bytes'}
  assert line['device']['platform'] == 'cpu'
  return line


def test_one_device_untraced_reports_the_end_to_end_metrics(tmp_path):
  result = helpers.run_cell(tmp_path, 'tiny_train')
  line = _check_line(result, ['train_examples_per_s_per_chip', 'setup_s'])
  assert line['metrics']['train_examples_per_s_per_chip']['value'] > 0
  assert 'platform=cpu' in result.stdout and "device_kind='cpu'" in \
      result.stdout
  for line in ('set-up', 'whole steps', 'inputs from the seed',
               'step boundaries on the training thread',
               'the step against the plain forward', 'against float32'):
    assert line in result.stdout, line


def test_two_virtual_devices_traced_reports_the_per_layer_metrics(tmp_path):
  """The rehearsal of the data-parallel cell: the same code over a mesh."""
  result = helpers.run_cell(tmp_path, 'tiny_train_dp2', trace=1, devices=2)
  # The CPU trace has no device plane: the readers that find nothing to read
  # return nothing and the harness leaves those metrics out of the line.
  line = _check_line(result, ['input_wait_share', 'wire_bytes_per_example',
                              'compile_requests', 'window_compiles'])
  assert line['metrics']['window_compiles']['value'] == 0.0
  assert line['metrics']['wire_bytes_per_example']['value'] > 512 * 640 * 3
  assert 'on 2 chip(s)' in result.stdout


def test_a_four_chip_cells_rehearsal_on_four_virtual_devices(tmp_path):
  """Three frames an example, no labels, mesh data=4, with ResNet-18 towers.
  No real cell takes four chips yet (PERF.md, Open questions); here the
  data-parallel step and the float32 reference agree exactly."""
  result = helpers.run_cell(tmp_path, 'tiny_grasp2vec_dp4', devices=4)
  line = _check_line(result, ['train_examples_per_s_per_chip', 'setup_s'])
  assert line['device']['count'] == 4
  assert 'on 4 chip(s)' in result.stdout


def _real_cells():
  with open(helpers.REAL, encoding='utf-8') as f:
    return [w['name'] for w in json.load(f)['workloads']]


@pytest.mark.parametrize('workload', _real_cells())
def test_a_real_cell_refuses_to_run_without_a_tpu(tmp_path, workload):
  result = helpers.run_cell(tmp_path, workload, manifest=None, devices=4)
  assert result.returncode != 0
  assert 'refused' in result.stdout
  assert not result.stdout.rstrip().splitlines()[-1].startswith('{')


def test_it_fails_where_only_the_benchmarks_files_are(tmp_path):
  """BENCHMARK.json and the files under ``paths`` alone are not the system:
  the command exits non-zero and prints no result."""
  checkout = tmp_path / 'bare'
  checkout.mkdir()
  manifest = json.load(open(helpers.REAL))
  shutil.copy(helpers.REAL, checkout / 'BENCHMARK.json')
  for path in manifest['paths']:
    shutil.copytree(os.path.join(helpers.ROOT, path), checkout / path,
                    ignore=shutil.ignore_patterns('__pycache__'))
  result = helpers.run_cell(tmp_path, _real_cells()[0], manifest=None,
                            cwd=str(checkout))
  assert result.returncode != 0
  assert '"correct"' not in result.stdout


def test_an_unknown_cell_is_an_error(tmp_path):
  result = helpers.run_cell(tmp_path, 'no_such_cell')
  assert result.returncode != 0 and '"correct"' not in result.stdout
