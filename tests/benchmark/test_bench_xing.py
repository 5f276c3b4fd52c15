"""The Xing4.0-style configuration's part of the benchmark: the tiny cell
through the one command on the CPU (untraced and traced), a planted fault
coming out ``correct: false``, the cost function by hand and its stream
bytes against the kernels' own operands, the readers on a hand-made
observation, the manifests' new entries (looked up BY NAME), and the
configuration's file."""

import collections
import importlib.util
import json
import os
import shutil

import pytest

import bench_helpers as helpers
from benchmark.harness import cells, xing_costs

TINY_XING = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                         'BENCHMARK_xing.json')
TINY_TRACE = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                          'BENCHMARK_program_trace.json')
NEW = ('xing_hc_roofline', 'xing_attention_roofline',
       'xing_expert_matmul_roofline', 'xing_kernels_step_share',
       'xing_pairs_held_per_token', 'xing_dropped_pairs',
       'xing_hc_res_stochastic_error', 'xing_expert_load_max_over_mean',
       'xing_chosen_load_max_over_mean')
RING = ('xing_hc_res_stochastic_error', 'xing_chosen_load_max_over_mean')
CONFIG = 'xing4_29b_a4b_ep8share'
CELL = 'xing4_train_packed4k'
REAL_CONFIG = os.path.join(helpers.ROOT, 'benchmark', 'configs',
                           CONFIG + '.json')
TINY_CONFIG = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                           'configs', 'tiny_xing.json')
PUBLISHED = {
    'attention_bias': False, 'ep_size': 1, 'first_k_dense_replace': 2,
    'hidden_act': 'silu', 'hidden_size': 3584, 'intermediate_size': 9216,
    'kv_lora_rank': 512, 'max_position_embeddings': 262144,
    'model_type': 'xing4_0', 'moe_intermediate_size': 1024,
    'moe_layer_freq': 1, 'n_group': 1, 'n_routed_experts': 64,
    'n_shared_experts': 1, 'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_experts_per_tok': 4, 'num_hidden_layers': 40,
    'num_key_value_heads': 32, 'num_nextn_predict_layers': 1, 'hc_mult': 4,
    'hc_sinkhorn_iters': 20, 'hc_eps': 1e-06, 'mhc_h_res_clamp_min': -30,
    'mhc_h_res_clamp_max': 30, 'q_lora_rank': 768, 'qk_nope_head_dim': 128,
    'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06, 'rope_theta': 10000,
    'rope_scaling': {'beta_fast': 32, 'beta_slow': 1, 'factor': 64,
                     'mscale': 1, 'mscale_all_dim': 1,
                     'original_max_position_embeddings': 4096,
                     'type': 'yarn'},
    'routed_scaling_factor': 2, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_group': 1, 'topk_method': 'noaux_tc',
    'v_head_dim': 128, 'vocab_size': 131072,
}
HELD = {'num_hidden_layers': 5, 'first_k_dense_replace': 1,
        'n_routed_experts': 8, 'vocab_size': 16384,
        'num_nextn_predict_layers': 0}


def _module():
  spec = importlib.util.spec_from_file_location(
      'xing_under_test', os.path.join(cells.METRICS_DIR, 'xing.py'))
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


def _settings(path):
  """The plain reference's settings as the driver hands them over."""
  from benchmark.harness import common

  return dict(common._tuples(cells.load_json(path)['reference']['settings']))


# -- the one command ----------------------------------------------------------


@pytest.mark.parametrize('trace', [0, 1], ids=['untraced', 'traced'])
def test_the_tiny_cell_runs_through_the_one_command(tmp_path, trace):
  result = helpers.run_cell(tmp_path, 'tiny_xing', trace=trace,
                            manifest=TINY_XING)
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  line = helpers.last_json_line(result.stdout)
  assert set(line) - {'breakdown'} == helpers.RESULT_KEYS
  assert line['correct'] is True and line['failed'] == 0
  assert line['attempted'] > 0 and line['device']['platform'] == 'cpu'
  metrics = {k: v['value'] for k, v in line['metrics'].items()}
  if not trace:
    assert set(metrics) == {'train_examples_per_s_per_chip', 'setup_s'}
    assert metrics['train_examples_per_s_per_chip'] > 0
  else:
    # The CPU trace has no device plane and no kernel runs there (the CPU's
    # streams are the plain formulation): the readers of the trace find
    # nothing and are left out; the others read.
    assert {'xing_pairs_held_per_token', 'xing_dropped_pairs',
            'xing_hc_res_stochastic_error', 'xing_expert_load_max_over_mean',
            'xing_chosen_load_max_over_mean',
            'window_compiles'} <= set(metrics)
    assert not {'xing_hc_roofline', 'xing_attention_roofline',
                'xing_expert_matmul_roofline', 'xing_kernels_step_share',
                'mfu'} & set(metrics)
    assert metrics['xing_dropped_pairs'] == 0
    assert metrics['window_compiles'] == 0
    # 4 of 8 experts held, 4 chosen: 2 a token an EXPERT layer.
    assert 1.0 < metrics['xing_pairs_held_per_token'] < 3.0
    assert 0 < metrics['xing_hc_res_stochastic_error'] < 1e-2
    assert metrics['xing_expert_load_max_over_mean'] >= 1
    assert metrics['xing_chosen_load_max_over_mean'] >= 1
  for said in ('(1) loss of the first batch', '(2) loss of the first batch',
               '(3) global norm', '(4) norm of the first step\'s gradient by',
               '(5) the first step\'s gradient, read back from',
               '(6) the parameters after the first step', 'whole steps',
               'set-up'):
    assert said in result.stdout, said


def test_a_fault_planted_in_the_reference_comes_out_not_correct(tmp_path):
  """The reference computing another model than the program (here the write
  back of the streams without its factor 2) is what a wrong stream kernel
  looks like to the check; every other fault is refused by the same
  comparison in tests/test_xing.py."""
  tiny = os.path.dirname(TINY_XING)
  for part in ('configs', 'traffic'):
    shutil.copytree(os.path.join(tiny, part), str(tmp_path / part))
  shutil.copy(TINY_XING, str(tmp_path / 'BENCHMARK.json'))
  path = str(tmp_path / 'configs' / 'tiny_xing.json')
  config = cells.load_json(path)
  assert config['reference']['settings']['post_factor'] == 2
  config['reference']['settings']['post_factor'] = 1
  with open(path, 'w') as f:
    json.dump(config, f)
  result = helpers.run_cell(tmp_path, 'tiny_xing',
                            manifest=str(tmp_path / 'BENCHMARK.json'))
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  assert helpers.last_json_line(result.stdout)['correct'] is False
  said = [line for line in result.stdout.splitlines() if 'INCORRECT' in line]
  assert any('(5) the first step\'s gradient' in line for line in said), said


# -- the cost function --------------------------------------------------------


def test_the_step_cost_by_hand():
  s = _settings(TINY_CONFIG)
  cost = xing_costs.step_cost(s, 2, 32, pairs_held=400.0)
  rows = 2 * 32
  attention = 2.0 * rows * (128 * 32 + 32 * 4 * 32 + 128 * 48 + 32 * 4 * 24 +
                            4 * 8 * 128)
  dense_mlp = 3 * 2.0 * rows * 128 * 96
  shared = 3 * 2.0 * rows * 128 * 32
  routers = 2 * 2.0 * rows * 128 * 8
  head = 2.0 * 2 * 31 * 128 * 64
  assert xing_costs.dense_forward_flops(s, 2, 32) == (
      3 * attention + dense_mlp + 2 * shared + routers + head)
  assert cost['dot']['flops'] == 3 * xing_costs.dense_forward_flops(s, 2, 32)
  assert cost['dot']['calls'] == 3 * (3 * 8 + 2 + 1)
  # 2 x (32 + 8) x 4 heads a pair of the band, 3 layers, 2 sequences.
  assert cost['attention']['flops'] == 3 * 320.0 * (32 * 33 // 2) * 2 * 3
  assert cost['experts']['flops'] == 3 * 6.0 * 128 * 32 * 400.0
  # The projection, the read of h and the write of the streams, six
  # sublayers.
  assert cost['hc']['flops'] == 3 * 6 * rows * (
      2.0 * 512 * 24 + 2 * 512 + 2 * 4 * 512 + 2 * 512)
  assert cost['flops'] == sum(cost[family]['flops'] for family in (
      'dot', 'attention', 'experts', 'hc'))
  assert cost['conv'] == {'flops': 0.0, 'bytes': 0.0, 'calls': 0}
  assert cost['layers'] == {'held': 3, 'attention': 3, 'experts': 2,
                            'streams': 4}
  # The real size: 11.68e12 FLOPs a step at one pair a token a layer's
  # eighth (11.65e12 without the streams' projections), 59 ms at
  # the bf16 peak; the stream kernels must move 23.2 GB, 28 ms at 819 GB/s.
  real = _settings(REAL_CONFIG)
  shipped = xing_costs.step_cost(real, 1, 4096, 4096 * 4 * 4 / 8.0)
  assert shipped['flops'] == pytest.approx(11.68e12, rel=2e-3)
  assert shipped['hc']['bytes'] == pytest.approx(23.25e9, rel=2e-3)
  assert shipped['attention']['flops'] == pytest.approx(
      3 * 2 * 320 * 32 * 4096 * 4097 / 2 * 5, rel=1e-12)


def test_the_stream_bytes_are_the_kernels_operands_and_results():
  """The traffic the cost function counts for one call of each stream kernel
  is what the kernel's own signature moves at the cell's shape: every
  operand read and every result written once."""
  import functools

  import jax
  import jax.numpy as jnp
  from tensor2robot_tpu.parallel import hyper_connections as hc

  real = _settings(REAL_CONFIG)
  rows, c, n = 4096, real['hidden_size'], real['streams']
  shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims, dtype)
  x, f = shape(rows, n * c), shape(rows, c, dtype=jnp.bfloat16)
  maps, dh = shape(rows, 32), shape(rows, c)
  phi = shape(n * c, n * (n + 2), dtype=jnp.bfloat16)
  alpha, bias = shape(3), shape(n * (n + 2))
  kw = dict(n=n, iters=real['sinkhorn_iters'], eps=real['stream_eps'],
            clamp=float(real['clamp']))
  nbytes = lambda *arrays: sum(
      a.size * a.dtype.itemsize for a in jax.tree.leaves(arrays))
  calls = {
      'pre_fwd': ((x, phi, alpha, bias), functools.partial(
          hc.hc_pre_fwd, **kw)),
      'post_fwd': ((x, f, maps), functools.partial(hc.hc_post_fwd, n=n)),
      'post_bwd': ((x, f, maps, x), functools.partial(hc.hc_post_bwd, n=n)),
      'pre_bwd': ((x, phi, alpha, bias, dh, x, maps), functools.partial(
          hc.hc_pre_bwd, **kw)),
  }
  for kernel, (operands, call) in calls.items():
    results = jax.eval_shape(call, *operands)
    assert xing_costs.hc_call_bytes(real, kernel, rows) == nbytes(
        operands, results), kernel
  assert xing_costs.hc_step_bytes(real, 1, rows) == 2 * 5 * sum(
      xing_costs.hc_call_bytes(real, kernel, rows) for kernel in calls)


def test_the_dense_count_equals_the_jaxpr_of_the_plain_reference():
  """``costs.py`` counts every matrix product of a jaxpr; on the plain
  reference (no kernel hides anything) that is the dense products, the
  streams' projections, the attention's two products over the WHOLE
  square and every held expert over every token."""
  import jax
  import numpy as np

  from benchmark.harness import common, costs, xing_reference

  s = _settings(TINY_CONFIG)
  model = common.build_model(cells.load_json(TINY_CONFIG)['model'])
  tokens = jax.ShapeDtypeStruct((1, 32), np.int32)
  params = jax.eval_shape(
      lambda t: model.create_train_state(jax.random.PRNGKey(0), {'tokens': t},
                                         None), tokens).params
  counted = costs.program_cost(
      lambda p, t: xing_reference.loss(p, t, s), params, tokens)
  square = 2.0 * (32 + 8) * 4 * 32 * 32 * 3    # three attention layers
  experts = 2 * 4 * 6.0 * 128 * 32 * 32        # 2 layers x 4 held x 32
  head_last_row = 2.0 * 128 * 64               # the reference's head runs on L
  # The streams' projection and their two mixes, six sublayers (the write
  # post x f is elementwise in the reference, no product).
  streams = 6 * 32 * (2.0 * 512 * 24 + 2 * 512 + 2 * 4 * 512)
  assert streams == xing_costs.hc_forward_flops(s, 1, 32) - 6 * 32 * 2 * 512
  assert counted['flops'] == pytest.approx(
      xing_costs.dense_forward_flops(s, 1, 32) + head_last_row + square +
      experts + streams, rel=1e-12)


# -- the readers ----------------------------------------------------------------

Record = collections.namedtuple('Record', 'name thread start_ns end_ns attrs')


def _observation():
  peaks = {'bf16_flops_per_s': 100e12, 'hbm_bytes_per_s': 1e12}
  return {
      'chips': 1, 'peaks': peaks, 'window_s': 10.0, 'steps': 20,
      'examples_per_step': 1,
      'counters': {'before': {'span/train.step/count': 3.0},
                   'after': {'span/train.step/count': 23.0}},
      'trace': {'chips': 1,
                'modules': {'jit_step(1)': [0.5, 0.5], 'jit_other': [0.01]},
                'families': {'flash_attention_fwd': 0.08,
                             'flash_attention_bwd_dq': 0.12,
                             'moe_grouped_matmul': 0.02,
                             'moe_grouped_matmul_nt': 0.01,
                             'moe_grouped_matmul_dw': 0.01,
                             'moe_take_rows': 0.02, 'moe_sum_rows': 0.04,
                             'hc_pre_fwd': 0.01, 'hc_post_fwd': 0.01,
                             'hc_post_bwd': 0.01, 'hc_pre_bwd': 0.01,
                             'fusion kOutput': 0.3}},
      'cost': {'attention': {'flops': 5e12, 'bytes': 1e9},
               'experts': {'flops': 1e12, 'bytes': 5e9},
               'hc': {'flops': 1e9, 'bytes': 8e9},
               'layers': {'held': 5, 'attention': 5, 'experts': 4,
                          'streams': 4}},
      'moe': {'pairs_held_per_step': 160.0, 'tokens_per_step': 320.0,
              'load_max_over_mean': 2.5, 'dropped_pairs': 0.0},
  }


def test_the_readers_on_a_hand_made_observation(monkeypatch):
  module, obs = _module(), _observation()
  readers = module.METRICS
  assert tuple(readers) == NEW
  # Two steps traced: 0.02 s of the four stream kernels a step; 8e9 bytes
  # are 0.008 s at 1e12 B/s (1e9 operations are nothing): 40%.
  assert readers['xing_hc_roofline'](obs) == pytest.approx(40.0)
  # 0.1 s of attention a step for 5e12 FLOPs at 100e12/s.
  assert readers['xing_attention_roofline'](obs) == pytest.approx(50.0)
  # 0.02 s of grouped products a step; 1e12 FLOPs are 0.01 s at the peak.
  assert readers['xing_expert_matmul_roofline'](obs) == pytest.approx(50.0)
  # All twelve kernels: 0.1 + 0.02 + 0.03 + 0.02 s a step of 0.5.
  assert readers['xing_kernels_step_share'](obs) == pytest.approx(0.17 / 0.5)
  # 160 pairs over 64 tokens x the FOUR expert layers of the five held.
  assert readers['xing_pairs_held_per_token'](obs) == pytest.approx(0.625)
  assert readers['xing_dropped_pairs'](obs) == 0.0
  assert readers['xing_expert_load_max_over_mean'](obs) == 2.5

  from benchmark.metrics import program_trace

  second = 10**9
  records = []
  for n in range(1, 25):
    records.append(Record('train.step', 'main', n * second // 2 - 1000,
                          n * second // 2, {'step': n}))
    records.append(Record('data.next', 'main', n * second // 2 + 10,
                          n * second // 2 + 20, {}))
    attrs = {'step': n, 'steps_covered': 1}
    if n % 2:   # every other event carries the values
      attrs['hc/res_stochastic_error'] = 1e-4 * n
      attrs['moe/chosen_load_max_over_mean'] = 1.0 + 0.1 * n
    records.append(Record('train.step_done', 'watch', n * second // 2 + 500,
                          n * second // 2 + 500, attrs))
  monkeypatch.setattr(program_trace, 'read_ring', lambda: (records, 0))
  inside = list(range(3, 22, 2))
  assert readers['xing_hc_res_stochastic_error'](obs) == pytest.approx(
      1e-4 * sum(inside) / len(inside))
  assert readers['xing_chosen_load_max_over_mean'](obs) == pytest.approx(
      1.0 + 0.1 * sum(inside) / len(inside))
  for ring in ((records, 5), None):
    monkeypatch.setattr(program_trace, 'read_ring', lambda ring=ring: ring)
    for name in RING:
      assert readers[name](obs) is None, name


@pytest.mark.parametrize('missing', ['trace', 'cost', 'moe', 'families',
                                     'hc'])
def test_a_reader_with_nothing_to_read_returns_none(missing):
  """``hc`` missing from the cost is ANOTHER token cell's cost: none of these
  metrics reads there, whatever else that observation holds."""
  readers, obs = _module().METRICS, _observation()
  if missing == 'families':
    obs['trace']['families'] = {'fusion kOutput': 0.3}
  elif missing == 'hc':
    del obs['cost']['hc']
  else:
    obs[missing] = None
  trace_readers = {'xing_hc_roofline', 'xing_attention_roofline',
                   'xing_expert_matmul_roofline', 'xing_kernels_step_share'}
  expected_none = {
      'trace': trace_readers, 'families': trace_readers,
      'cost': set(NEW), 'hc': set(NEW),
      'moe': {'xing_pairs_held_per_token', 'xing_dropped_pairs',
              'xing_expert_load_max_over_mean'},
  }[missing]
  for name in NEW:
    if name in RING and name not in expected_none:
      continue   # the program's ring, not the observation
    assert (readers[name](obs) is None) == (name in expected_none), name


def test_the_parents_program_reads_nothing_and_raises_nothing():
  """What a traced run of another token cell does with these files over a
  checkout that has no such model: the other token cells' cost has
  no ``hc``, no kernel of that name is in the trace, no event carries the
  attribute."""
  from benchmark.harness import token_costs

  module, obs = _module(), _observation()
  settings = dict(
      hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=32,
      num_experts=8, experts_held=(0, 4), vocab_rows=64, window=8,
      window_layers=(False, True))
  obs['cost'] = token_costs.step_cost(settings, 2, 32, 100.0)
  for kernel in ('hc_pre_fwd', 'hc_post_fwd', 'hc_post_bwd', 'hc_pre_bwd'):
    del obs['trace']['families'][kernel]
  for name in NEW:
    assert module.METRICS[name](obs) is None, name


# -- the manifests ----------------------------------------------------------------


@pytest.mark.parametrize('path, cell', [(helpers.REAL, CELL),
                                        (TINY_XING, 'tiny_xing')],
                         ids=['real', 'tiny_xing'])
def test_the_manifests_list_the_seven_with_just_the_contracts_keys(path,
                                                                   cell):
  manifest = cells.load_json(path)
  readers = cells.metric_readers()
  entries = {m['name']: m for m in manifest['per_layer']}
  layers = {'xing_hc_roofline': 'kernels',
            'xing_attention_roofline': 'kernels',
            'xing_expert_matmul_roofline': 'kernels',
            'xing_kernels_step_share': 'train step',
            'xing_pairs_held_per_token': 'expert layers',
            'xing_dropped_pairs': 'expert layers',
            'xing_hc_res_stochastic_error': 'residual streams',
            'xing_expert_load_max_over_mean': 'expert layers',
            'xing_chosen_load_max_over_mean': 'expert layers'}
  for name in NEW:
    entry = entries[name]
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert entry['workloads'] == [cell]
    assert entry['moves'] == 'train_examples_per_s_per_chip'
    assert entry['layer'] == layers[name]
    assert name in readers
  assert entries['xing_hc_roofline']['unit'] == '%'
  assert entries['xing_hc_roofline']['source'] == 'device_trace'
  assert entries['xing_pairs_held_per_token']['source'] == 'program_counter'
  assert entries['xing_hc_res_stochastic_error']['source'] == 'program_span'
  assert entries['xing_kernels_step_share']['unit'] == 'share'
  assert entries['xing_expert_load_max_over_mean']['source'] == (
      'program_counter')
  assert entries['xing_chosen_load_max_over_mean']['source'] == (
      'program_span')
  names = cells.Cell(path, cell).metric_names('per_layer')
  assert set(NEW) <= set(names)
  assert cells.Cell(path, cell).traffic['kind'] == 'train_tokens'


def test_the_other_cells_do_not_list_the_seven():
  manifest = cells.load_json(helpers.REAL)
  for workload in manifest['workloads']:
    if workload['name'] != CELL:
      names = cells.Cell(helpers.REAL, workload['name']).metric_names(
          'per_layer')
      assert not set(NEW) & set(names), workload['name']
  mine = cells.Cell(helpers.REAL, CELL).metric_names('per_layer')
  assert not [name for name in mine
              if name.startswith(('bd_', 'moe_', 'new_kernels', 'lfm2_')) or
              name == 'attention_roofline']
  assert {'mfu', 'step_device_ms', 'train_peak_hbm_gb', 'conv_roofline',
          'setup_trace_s'} <= set(mine)


def test_the_tiny_manifest_of_its_own_differs_by_its_cell_and_the_seven():
  traced, mine = cells.load_json(TINY_TRACE), cells.load_json(TINY_XING)
  for key in ('command', 'paths', 'run_seconds', 'end_to_end'):
    assert traced[key] == mine[key]
  assert mine['per_layer'][:-len(NEW)] == traced['per_layer']
  assert len(mine['configs']) == len(mine['workloads']) == 1


def test_the_real_manifest_holds_the_configuration_and_the_cell():
  """By NAME: entries go at the end of their lists and later PRs add more."""
  manifest = cells.load_json(helpers.REAL)
  entry = next(c for c in manifest['configs'] if c['name'] == CONFIG)
  assert entry['source'] == ('https://huggingface.co/XingChen-AGI/'
                             'Xing4.0-29B-A4B/blob/main/config.json')
  assert entry['file'] == 'benchmark/configs/' + CONFIG + '.json'
  assert entry['reduced'] == list(HELD)
  cell = next(w for w in manifest['workloads'] if w['name'] == CELL)
  assert (cell['config'], cell['traffic'], cell['chips']) == (
      CONFIG, 'packed4k', 1)
  assert len(cell['why']) <= 200 and 'eighth' in cell['why']
  assert len(entry['why']) <= 200
  traffic = cells.Cell(helpers.REAL, CELL).traffic
  packed8k = cells.load_json(os.path.join(helpers.ROOT, 'benchmark',
                                          'traffic', 'packed8k.json'))
  # packed8k's generator at 4,096 tokens.
  assert traffic['document_longest'] == 4096
  for key in ('kind', 'num_records', 'zipf_exponent', 'document_median',
              'document_sigma', 'document_shortest', 'trace_seconds'):
    assert traffic[key] == packed8k[key], key
  # Nothing that was there moved: the four configurations and cells of PRs
  # 24, 28, 32 and 34 are still the first four, in their order.
  assert [c['name'] for c in manifest['configs']][:4] == [
      'grasp2vec_resnet50', 'smallthinker_21b_a3b_ep4share',
      'sdar_30b_a3b_ep8share', 'lfm2_8b_a1b_ep4share']
  assert [w['name'] for w in manifest['workloads']][:4] == [
      'grasp2vec_train_disk', 'smallthinker_train_packed8k',
      'sdar_train_bd4_packed8k', 'lfm2_train_packed8k']


# -- the configuration's file ----------------------------------------------------


def test_the_configuration_file_keeps_the_published_widths():
  """Every key of the public config.json (the catalog row) under its own
  name; the five reduced keys at what this chip holds, the published counts
  and the deployment beside them; the model's keyword arguments agree."""
  config = cells.load_json(REAL_CONFIG)
  assert config['reduced'] == list(HELD)
  assert config['source'].endswith('XingChen-AGI/Xing4.0-29B-A4B/blob/main/'
                                   'config.json')
  for key, value in PUBLISHED.items():
    assert config[key] == HELD.get(key, value), key
    if key in HELD:
      assert config['published'][key] == value
  assert config['deployment']['chips_sharing_each_layer'] == 8
  for key in ('experts', 'vocabulary', 'replicated', 'depth', 'held_here',
              'expert_load', 'router_bias', 'multi_token_prediction'):
    assert config['deployment'][key]
  assert '759,346,190' in config['deployment']['held_here']
  attention = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512 +
               512 * 32 * 256 + 4096 * 3584)
  maps = 2 * (14336 * 24 + 24 + 3)
  dense = attention + maps + 2 * 3584 + 3 * 3584 * 9216
  expert = (attention + maps + 2 * 3584 + 3 * 3584 * 1024 +
            8 * 3 * 3584 * 1024 + 3584 * 64)
  assert dense + 4 * expert + 2 * 16384 * 3584 + 3584 == 759346190
  kwargs = config['model']['kwargs']
  for key in ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
              'q_lora_rank', 'kv_lora_rank', 'qk_nope_head_dim',
              'qk_rope_head_dim', 'v_head_dim', 'intermediate_size',
              'moe_intermediate_size', 'n_shared_experts',
              'num_experts_per_tok', 'num_hidden_layers',
              'first_k_dense_replace', 'moe_layer_freq',
              'routed_scaling_factor', 'norm_topk_prob', 'scoring_func',
              'topk_method', 'n_group', 'topk_group', 'hidden_act',
              'attention_bias', 'tie_word_embeddings', 'rope_theta',
              'rope_scaling', 'rms_norm_eps', 'hc_mult', 'hc_sinkhorn_iters',
              'hc_eps', 'mhc_h_res_clamp_min', 'mhc_h_res_clamp_max',
              'num_nextn_predict_layers'):
    assert kwargs[key] == config[key], key
  assert kwargs['n_routed_experts'] == 64               # the router's width
  assert kwargs['experts_held'] == [0, config['n_routed_experts']]
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['sequence_length'] == 4096
  assert config['train']['batch_per_chip'] == 1
  assert config['train']['gradient_kept_in_state'] == 'mu'
  for key in ('initialisation', 'learning rate', 'experts_held', 'vocab',
              'document mask', 'rotate', 'stream', 'map set', 'Sinkhorn',
              'ends', 'planted faults'):
    assert any(key in name or key in text
               for name, text in config['assumed'].items()), key
  # (2) lies under what the float8 reference reads at the real size, 1.04e-4.
  for tolerance, largest in (('step_rel_tolerance', 0.05),
                             ('reference_rel_tolerance', 1e-4),
                             ('grad_norm_rel_tolerance', 0.05),
                             ('group_grad_norm_rel_tolerance', 0.05),
                             ('gradient_difference_tolerance', 0.1)):
    assert 0 < config['train'][tolerance] <= largest
    assert 'train.' + tolerance in config['assumed']
  # 1 is what a state left unchanged reads.
  assert 0 < config['train']['parameter_change_tolerance'] < 1
  assert 'train.parameter_change_tolerance' in config['assumed']
  assert 'train.batch_per_chip' in config['assumed']


@pytest.mark.parametrize('path', [REAL_CONFIG, TINY_CONFIG],
                         ids=['real', 'tiny'])
def test_the_reference_is_named_by_the_file_and_set_as_the_model_is(path):
  """The driver names no model: the file gives the reference's loss, the
  cost function and the settings, which say what the model's keywords say."""
  from benchmark.harness import common, train_tokens

  config = cells.load_json(path)
  plain, kwargs = config['reference'], config['model']['kwargs']
  assert callable(train_tokens._named(plain['loss']))
  assert train_tokens._named(plain['cost']) is xing_costs.step_cost
  layers = config['num_hidden_layers']
  same = {
      'hidden_size': 'hidden_size', 'num_heads': 'num_attention_heads',
      'q_lora_rank': 'q_lora_rank', 'kv_lora_rank': 'kv_lora_rank',
      'qk_nope_head_dim': 'qk_nope_head_dim',
      'qk_rope_head_dim': 'qk_rope_head_dim', 'v_head_dim': 'v_head_dim',
      'rope_theta': 'rope_theta', 'rope_scaling': 'rope_scaling',
      'dense_dim': 'intermediate_size', 'expert_dim': 'moe_intermediate_size',
      'shared_expert_dim': 'moe_intermediate_size',
      'num_experts': 'n_routed_experts', 'experts_held': 'experts_held',
      'top_k': 'num_experts_per_tok',
      'num_dense_layers': 'first_k_dense_replace',
      'routed_scaling': 'routed_scaling_factor', 'streams': 'hc_mult',
      'sinkhorn_iters': 'hc_sinkhorn_iters', 'stream_eps': 'hc_eps',
      'clamp': 'mhc_h_res_clamp_max', 'eps': 'rms_norm_eps',
      'vocab_rows': 'vocab_rows',
  }
  for setting, keyword in same.items():
    assert plain['settings'][setting] == kwargs[keyword], setting
  assert plain['settings']['window_layers'] == [False] * layers
  right = {'sinkhorn': 'sinkhorn', 'post_factor': 2, 'mscale_squared': True,
           'yarn': True, 'shared_expert': True, 'k_pe': 'shared',
           'kv_norm': True}
  for setting, value in right.items():
    assert plain['settings'][setting] == value, setting
  # No bias in the file: the cell's first step starts from zeros.
  assert set(plain['settings']) == set(same) | set(right) | {
      'window_layers', 'query_block', 'head_block'}
  assert kwargs['num_hidden_layers'] == layers
  assert kwargs['first_k_dense_replace'] == config['first_k_dense_replace']
  # The model can be built from the file as the driver builds it.
  model = common.build_model(config['model'])
  assert model.traced_step_metrics == (
      'hc/res_stochastic_error', 'moe/chosen_load_max_over_mean')
  assert model.report_gradient_norm


def test_the_reference_imports_nothing_of_the_program():
  import ast

  allowed = {'xing_reference.py': {'jax', 'math'},
             'xing_costs.py': {'benchmark'}}
  for name, modules in allowed.items():
    with open(os.path.join(helpers.ROOT, 'benchmark', 'harness', name)) as f:
      tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
      if isinstance(node, ast.Import):
        imported |= {alias.name.split('.')[0] for alias in node.names}
      elif isinstance(node, ast.ImportFrom):
        imported.add((node.module or '').split('.')[0])
    assert imported <= modules, (name, imported)
