"""The LFM2-style configuration's part of the benchmark: the tiny cell through
the one command on the CPU (untraced and traced), planted faults coming out
``correct: false``, the cost function by hand and against the jaxpr of the
plain reference, the readers on a hand-made observation, the manifests' new
entries (looked up BY NAME), and the configuration's file."""

import collections
import importlib.util
import json
import os
import shutil

import pytest

import bench_helpers as helpers
from benchmark.harness import cells, costs, lfm2_costs

TINY_LFM2 = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                         'BENCHMARK_lfm2.json')
TINY_TRACE = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                          'BENCHMARK_program_trace.json')
NEW = ('lfm2_short_conv_roofline', 'lfm2_attention_roofline',
       'lfm2_expert_matmul_roofline', 'lfm2_kernels_step_share',
       'lfm2_pairs_held_per_token', 'lfm2_expert_load_max_over_mean',
       'lfm2_dropped_pairs', 'lfm2_chosen_load_max_over_mean')
CONFIG = 'lfm2_8b_a1b_ep4share'
CELL = 'lfm2_train_packed8k'
REAL_CONFIG = os.path.join(helpers.ROOT, 'benchmark', 'configs',
                           CONFIG + '.json')
TINY_CONFIG = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                           'configs', 'tiny_lfm2.json')
PUBLISHED_LAYER_TYPES = [
    'full_attention' if layer in (2, 6, 10, 14, 18, 21) else 'conv'
    for layer in range(24)]


def _module():
  spec = importlib.util.spec_from_file_location(
      'lfm2_under_test', os.path.join(cells.METRICS_DIR, 'lfm2.py'))
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
  result = helpers.run_cell(tmp_path, 'tiny_lfm2', trace=trace,
                            manifest=TINY_LFM2)
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
    # convolution is the plain formulation): the readers of the trace find
    # nothing and are left out; the others read.
    assert {'lfm2_pairs_held_per_token', 'lfm2_expert_load_max_over_mean',
            'lfm2_dropped_pairs', 'lfm2_chosen_load_max_over_mean',
            'window_compiles', 'wire_bytes_per_example'} <= set(metrics)
    assert not {'lfm2_short_conv_roofline', 'lfm2_attention_roofline',
                'lfm2_expert_matmul_roofline', 'lfm2_kernels_step_share',
                'mfu'} & set(metrics)
    assert metrics['lfm2_dropped_pairs'] == 0
    assert metrics['window_compiles'] == 0
    # 4 of 8 experts held, 3 of 8 chosen: 1.5 a token an EXPERT layer.
    assert 0.8 < metrics['lfm2_pairs_held_per_token'] < 2.4
    assert metrics['lfm2_expert_load_max_over_mean'] >= 1
    # The most chosen of the 8 experts over the mean: even at 1, and at
    # most every token's on one expert, 8 / 3.
    assert 1 <= metrics['lfm2_chosen_load_max_over_mean'] <= 8 / 3
    assert metrics['wire_bytes_per_example'] == 32 * 4
  for said in ('(1) loss of the first batch', '(2) loss of the first batch',
               '(3) global norm', '(4) norm of the first step\'s gradient by',
               '(5) the first step\'s gradient, read back from',
               '(6) the parameters after the first step',
               'moe/router_bias_abs_mean', 'whole steps', 'set-up'):
    assert said in result.stdout, said


_FAULTS = {
    'the_taps_reversed': ('taps', 'reversed'),
    'the_c_gate_left_out': ('c_gate', False),
    'a_softmax_router_for_the_sigmoid': ('router', 'softmax'),
    'the_renormalisation_left_out': ('renormalise', False),
    'the_norm_of_q_and_k_left_out': ('qk_norm', False),
    'experts_paired_with_their_neighbours_routing': ('experts_held', [3, 4]),
}


@pytest.mark.parametrize('fault', sorted(_FAULTS))
def test_a_fault_planted_in_the_reference_comes_out_not_correct(tmp_path,
                                                                fault):
  """The reference computing another model than the program is what a wrong
  kernel, gate or router in the program looks like to the check."""
  tiny = os.path.dirname(TINY_LFM2)
  for part in ('configs', 'traffic'):
    shutil.copytree(os.path.join(tiny, part), str(tmp_path / part))
  shutil.copy(TINY_LFM2, str(tmp_path / 'BENCHMARK.json'))
  path = str(tmp_path / 'configs' / 'tiny_lfm2.json')
  config = cells.load_json(path)
  key, value = _FAULTS[fault]
  assert config['reference']['settings'][key] != value
  config['reference']['settings'][key] = value
  with open(path, 'w') as f:
    json.dump(config, f)
  result = helpers.run_cell(tmp_path, 'tiny_lfm2',
                            manifest=str(tmp_path / 'BENCHMARK.json'))
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  assert helpers.last_json_line(result.stdout)['correct'] is False
  said = [line for line in result.stdout.splitlines() if 'INCORRECT' in line]
  assert any('(5) the first step\'s gradient' in line for line in said), said


# -- the cost function --------------------------------------------------------


def test_the_step_cost_by_hand():
  s = _settings(TINY_CONFIG)
  cost = lfm2_costs.step_cost(s, 2, 32, pairs_held=400.0)
  rows = 2 * 32
  conv_layer = 2.0 * rows * 128 * (3 * 128 + 128)
  attention_layer = 2.0 * rows * 128 * (2 * 128 + 2 * 64)
  dense_mlp = 3 * 2.0 * rows * 128 * 192
  routers = 4 * 2.0 * rows * 128 * 8
  head = 2.0 * 2 * 31 * 128 * 64
  assert lfm2_costs.dense_forward_flops(s, 2, 32) == (
      4 * conv_layer + attention_layer + dense_mlp + routers + head)
  assert cost['dot']['flops'] == 3 * lfm2_costs.dense_forward_flops(s, 2, 32)
  assert cost['dot']['calls'] == 3 * (4 * 2 + 4 + 3 + 4 + 1)
  assert cost['attention']['flops'] == 3 * 4.0 * 32 * 4 * (32 * 33 // 2) * 2
  assert cost['experts']['flops'] == 3 * 6.0 * 128 * 64 * 400.0
  # The kernel pair: 7 operations an element forward; what counts is bytes:
  # 4 elements a token a channel forward, 7 backward, the taps once each.
  assert cost['short_conv']['flops'] == 3 * 7.0 * rows * 128 * 4
  assert cost['short_conv']['bytes'] == 4 * (
      (4 + 7) * rows * 128 * 2 + 2 * 3 * 128 * 4)
  assert cost['flops'] == sum(cost[family]['flops'] for family in (
      'dot', 'short_conv', 'attention', 'experts'))
  assert cost['conv'] == {'flops': 0.0, 'bytes': 0.0, 'calls': 0}
  assert cost['layers'] == {'held': 5, 'conv': 4, 'attention': 1,
                            'experts': 4}
  # The real sizes: ISSUE 34's count, 432 MFLOP a token forward (134 in the
  # convolutions' projections, 88 dense SwiGLU, 88 experts at one pair a
  # token a layer, 67 head, 34 attention over the band, 21 its projections),
  # 2.13e13 a step at 2 sequences; the kernel must move 268 MB forward and
  # 470 MB backward a layer at 2 x 8,192 tokens.
  real = _settings(REAL_CONFIG)
  at_two = lfm2_costs.step_cost(real, 2, 8192, 4 * 16384.0)
  assert at_two['flops'] / (3 * 16384) == pytest.approx(432.6e6, rel=1e-3)
  assert at_two['flops'] == pytest.approx(2.13e13, rel=0.01)
  assert lfm2_costs.short_conv_call_bytes(real, 2, 8192, False) == \
      pytest.approx(268e6, rel=0.01)
  assert lfm2_costs.short_conv_call_bytes(real, 2, 8192, True) == \
      pytest.approx(470e6, rel=0.01)
  shipped = lfm2_costs.step_cost(real, 3, 8192, 4 * 24576.0)
  assert shipped['flops'] == pytest.approx(1.5 * at_two['flops'], rel=1e-3)


def test_the_cost_functions_bytes_are_the_kernels_operands_and_results():
  """The least traffic the cost function counts for one call is what the
  kernel's own signature moves at the cell's shape: every operand read and
  every result written once (the filter and its gradient in float32)."""
  import jax
  import jax.numpy as jnp
  from tensor2robot_tpu.parallel import short_conv

  real = _settings(REAL_CONFIG)
  shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype)
  bcx, dy = shape(3, 8192, 6144), shape(3, 8192, 2048)
  taps = shape(2048, 3, dtype=jnp.float32)
  nbytes = lambda *arrays: sum(
      a.size * a.dtype.itemsize for a in jax.tree.leaves(arrays))
  y = jax.eval_shape(short_conv.short_conv_fwd, bcx, taps)
  d_bcx, d_taps = jax.eval_shape(short_conv.short_conv_bwd, bcx, taps, dy)
  assert (y.shape, d_bcx.shape, d_taps.shape) == (
      dy.shape, bcx.shape, taps.shape)
  assert lfm2_costs.short_conv_call_bytes(real, 3, 8192, False) == \
      nbytes(bcx, taps, y)
  assert lfm2_costs.short_conv_call_bytes(real, 3, 8192, True) == \
      nbytes(bcx, dy, d_bcx, d_taps)


def test_the_dense_count_equals_the_jaxpr_of_the_plain_reference():
  """``costs.py`` counts every matrix product of a jaxpr; on the plain
  reference (no kernel hides anything) that is the dense products, the
  attention's two products over the WHOLE square and every held expert
  over every token."""
  import jax
  import numpy as np

  from benchmark.harness import common, lfm2_reference

  s = _settings(TINY_CONFIG)
  model = common.build_model(cells.load_json(TINY_CONFIG)['model'])
  tokens = jax.ShapeDtypeStruct((1, 32), np.int32)
  params = jax.eval_shape(
      lambda t: model.create_train_state(jax.random.PRNGKey(0), {'tokens': t},
                                         None), tokens).params
  counted = costs.program_cost(
      lambda p, t: lfm2_reference.loss(p, t, s), params, tokens)
  square = 4.0 * 32 * 4 * 32 * 32            # one attention layer
  experts = 4 * 4 * 6.0 * 128 * 64 * 32      # 4 layers x 4 held x 32 tokens
  head_last_row = 2.0 * 128 * 64             # the reference's head runs on L
  assert counted['flops'] == pytest.approx(
      lfm2_costs.dense_forward_flops(s, 1, 32) + head_last_row + square +
      experts, rel=1e-12)


# -- the readers ----------------------------------------------------------------

Record = collections.namedtuple('Record', 'name thread start_ns end_ns attrs')


def _observation():
  peaks = {'bf16_flops_per_s': 100e12, 'hbm_bytes_per_s': 1e12}
  return {
      'chips': 1, 'peaks': peaks, 'window_s': 10.0, 'steps': 20,
      'examples_per_step': 2,
      'counters': {'before': {'span/train.step/count': 3.0},
                   'after': {'span/train.step/count': 23.0}},
      'trace': {'chips': 1,
                'modules': {'jit_step(1)': [0.5, 0.5], 'jit_other': [0.01]},
                'families': {'flash_attention_fwd': 0.08,
                             'flash_attention_bwd_dkv': 0.07,
                             'flash_attention_bwd_dq': 0.05,
                             'moe_grouped_matmul': 0.02,
                             'moe_grouped_matmul_nt': 0.01,
                             'moe_grouped_matmul_dw': 0.01,
                             'moe_take_rows': 0.02, 'moe_sum_rows': 0.04,
                             'short_conv_fwd': 0.024, 'short_conv_bwd': 0.016,
                             'fusion kOutput': 0.3}},
      'cost': {'attention': {'flops': 5e12, 'bytes': 1e9},
               'experts': {'flops': 1e12, 'bytes': 5e9},
               'short_conv': {'flops': 1e9, 'bytes': 8e9},
               'layers': {'held': 5, 'conv': 4, 'attention': 1,
                          'experts': 4}},
      'moe': {'pairs_held_per_step': 384.0, 'tokens_per_step': 320.0,
              'load_max_over_mean': 2.5, 'dropped_pairs': 0.0},
  }


def test_the_readers_on_a_hand_made_observation(monkeypatch):
  module, obs = _module(), _observation()
  readers = module.METRICS
  assert tuple(readers) == NEW
  # Two steps traced: 0.02 s of the two convolution kernels a step; 8e9
  # bytes are 0.008 s at 1e12 B/s (1e9 operations are nothing): 40%.
  assert readers['lfm2_short_conv_roofline'](obs) == pytest.approx(40.0)
  # 0.1 s of attention a step for 5e12 FLOPs at 100e12/s.
  assert readers['lfm2_attention_roofline'](obs) == pytest.approx(50.0)
  # 0.02 s of grouped products a step; 1e12 FLOPs are 0.01 s at the peak.
  assert readers['lfm2_expert_matmul_roofline'](obs) == pytest.approx(50.0)
  # All ten kernels: 0.1 + 0.02 + 0.03 + 0.02 s a step of 0.5.
  assert readers['lfm2_kernels_step_share'](obs) == pytest.approx(0.17 / 0.5)
  # 384 pairs over 64 tokens x the FOUR expert layers of the five held.
  assert readers['lfm2_pairs_held_per_token'](obs) == pytest.approx(1.5)
  assert readers['lfm2_expert_load_max_over_mean'](obs) == 2.5
  assert readers['lfm2_dropped_pairs'](obs) == 0.0

  # The ring: the window is found as program_trace finds it, from the
  # data.next that follows train.step number 3 to the one after number 23.
  from benchmark.metrics import program_trace

  second = 10**9
  records = []
  for n in range(1, 25):
    records.append(Record('train.step', 'main', n * second // 2 - 1000,
                          n * second // 2, {'step': n}))
    records.append(Record('data.next', 'main', n * second // 2 + 10,
                          n * second // 2 + 20, {}))
    attrs = {'step': n, 'steps_covered': 1}
    if n % 2:   # every other event carries the value
      attrs['moe/chosen_load_max_over_mean'] = 1 + 1e-3 * n
    records.append(Record('train.step_done', 'watch', n * second // 2 + 500,
                          n * second // 2 + 500, attrs))
  monkeypatch.setattr(program_trace, 'read_ring', lambda: (records, 0))
  # The events of steps 3, 5, .. 21 fall in the window; that of step 23
  # ends after the data.next that closes it.
  inside = list(range(3, 22, 2))
  assert readers['lfm2_chosen_load_max_over_mean'](obs) == pytest.approx(
      1 + 1e-3 * sum(inside) / len(inside))
  monkeypatch.setattr(program_trace, 'read_ring', lambda: (records, 5))
  # A torn ring:
  assert readers['lfm2_chosen_load_max_over_mean'](obs) is None
  monkeypatch.setattr(program_trace, 'read_ring', lambda: None)
  assert readers['lfm2_chosen_load_max_over_mean'](obs) is None


@pytest.mark.parametrize('missing', ['trace', 'cost', 'moe', 'families',
                                     'short_conv'])
def test_a_reader_with_nothing_to_read_returns_none(missing):
  """``short_conv`` missing from the cost is ANOTHER token cell's cost: none
  of these metrics reads there, whatever else that observation holds."""
  readers, obs = _module().METRICS, _observation()
  if missing == 'families':
    obs['trace']['families'] = {'fusion kOutput': 0.3}
  elif missing == 'short_conv':
    del obs['cost']['short_conv']
  else:
    obs[missing] = None
  trace_readers = {'lfm2_short_conv_roofline', 'lfm2_attention_roofline',
                   'lfm2_expert_matmul_roofline', 'lfm2_kernels_step_share'}
  expected_none = {
      'trace': trace_readers, 'families': trace_readers,
      'cost': set(NEW), 'short_conv': set(NEW),
      'moe': {'lfm2_pairs_held_per_token', 'lfm2_expert_load_max_over_mean',
              'lfm2_dropped_pairs'},
  }[missing]
  for name in NEW:
    if (name == 'lfm2_chosen_load_max_over_mean' and
        name not in expected_none):
      continue   # the program's ring, not the observation
    assert (readers[name](obs) is None) == (name in expected_none), name


def test_the_parents_program_reads_nothing_and_raises_nothing():
  """What the driver does with these files laid over the parent's checkout,
  in a traced run of a cell the parent has: the other token cells' cost has
  no ``short_conv``, no kernel of that name is in the trace, no event carries
  the attribute."""
  from benchmark.harness import token_costs

  module, obs = _module(), _observation()
  settings = dict(
      hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=32,
      num_experts=8, experts_held=(0, 4), vocab_rows=64, window=8,
      window_layers=(False, True))
  obs['cost'] = token_costs.step_cost(settings, 2, 32, 100.0)
  del obs['trace']['families']['short_conv_fwd']
  del obs['trace']['families']['short_conv_bwd']
  for name in NEW:
    assert module.METRICS[name](obs) is None, name


# -- the manifests ----------------------------------------------------------------


@pytest.mark.parametrize('path, cell', [(helpers.REAL, CELL),
                                        (TINY_LFM2, 'tiny_lfm2')],
                         ids=['real', 'tiny_lfm2'])
def test_the_manifests_list_the_eight_with_just_the_contracts_keys(path,
                                                                   cell):
  manifest = cells.load_json(path)
  readers = cells.metric_readers()
  entries = {m['name']: m for m in manifest['per_layer']}
  layers = {'lfm2_short_conv_roofline': 'kernels',
            'lfm2_kernels_step_share': 'train step',
            'lfm2_chosen_load_max_over_mean': 'expert layers'}
  for name in NEW:
    entry = entries[name]
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert entry['workloads'] == [cell]
    assert entry['moves'] == 'train_examples_per_s_per_chip'
    assert entry['layer'] == layers.get(name, entry['layer'])
    assert name in readers
  assert entries['lfm2_short_conv_roofline']['unit'] == '%'
  assert entries['lfm2_short_conv_roofline']['source'] == 'device_trace'
  assert entries['lfm2_chosen_load_max_over_mean']['source'] == \
      'program_span'
  assert entries['lfm2_chosen_load_max_over_mean']['unit'] == 'ratio'
  names = cells.Cell(path, cell).metric_names('per_layer')
  assert set(NEW) <= set(names)
  assert cells.Cell(path, cell).traffic['kind'] == 'train_tokens'


def test_the_other_cells_do_not_list_the_eight():
  manifest = cells.load_json(helpers.REAL)
  for workload in manifest['workloads']:
    if workload['name'] != CELL:
      names = cells.Cell(helpers.REAL, workload['name']).metric_names(
          'per_layer')
      assert not set(NEW) & set(names), workload['name']
  # and this cell lists none of the other token cells' own
  mine = cells.Cell(helpers.REAL, CELL).metric_names('per_layer')
  assert not [name for name in mine
              if name.startswith(('bd_', 'moe_', 'new_kernels')) or
              name == 'attention_roofline']
  assert {'mfu', 'step_device_ms', 'train_peak_hbm_gb',
          'conv_roofline'} <= set(mine)


def test_the_tiny_manifest_of_its_own_differs_by_its_cell_and_the_eight():
  traced, mine = cells.load_json(TINY_TRACE), cells.load_json(TINY_LFM2)
  for key in ('command', 'paths', 'run_seconds', 'end_to_end'):
    assert traced[key] == mine[key]
  assert mine['per_layer'][:-8] == traced['per_layer']
  assert len(mine['configs']) == len(mine['workloads']) == 1


def test_the_real_manifest_holds_the_configuration_and_the_cell():
  """By NAME: entries go at the end of their lists and later PRs add more."""
  manifest = cells.load_json(helpers.REAL)
  entry = next(c for c in manifest['configs'] if c['name'] == CONFIG)
  assert entry['source'] == \
      'https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json'
  assert entry['file'] == 'benchmark/configs/' + CONFIG + '.json'
  assert entry['reduced'] == ['num_hidden_layers', 'num_dense_layers',
                              'num_experts', 'vocab_size']
  cell = next(w for w in manifest['workloads'] if w['name'] == CELL)
  assert (cell['config'], cell['traffic'], cell['chips']) == (
      CONFIG, 'packed8k', 1)
  assert len(cell['why']) <= 200 and 'quarter' in cell['why']
  assert len(entry['why']) <= 200
  four = [w for w in manifest['workloads'] if w['chips'] == 4]
  assert len(four) <= max(1, len(manifest['workloads']) // 4)
  # Nothing that was there moved: the three configurations and cells of
  # PRs 24, 28 and 32 are still the first three, in their order.
  assert [c['name'] for c in manifest['configs']][:3] == [
      'grasp2vec_resnet50', 'smallthinker_21b_a3b_ep4share',
      'sdar_30b_a3b_ep8share']
  assert [w['name'] for w in manifest['workloads']][:3] == [
      'grasp2vec_train_disk', 'smallthinker_train_packed8k',
      'sdar_train_bd4_packed8k']


# -- the configuration's file ----------------------------------------------------


def test_the_configuration_file_keeps_the_published_widths():
  """Every key of the public config.json (the catalog row) under its own
  name; the four reduced keys at what this chip holds, the published counts
  and the deployment beside them; the model's keyword arguments agree."""
  config = cells.load_json(REAL_CONFIG)
  published = {
      'conv_L_cache': 3, 'conv_bias': False, 'hidden_size': 2048,
      'intermediate_size': 7168, 'layer_types': PUBLISHED_LAYER_TYPES,
      'max_position_embeddings': 128000, 'model_type': 'lfm2_moe',
      'moe_intermediate_size': 1792, 'norm_eps': 1e-05,
      'norm_topk_prob': True, 'num_attention_heads': 32,
      'num_dense_layers': 2, 'num_experts': 32, 'num_experts_per_tok': 4,
      'num_hidden_layers': 24, 'num_key_value_heads': 8,
      'rope_theta': 1000000, 'routed_scaling_factor': 1,
      'use_expert_bias': True, 'vocab_size': 65536,
  }
  held = {'num_hidden_layers': 5, 'num_dense_layers': 1, 'num_experts': 8,
          'vocab_size': 16384}
  assert config['reduced'] == list(held)
  assert config['source'].endswith('LiquidAI/LFM2-8B-A1B/blob/main/'
                                   'config.json')
  for key, value in published.items():
    assert config[key] == held.get(key, value), key
    if key in held:
      assert config['published'][key] == value
  assert config['deployment']['chips_sharing_each_layer'] == 4
  for key in ('experts', 'vocabulary', 'replicated', 'depth', 'held_here',
              'expert_load', 'router_bias'):
    assert config['deployment'][key]
  assert '507,820,160' in config['deployment']['held_here']
  conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
  attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
  experts = 2048 * 32 + 8 * 3 * 2048 * 1792
  assert (conv + 3 * 2048 * 7168 + 4096) + (attention + experts + 4096) + \
      3 * (conv + experts + 4096) + 16384 * 2048 + 2048 == 507820160
  kwargs = config['model']['kwargs']
  for key in ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
              'intermediate_size', 'moe_intermediate_size',
              'num_experts_per_tok', 'num_hidden_layers', 'num_dense_layers',
              'layer_types', 'conv_L_cache', 'conv_bias', 'norm_topk_prob',
              'use_expert_bias', 'routed_scaling_factor', 'rope_theta',
              'norm_eps'):
    assert kwargs[key] == config[key], key
  assert kwargs['num_experts'] == 32                    # the router's width
  assert kwargs['experts_held'] == [0, config['num_experts']]
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['first_layer'] == 1
  held_kinds = config['layer_types'][1:6]
  assert held_kinds == ['conv', 'full_attention', 'conv', 'conv', 'conv']
  assert config['reference']['settings']['layer_types'] == held_kinds
  assert kwargs['sequence_length'] == 8192
  assert config['train']['batch_per_chip'] in (2, 3)
  assert config['train']['gradient_kept_in_state'] == 'mu'
  for key in ('chunk order', 'tap', 'intermediate_size', 'tied', 'bias',
              'initialisation', 'learning rate', 'experts_held', 'vocab',
              'document mask', 'rotate', 'q/k norm'):
    assert any(key in name or key in text
               for name, text in config['assumed'].items()), key
  for tolerance in ('step_rel_tolerance', 'reference_rel_tolerance',
                    'grad_norm_rel_tolerance',
                    'group_grad_norm_rel_tolerance',
                    'gradient_difference_tolerance'):
    assert 0 < config['train'][tolerance] <= 0.05
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
  assert train_tokens._named(plain['cost']) is lfm2_costs.step_cost
  layers = config['num_hidden_layers']
  same = {
      'hidden_size': 'hidden_size', 'num_heads': 'num_attention_heads',
      'num_kv_heads': 'num_key_value_heads',
      'dense_dim': 'intermediate_size',
      'expert_dim': 'moe_intermediate_size', 'num_experts': 'num_experts',
      'experts_held': 'experts_held', 'top_k': 'num_experts_per_tok',
      'num_dense_layers': 'num_dense_layers', 'rope_theta': 'rope_theta',
      'eps': 'norm_eps', 'vocab_rows': 'vocab_rows',
  }
  for setting, keyword in same.items():
    assert plain['settings'][setting] == kwargs[keyword], setting
  assert plain['settings']['head_dim'] == \
      kwargs['hidden_size'] // kwargs['num_attention_heads']
  first = kwargs['first_layer']
  assert plain['settings']['layer_types'] == \
      kwargs['layer_types'][first:first + layers]
  assert plain['settings']['window_layers'] == [False] * layers
  assert kwargs.get('routed_scaling_factor', 1) == 1    # no other is built
  right = {'taps': 'causal', 'c_gate': True, 'router': 'sigmoid',
           'renormalise': True, 'qk_norm': True}
  for setting, value in right.items():
    assert plain['settings'][setting] == value, setting
  # No bias in the file: the cell's first step starts from zeros.
  assert set(plain['settings']) == set(same) | set(right) | {
      'head_dim', 'layer_types', 'window_layers', 'query_block',
      'head_block'}
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['num_hidden_layers'] == layers
  # The model can be built from the file as the driver builds it.
  model = common.build_model(config['model'])
  assert model.traced_step_metrics == ('moe/chosen_load_max_over_mean',)
  assert model.report_gradient_norm


def test_the_reference_imports_nothing_of_the_program():
  import ast

  allowed = {'lfm2_reference.py': {'jax'}, 'lfm2_costs.py': {'benchmark'}}
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
