"""The block-diffusion configuration's part of the benchmark: the tiny cell
through the one command on the CPU (untraced and traced), planted faults
coming out ``correct: false``, the cost function against a brute-force count
of the mask and the jaxpr of the plain reference, the readers on a hand-made
observation, the manifests' new entries, and the configuration's file."""

import collections
import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

import bench_helpers as helpers
from benchmark.harness import cells, costs, sdar_costs

TINY_BD = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                       'BENCHMARK_block_diffusion.json')
TINY_TRACE = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                          'BENCHMARK_program_trace.json')
NEW = ('bd_attention_roofline', 'bd_attention_pairs_computed_over_needed',
       'bd_expert_matmul_roofline', 'bd_kernels_step_share',
       'bd_masked_position_share', 'bd_pairs_held_per_position',
       'bd_expert_load_max_over_mean', 'bd_dropped_pairs')
CONFIG = 'sdar_30b_a3b_ep8share'
CELL = 'sdar_train_bd4_packed8k'
REAL_CONFIG = os.path.join(helpers.ROOT, 'benchmark', 'configs',
                           CONFIG + '.json')
TINY_CONFIG = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                           'configs', 'tiny_sdar.json')


def _module():
  spec = importlib.util.spec_from_file_location(
      'block_diffusion_under_test',
      os.path.join(cells.METRICS_DIR, 'block_diffusion.py'))
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
  result = helpers.run_cell(tmp_path, 'tiny_bd', trace=trace,
                            manifest=TINY_BD)
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
    # The CPU trace has no device plane and the CPU's attention is dense
    # (no kernel is traced, so no gauge is set): the readers of the trace
    # and of the gauges find nothing and are left out; the others read.
    assert {'bd_masked_position_share', 'bd_pairs_held_per_position',
            'bd_expert_load_max_over_mean', 'bd_dropped_pairs',
            'window_compiles', 'wire_bytes_per_example'} <= set(metrics)
    assert not {'bd_attention_roofline', 'bd_expert_matmul_roofline',
                'bd_kernels_step_share',
                'bd_attention_pairs_computed_over_needed',
                'mfu'} & set(metrics)
    assert metrics['bd_dropped_pairs'] == 0
    assert metrics['window_compiles'] == 0
    # Half the positions are masked on average (t uniform on [1e-3, 1]).
    assert 0.3 < metrics['bd_masked_position_share'] < 0.7
    # 4 of 8 experts held, 3 of 8 chosen: 1.5 a position a layer expected.
    assert 0.8 < metrics['bd_pairs_held_per_position'] < 2.4
    assert metrics['bd_expert_load_max_over_mean'] >= 1
    assert metrics['wire_bytes_per_example'] == 32 * 4
  for said in ('(1) loss of the first batch', '(2) loss of the first batch',
               '(3) global norm', '(4) norm of the first step\'s gradient by',
               '(5) the first step\'s gradient, read back from',
               '(6) the parameters after the first step',
               'diffusion/masked_positions', 'whole steps', 'set-up'):
    assert said in result.stdout, said


_FAULTS = {
    'the_mask_taken_as_plain_causal': ('mask', 'causal'),
    'clean_tokens_token_causal_not_block_causal': ('mask',
                                                   'clean_token_causal'),
    'the_loss_shifted_by_one': ('loss_shift', 1),
    'the_weight_left_out': ('loss_weight', '1'),
    'the_norm_of_q_and_k_left_out': ('qk_norm', False),
    'relu_for_silu': ('gate', 'relu'),
    'another_noise_than_the_steps': ('trainer_seed', 1),
    'experts_paired_with_their_neighbours_routing': ('experts_held', [3, 4]),
}


@pytest.mark.parametrize('fault', sorted(_FAULTS))
def test_a_fault_planted_in_the_reference_comes_out_not_correct(tmp_path,
                                                                fault):
  """The reference computing another model than the program is what a wrong
  layer, mask or loss in the program looks like to the check."""
  tiny = os.path.dirname(TINY_BD)
  for part in ('configs', 'traffic'):
    shutil.copytree(os.path.join(tiny, part), str(tmp_path / part))
  shutil.copy(TINY_BD, str(tmp_path / 'BENCHMARK.json'))
  path = str(tmp_path / 'configs' / 'tiny_sdar.json')
  config = cells.load_json(path)
  key, value = _FAULTS[fault]
  assert config['reference']['settings'][key] != value
  config['reference']['settings'][key] = value
  with open(path, 'w') as f:
    json.dump(config, f)
  result = helpers.run_cell(tmp_path, 'tiny_bd',
                            manifest=str(tmp_path / 'BENCHMARK.json'))
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  assert helpers.last_json_line(result.stdout)['correct'] is False
  said = [line for line in result.stdout.splitlines() if 'INCORRECT' in line]
  assert any('(5) the first step\'s gradient' in line or
             '(2) loss of the first batch' in line for line in said), said


# -- the cost function --------------------------------------------------------


@pytest.mark.parametrize('length, block', [(8, 4), (32, 4), (24, 1), (32, 32),
                                           (24, 3)])
def test_the_masks_pairs_by_brute_force(length, block):
  brute = 0
  for i in range(2 * length):
    for j in range(2 * length):
      i_block, j_block = (i % length) // block, (j % length) // block
      if i < length:
        brute += (i_block == j_block) if j < length else (j_block < i_block)
      else:
        brute += j >= length and j_block <= i_block
  assert sdar_costs.mask_pairs(length, block) == brute
  assert brute == length * length + length * block


def test_the_real_masks_pairs_are_a_quarter_of_the_square():
  assert sdar_costs.mask_pairs(8192, 4) == 67141632
  assert sdar_costs.mask_pairs(8192, 4) / (16384 ** 2) == pytest.approx(
      0.25, rel=1e-3)


def test_the_dense_count_equals_the_jaxpr_of_the_plain_reference():
  """The plain reference computes the dense [2L, 2L] attention and every
  held expert over every position, so its jaxpr holds the dense products
  (what is checked), the full square and experts x positions (by hand)."""
  import jax

  from benchmark.harness import common, sdar_reference as plain
  from tensor2robot_tpu.research.sdar import SDARModel

  s = _settings(TINY_CONFIG)
  batch, length = 2, 32
  model = common.build_model(cells.load_json(TINY_CONFIG)['model'])
  assert isinstance(model, SDARModel)
  tokens = jax.ShapeDtypeStruct((batch, length), np.int32)
  params = jax.eval_shape(
      lambda t: model.create_train_state(jax.random.PRNGKey(0), {'tokens': t},
                                         None), tokens).params
  counted = costs.program_cost(
      lambda p, t: plain.loss(p, t, s), params, tokens)
  layers = len(s['window_layers'])
  square = 4.0 * s['head_dim'] * s['num_heads'] * (2 * length) ** 2 * batch
  every_position = (s['experts_held'][1] * 6.0 * batch * 2 * length *
                    s['hidden_size'] * s['expert_dim'])
  assert counted['flops'] == pytest.approx(
      sdar_costs.dense_forward_flops(s, batch, length) +
      layers * (square + every_position), rel=1e-12)


def test_the_step_cost_by_hand():
  s = _settings(TINY_CONFIG)
  cost = sdar_costs.step_cost(s, 2, 32, pairs_held=600.0)
  assert cost['attention']['flops'] == 3 * 4.0 * 16 * 4 * 3 * (
      32 * 32 + 32 * 4) * 2
  assert cost['experts']['flops'] == 3 * 6.0 * 64 * 32 * 600.0
  assert cost['dot']['flops'] == 3 * sdar_costs.dense_forward_flops(s, 2, 32)
  assert cost['flops'] == (cost['dot']['flops'] + cost['attention']['flops'] +
                           cost['experts']['flops'])
  assert cost['conv'] == {'flops': 0.0, 'bytes': 0.0, 'calls': 0}
  assert cost['sequence'] == {'length': 32, 'positions': 64,
                              'block_length': 4, 'mask_pairs': 1152}
  # The real sizes: the ISSUE's count, 6 x 5.65e12 + 1.91e12 = 3.58e13 a
  # step at 98,304 pairs held (one a position a layer).
  real = _settings(REAL_CONFIG)
  assert sdar_costs.step_cost(real, 1, 8192, 98304.0)['flops'] == \
      pytest.approx(3.58e13, rel=0.01)
  assert sdar_costs.attention_forward_flops(real, 1, 8192) / 6 == \
      pytest.approx(1.10e12, rel=0.01)
  assert sdar_costs.dense_forward_flops(real, 1, 8192) == pytest.approx(
      6 * 6.27e11 + 1.91e12 / 3, rel=0.01)


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
                             'fusion kOutput': 0.3}},
      'cost': {'attention': {'flops': 5e12, 'bytes': 1e9},
               'experts': {'flops': 1e12, 'bytes': 5e9},
               'sequence': {'length': 32, 'positions': 64, 'block_length': 4,
                            'mask_pairs': 1152}},
      'moe': {'pairs_held_per_step': 192.0, 'tokens_per_step': 64.0,
              'load_max_over_mean': 2.5, 'dropped_pairs': 0.0},
  }


def test_the_readers_on_a_hand_made_observation(monkeypatch):
  module, obs = _module(), _observation()
  readers = module.METRICS
  assert tuple(readers) == NEW
  # Two steps traced: 0.1 s of attention a step for 5e12 FLOPs at 100e12/s.
  assert readers['bd_attention_roofline'](obs) == pytest.approx(50.0)
  # 0.02 s of grouped products a step; 1e12 FLOPs are 0.01 s at the peak
  # and 5e9 bytes 0.005 s: compute bound, 50%.
  assert readers['bd_expert_matmul_roofline'](obs) == pytest.approx(50.0)
  # All eight kernels: 0.1 + 0.02 + 0.03 s a step of 0.5.
  assert readers['bd_kernels_step_share'](obs) == pytest.approx(0.15 / 0.5)
  # 192 pairs over 2 x 64 positions-layers.
  assert readers['bd_pairs_held_per_position'](obs) == 1.5
  assert readers['bd_expert_load_max_over_mean'](obs) == 2.5
  assert readers['bd_dropped_pairs'](obs) == 0.0

  gauges = {'attention/mask_pairs_needed': 1000.0,
            'attention/mask_pairs_computed': 1300.0,
            'attention/mask_pairs_computed_bwd': 1200.0}
  monkeypatch.setattr(module, '_gauge', gauges.get)
  assert readers['bd_attention_pairs_computed_over_needed'](obs) == \
      pytest.approx((1300 + 2 * 1200) / 3000)
  del gauges['attention/mask_pairs_computed_bwd']
  assert readers['bd_attention_pairs_computed_over_needed'](obs) is None

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
    if n % 2:   # every other event carries the value: 30 of 64 masked
      attrs['diffusion/masked_positions'] = 30.0
    records.append(Record('train.step_done', 'watch', n * second // 2 + 500,
                          n * second // 2 + 500, attrs))
  monkeypatch.setattr(program_trace, 'read_ring', lambda: (records, 0))
  assert readers['bd_masked_position_share'](obs) == pytest.approx(
      30.0 / (32 * 2))
  monkeypatch.setattr(program_trace, 'read_ring', lambda: (records, 5))
  assert readers['bd_masked_position_share'](obs) is None   # a torn ring
  monkeypatch.setattr(program_trace, 'read_ring', lambda: None)
  assert readers['bd_masked_position_share'](obs) is None


@pytest.mark.parametrize('missing', ['trace', 'cost', 'moe', 'families',
                                     'sequence'])
def test_a_reader_with_nothing_to_read_returns_none(missing):
  """``sequence`` missing is the OTHER token cell's cost: none of these
  metrics reads there, whatever else that cell's observation holds."""
  readers, obs = _module().METRICS, _observation()
  if missing == 'families':
    obs['trace']['families'] = {'fusion kOutput': 0.3}
  elif missing == 'sequence':
    del obs['cost']['sequence']
  else:
    obs[missing] = None
  trace_readers = {'bd_attention_roofline', 'bd_expert_matmul_roofline',
                   'bd_kernels_step_share'}
  expected_none = {
      'trace': trace_readers, 'families': trace_readers,
      'cost': set(NEW), 'sequence': set(NEW),
      'moe': {'bd_pairs_held_per_position', 'bd_expert_load_max_over_mean',
              'bd_dropped_pairs'},
  }[missing]
  for name in NEW:
    if name in ('bd_attention_pairs_computed_over_needed',
                'bd_masked_position_share') and name not in expected_none:
      continue   # the program's gauges and ring, not the observation
    assert (readers[name](obs) is None) == (name in expected_none), name


def test_the_parents_program_reads_nothing_and_raises_nothing():
  """What the driver does with these files laid over the parent's checkout:
  no gauge was ever set, no event carries the attribute."""
  module, obs = _module(), _observation()
  assert module._gauge('attention/never_set_by_any_program') is None
  obs['counters'] = None
  assert module.METRICS['bd_masked_position_share'](obs) is None


# -- the manifests ----------------------------------------------------------------


@pytest.mark.parametrize('path, cell', [(helpers.REAL, CELL),
                                        (TINY_BD, 'tiny_bd')],
                         ids=['real', 'tiny_block_diffusion'])
def test_the_manifests_list_the_eight_with_just_the_contracts_keys(path,
                                                                   cell):
  manifest = cells.load_json(path)
  readers = cells.metric_readers()
  entries = {m['name']: m for m in manifest['per_layer']}
  for name in NEW:
    entry = entries[name]
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert entry['workloads'] == [cell]
    assert entry['moves'] == 'train_examples_per_s_per_chip'
    assert name in readers
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
  # and this cell lists none of the other token cell's six
  mine = cells.Cell(helpers.REAL, CELL).metric_names('per_layer')
  assert not {'attention_roofline', 'moe_grouped_matmul_roofline',
              'new_kernels_step_share', 'moe_pairs_held_per_token'} & set(
                  mine)
  assert {'mfu', 'step_device_ms', 'train_peak_hbm_gb'} <= set(mine)


def test_the_tiny_manifest_of_its_own_differs_by_its_cell_and_the_eight():
  traced, mine = cells.load_json(TINY_TRACE), cells.load_json(TINY_BD)
  for key in ('command', 'paths', 'run_seconds', 'end_to_end'):
    assert traced[key] == mine[key]
  assert mine['per_layer'][:-8] == traced['per_layer']
  assert len(mine['configs']) == len(mine['workloads']) == 1


def test_the_real_manifest_holds_the_configuration_and_the_cell():
  manifest = cells.load_json(helpers.REAL)
  entry = next(c for c in manifest['configs'] if c['name'] == CONFIG)
  assert entry['source'] == \
      'https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json'
  assert entry['file'] == 'benchmark/configs/' + CONFIG + '.json'
  assert entry['reduced'] == ['num_hidden_layers', 'num_experts',
                              'vocab_size']
  cell = next(w for w in manifest['workloads'] if w['name'] == CELL)
  assert (cell['config'], cell['traffic'], cell['chips']) == (
      CONFIG, 'packed8k', 1)
  assert len(cell['why']) <= 200 and 'eighth' in cell['why']
  assert len(entry['why']) <= 200
  four = [w for w in manifest['workloads'] if w['chips'] == 4]
  assert len(four) <= max(1, len(manifest['workloads']) // 4)


# -- the configuration's file ----------------------------------------------------


def test_the_configuration_file_keeps_the_published_widths():
  """Every number of the public config.json under its own key; the three
  reduced keys at what this chip holds, the published counts and the
  deployment beside them; the model's keyword arguments agree with them."""
  config = cells.load_json(REAL_CONFIG)
  published = {
      'attention_bias': False, 'decoder_sparse_step': 1, 'head_dim': 128,
      'hidden_act': 'silu', 'hidden_size': 2048, 'intermediate_size': 6144,
      'max_position_embeddings': 32768, 'max_window_layers': 48,
      'mlp_only_layers': [], 'model_type': 'sdar_moe',
      'moe_intermediate_size': 768, 'norm_topk_prob': True,
      'num_attention_heads': 32, 'num_experts': 128,
      'num_experts_per_tok': 8, 'num_hidden_layers': 48,
      'num_key_value_heads': 4, 'rms_norm_eps': 1e-06, 'rope_scaling': None,
      'rope_theta': 1000000, 'sliding_window': None,
      'tie_word_embeddings': False, 'use_sliding_window': False,
      'vocab_size': 151936,
  }
  held = {'num_hidden_layers': 6, 'num_experts': 16, 'vocab_size': 18992}
  assert config['reduced'] == list(held)
  assert config['source'].endswith('JetLM/SDAR-30B-A3B-Chat/blob/main/'
                                   'config.json')
  for key, value in published.items():
    assert config[key] == held.get(key, value), key
    if key in held:
      assert config['published'][key] == value
  assert config['deployment']['chips_sharing_each_layer'] == 8
  for key in ('experts', 'vocabulary', 'replicated', 'depth', 'held_here',
              'expert_load'):
    assert config['deployment'][key]
  assert '645,623,296' in config['deployment']['held_here']
  assert 6 * (19140864 + 16 * 4718592) + 2 * 18992 * 2048 + 2048 == 645623296
  kwargs = config['model']['kwargs']
  for key in ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
              'head_dim', 'moe_intermediate_size', 'num_experts_per_tok',
              'num_hidden_layers', 'rope_theta', 'rms_norm_eps'):
    assert kwargs[key] == config[key], key
  assert kwargs['num_experts'] == 128                   # the router's width
  assert kwargs['experts_held'] == [0, config['num_experts']]
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['mask_token_id'] == config['vocab_size'] - 1
  assert kwargs['sequence_length'] == 8192 and kwargs['block_length'] == 4
  assert config['train']['batch_per_chip'] == 1
  assert config['train']['gradient_kept_in_state'] == 'mu'
  for key in ('q/k norm', 'block length', 'noise schedule', 'mask id',
              'initialisation', 'learning rate', 'experts_held', 'vocab',
              'document mask', 'bias', 'rotate', 'router'):
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
  cost function and the settings, which say what the model's keywords say
  (and what the trainer's default seed is)."""
  import inspect

  from benchmark.harness import common, train_tokens
  from tensor2robot_tpu.trainer.train_eval import Trainer

  config = cells.load_json(path)
  plain, kwargs = config['reference'], config['model']['kwargs']
  assert callable(train_tokens._named(plain['loss']))
  assert train_tokens._named(plain['cost']) is sdar_costs.step_cost
  layers = config['num_hidden_layers']
  same = {
      'hidden_size': 'hidden_size', 'num_heads': 'num_attention_heads',
      'num_kv_heads': 'num_key_value_heads', 'head_dim': 'head_dim',
      'expert_dim': 'moe_intermediate_size', 'num_experts': 'num_experts',
      'experts_held': 'experts_held', 'top_k': 'num_experts_per_tok',
      'rope_theta': 'rope_theta', 'eps': 'rms_norm_eps',
      'vocab_rows': 'vocab_rows', 'block_length': 'block_length',
      'noise_eps': 'noise_eps', 'mask_token_id': 'mask_token_id',
  }
  for setting, keyword in same.items():
    assert plain['settings'][setting] == kwargs[keyword], setting
  assert plain['settings']['window_layers'] == [False] * layers
  right = {'mask': 'block_diffusion', 'qk_norm': True, 'gate': 'silu',
           'loss_weight': '1/t', 'loss_shift': 0}
  for setting, value in right.items():
    assert plain['settings'][setting] == value, setting
  assert set(plain['settings']) == set(same) | set(right) | {
      'window_layers', 'trainer_seed', 'query_block', 'head_block'}
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['num_hidden_layers'] == layers
  trainer_kwargs = config['train'].get('trainer_kwargs', {})
  assert plain['settings']['trainer_seed'] == trainer_kwargs.get(
      'seed', inspect.signature(Trainer.__init__).parameters['seed'].default)
  # The model can be built from the file as the driver builds it.
  model = common.build_model(config['model'])
  assert model.traced_step_metrics and model.report_gradient_norm


def test_the_reference_imports_nothing_of_the_program():
  import ast

  with open(os.path.join(helpers.ROOT, 'benchmark', 'harness',
                         'sdar_reference.py')) as f:
    tree = ast.parse(f.read())
  imported = set()
  for node in ast.walk(tree):
    if isinstance(node, ast.Import):
      imported |= {alias.name for alias in node.names}
    elif isinstance(node, ast.ImportFrom):
      imported.add(node.module)
  assert imported == {'jax', 'jax.numpy'}
