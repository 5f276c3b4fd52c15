"""The GLM-4.7-Flash configuration's part of the benchmark: ONE tiny traced
run of the one command on the CPU, shared by the tests that read it; the
cost function by hand and against the plain reference's jaxpr; the readers
on a hand-made observation and on other cells' observations; the manifests'
new entries (looked up BY NAME); and the configuration's file held to the
catalog row."""

import collections
import importlib.util
import os

import pytest

import bench_helpers as helpers
from benchmark.harness import cells, glm_costs

TINY_GLM = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                        'BENCHMARK_glm.json')
TINY_TRACE = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                          'BENCHMARK_program_trace.json')
NEW = ('glm_attention_roofline', 'glm_expert_matmul_roofline',
       'glm_kernels_step_share', 'glm_pairs_held_per_token',
       'glm_dropped_pairs', 'glm_chosen_load_max_over_mean')
TRACE_READERS = {'glm_attention_roofline', 'glm_expert_matmul_roofline',
                 'glm_kernels_step_share'}
CONFIG = 'glm47_flash_ep8share'
CELL = 'glm47_flash_train_mtp_packed8k'
REAL_CONFIG = os.path.join(helpers.ROOT, 'benchmark', 'configs',
                           CONFIG + '.json')
TINY_CONFIG = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                           'configs', 'tiny_glm.json')
# The catalog row's 'config' (the public config.json).
PUBLISHED = {
    'attention_bias': False, 'hidden_act': 'silu', 'hidden_size': 2048,
    'intermediate_size': 10240, 'max_position_embeddings': 202752,
    'model_type': 'glm4_moe_lite', 'moe_intermediate_size': 1536,
    'topk_method': 'noaux_tc', 'norm_topk_prob': True,
    'num_attention_heads': 20, 'n_group': 1, 'topk_group': 1,
    'n_routed_experts': 64, 'n_shared_experts': 1,
    'routed_scaling_factor': 1.8, 'num_experts_per_tok': 4,
    'first_k_dense_replace': 1, 'num_hidden_layers': 47,
    'num_key_value_heads': 20, 'num_nextn_predict_layers': 1,
    'partial_rotary_factor': 1, 'rms_norm_eps': 1e-05, 'rope_scaling': None,
    'rope_theta': 1000000, 'tie_word_embeddings': False, 'q_lora_rank': 768,
    'kv_lora_rank': 512, 'qk_nope_head_dim': 192, 'qk_rope_head_dim': 64,
    'v_head_dim': 256, 'vocab_size': 154880,
}
HELD = {'num_hidden_layers': 5, 'n_routed_experts': 8, 'vocab_size': 19360}


def _module():
  spec = importlib.util.spec_from_file_location(
      'glm_under_test', os.path.join(cells.METRICS_DIR, 'glm.py'))
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


def _settings(path):
  """The plain reference's settings as the driver hands them over."""
  from benchmark.harness import common

  return dict(common._tuples(cells.load_json(path)['reference']['settings']))


# -- the one command ----------------------------------------------------------


@pytest.fixture(scope='module')
def tiny_run(tmp_path_factory):
  return helpers.run_cell(tmp_path_factory.mktemp('tiny_glm'), 'tiny_glm',
                          trace=1, manifest=TINY_GLM)


def test_the_tiny_cell_runs_through_the_one_command(tiny_run):
  result = tiny_run
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  line = helpers.last_json_line(result.stdout)
  assert set(line) - {'breakdown'} == helpers.RESULT_KEYS
  assert line['correct'] is True and line['failed'] == 0
  assert line['attempted'] > 0 and line['device']['platform'] == 'cpu'
  metrics = {k: v['value'] for k, v in line['metrics'].items()}
  # The CPU trace has no device plane and no kernel runs there: the readers
  # of the trace find nothing and are left out; the others read.
  assert set(NEW) - TRACE_READERS | {'window_compiles'} <= set(metrics)
  assert not (TRACE_READERS | {'mfu'}) & set(metrics)
  assert metrics['glm_dropped_pairs'] == 0
  assert metrics['window_compiles'] == 0
  # 4 of 8 experts held, 4 chosen: 2 a token an EXPERT layer, the MTP's
  # among them.
  assert 1.0 < metrics['glm_pairs_held_per_token'] < 3.0
  assert metrics['glm_chosen_load_max_over_mean'] >= 1
  for said in ('(1) loss of the first batch', '(2) loss of the first batch',
               '(3) global norm', '(4) norm of the first step\'s gradient by',
               '(5) the first step\'s gradient, read back from',
               '(6) the parameters after the first step', 'whole steps',
               'set-up', 'grad_group_norm/mtp', 'main/loss', 'mtp/loss'):
    assert said in result.stdout, said


# -- the cost function --------------------------------------------------------


def test_the_step_cost_by_hand():
  s = _settings(TINY_CONFIG)
  cost = glm_costs.step_cost(s, 2, 32, pairs_held=400.0)
  rows = 2 * 32
  # q_a, q_b, kv_a, kv_b, out at 64 wide, 4 heads of 16 + 16 and values 32.
  attention = 2.0 * rows * (64 * 16 + 16 * 4 * 32 + 64 * 32 + 16 * 4 * 48 +
                            4 * 32 * 64)
  dense_mlp = 3 * 2.0 * rows * 64 * 96
  shared = 3 * 2.0 * rows * 64 * 32
  routers = 3 * 2.0 * rows * 64 * 8
  eh_proj = 2.0 * rows * 128 * 64
  heads = 2.0 * 2 * (31 + 30) * 64 * 64
  # Three trunk layers (one dense) and the MTP's: four latent layers, three
  # expert layers.
  assert glm_costs.dense_forward_flops(s, 2, 32) == (
      4 * attention + dense_mlp + 3 * shared + routers + eh_proj + heads)
  assert cost['dot']['flops'] == 3 * glm_costs.dense_forward_flops(s, 2, 32)
  assert cost['dot']['calls'] == 3 * (4 * 8 + 1 + 3 + 2)
  # 2 x (32 + 32) x 4 heads a pair of the band, 4 latent layers, 2
  # sequences.
  assert cost['attention']['flops'] == 3 * 512.0 * (32 * 33 // 2) * 2 * 4
  assert cost['experts']['flops'] == 3 * 6.0 * 64 * 32 * 400.0
  assert cost['flops'] == sum(cost[family]['flops'] for family in (
      'dot', 'attention', 'experts'))
  assert cost['conv'] == {'flops': 0.0, 'bytes': 0.0, 'calls': 0}
  assert cost['layers'] == {'held': 3, 'attention': 4, 'experts': 3,
                            'mtp': 1}
  # The real size: 2.970e13 FLOPs a step at one pair a token an expert
  # layer's eighth, 151 ms at the bf16 peak; attention over six latent
  # layers at 256 / 256.
  real = _settings(REAL_CONFIG)
  shipped = glm_costs.step_cost(real, 1, 8192, 8192 * 4 * 5 / 8.0)
  assert shipped['flops'] == pytest.approx(2.970e13, rel=2e-3)
  assert shipped['attention']['flops'] == pytest.approx(
      3 * 2 * 512 * 20 * 8192 * 8193 / 2 * 6, rel=1e-12)
  assert shipped['layers'] == {'held': 5, 'attention': 6, 'experts': 5,
                               'mtp': 1}


def test_the_dense_count_equals_the_jaxpr_of_the_plain_reference():
  """``costs.py`` counts every matrix product of a jaxpr; on the plain
  reference (no kernel hides anything) that is the dense products, eh_proj,
  both head passes over EVERY row, the attention's two products over the
  WHOLE square and every held expert over every token."""
  import jax
  import numpy as np

  from benchmark.harness import common, costs, glm_reference

  s = _settings(TINY_CONFIG)
  model = common.build_model(cells.load_json(TINY_CONFIG)['model'])
  tokens = jax.ShapeDtypeStruct((1, 32), np.int32)
  params = jax.eval_shape(
      lambda t: model.create_train_state(jax.random.PRNGKey(0), {'tokens': t},
                                         None), tokens).params
  counted = costs.program_cost(
      lambda p, t: glm_reference.loss(p, t, s), params, tokens)
  square = 2.0 * (32 + 32) * 4 * 32 * 32 * 4    # four latent layers
  experts = 3 * 4 * 6.0 * 64 * 32 * 32         # 3 layers x 4 held x 32
  head_tail_rows = 2.0 * 64 * 64 * (1 + 2)     # the reference's heads run on L
  assert counted['flops'] == pytest.approx(
      glm_costs.dense_forward_flops(s, 1, 32) + head_tail_rows + square +
      experts, rel=1e-12)


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
                             'fusion kOutput': 0.3}},
      'cost': {'attention': {'flops': 5e12, 'bytes': 1e9},
               'experts': {'flops': 1e12, 'bytes': 5e9},
               'layers': {'held': 5, 'attention': 6, 'experts': 5,
                          'mtp': 1}},
      'moe': {'pairs_held_per_step': 160.0, 'tokens_per_step': 320.0,
              'load_max_over_mean': 2.5, 'dropped_pairs': 0.0},
  }


def test_the_readers_on_a_hand_made_observation(monkeypatch):
  module, obs = _module(), _observation()
  readers = module.METRICS
  assert tuple(readers) == NEW
  # Two steps traced: 0.1 s of attention a step for 5e12 FLOPs at 100e12/s.
  assert readers['glm_attention_roofline'](obs) == pytest.approx(50.0)
  # 0.02 s of grouped products a step; 1e12 FLOPs are 0.01 s at the peak.
  assert readers['glm_expert_matmul_roofline'](obs) == pytest.approx(50.0)
  # All eight kernels: 0.1 + 0.02 + 0.03 s a step of 0.5.
  assert readers['glm_kernels_step_share'](obs) == pytest.approx(0.15 / 0.5)
  # 160 pairs over 64 tokens x the FIVE expert layers (the MTP's among
  # them) of the five trunk layers held.
  assert readers['glm_pairs_held_per_token'](obs) == pytest.approx(0.5)
  assert readers['glm_dropped_pairs'](obs) == 0.0

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
      attrs['moe/chosen_load_max_over_mean'] = 1.0 + 0.1 * n
    records.append(Record('train.step_done', 'watch', n * second // 2 + 500,
                          n * second // 2 + 500, attrs))
  monkeypatch.setattr(program_trace, 'read_ring', lambda: (records, 0))
  inside = list(range(3, 22, 2))
  assert readers['glm_chosen_load_max_over_mean'](obs) == pytest.approx(
      1.0 + 0.1 * sum(inside) / len(inside))
  for ring in ((records, 5), None):
    monkeypatch.setattr(program_trace, 'read_ring', lambda ring=ring: ring)
    assert readers['glm_chosen_load_max_over_mean'](obs) is None


@pytest.mark.parametrize('missing', ['trace', 'cost', 'moe', 'families',
                                     'mtp'])
def test_a_reader_with_nothing_to_read_returns_none(missing, monkeypatch):
  """``mtp`` missing from the cost's layers is ANOTHER token cell's cost:
  none of these metrics reads there, whatever else that observation
  holds."""
  from benchmark.metrics import program_trace

  monkeypatch.setattr(program_trace, 'read_ring', lambda: None)
  readers, obs = _module().METRICS, _observation()
  if missing == 'families':
    obs['trace']['families'] = {'fusion kOutput': 0.3}
  elif missing == 'mtp':
    del obs['cost']['layers']['mtp']
  else:
    obs[missing] = None
  expected_none = {
      'trace': TRACE_READERS, 'families': TRACE_READERS,
      'cost': set(NEW), 'mtp': set(NEW),
      'moe': {'glm_pairs_held_per_token', 'glm_dropped_pairs',
              'glm_chosen_load_max_over_mean'},
  }[missing]
  for name in NEW:
    if name == 'glm_chosen_load_max_over_mean':
      continue   # the program's ring, empty here
    assert (readers[name](obs) is None) == (name in expected_none), name


@pytest.mark.parametrize('other', ['token_costs', 'xing_costs'])
def test_the_other_cells_and_the_parents_program_read_nothing(other):
  """What a traced run of another token cell does with these files, on this
  tree or over a checkout that has no such model: their cost has no ``mtp``
  layer, so nothing reads and nothing raises."""
  from benchmark.harness import token_costs, xing_costs

  module, obs = _module(), _observation()
  if other == 'token_costs':
    settings = dict(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        expert_dim=32, num_experts=8, experts_held=(0, 4), vocab_rows=64,
        window=8, window_layers=(False, True))
    obs['cost'] = token_costs.step_cost(settings, 2, 32, 100.0)
  else:
    obs['cost'] = xing_costs.step_cost(
        _settings(os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                               'configs', 'tiny_xing.json')), 2, 32, 100.0)
  for name in NEW:
    assert module.METRICS[name](obs) is None, name


# -- the manifests ----------------------------------------------------------------


@pytest.mark.parametrize('path, cell', [(helpers.REAL, CELL),
                                        (TINY_GLM, 'tiny_glm')],
                         ids=['real', 'tiny_glm'])
def test_the_manifests_list_the_six_by_name_with_just_the_contracts_keys(
    path, cell):
  manifest = cells.load_json(path)
  readers = cells.metric_readers()
  entries = {m['name']: m for m in manifest['per_layer']}
  layers = {'glm_attention_roofline': 'kernels',
            'glm_expert_matmul_roofline': 'kernels',
            'glm_kernels_step_share': 'train step',
            'glm_pairs_held_per_token': 'expert layers',
            'glm_dropped_pairs': 'expert layers',
            'glm_chosen_load_max_over_mean': 'expert layers'}
  sources = {'glm_attention_roofline': 'device_trace',
             'glm_expert_matmul_roofline': 'device_trace',
             'glm_kernels_step_share': 'device_trace',
             'glm_pairs_held_per_token': 'program_counter',
             'glm_dropped_pairs': 'program_counter',
             'glm_chosen_load_max_over_mean': 'program_span'}
  for name in NEW:
    entry = entries[name]
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert entry['workloads'] == [cell]
    assert entry['moves'] == 'train_examples_per_s_per_chip'
    assert entry['layer'] == layers[name]
    assert entry['source'] == sources[name]
    assert name in readers
  assert entries['glm_attention_roofline']['unit'] == '%'
  assert entries['glm_kernels_step_share']['unit'] == 'share'
  names = cells.Cell(path, cell).metric_names('per_layer')
  assert set(NEW) <= set(names)
  assert cells.Cell(path, cell).traffic['kind'] == 'train_tokens'


def test_the_other_cells_do_not_list_the_six():
  manifest = cells.load_json(helpers.REAL)
  for workload in manifest['workloads']:
    if workload['name'] != CELL:
      names = cells.Cell(helpers.REAL, workload['name']).metric_names(
          'per_layer')
      assert not set(NEW) & set(names), workload['name']
  mine = cells.Cell(helpers.REAL, CELL).metric_names('per_layer')
  assert not [name for name in mine
              if name.startswith(('bd_', 'moe_', 'new_kernels', 'lfm2_',
                                  'xing_')) or name == 'attention_roofline']
  assert {'mfu', 'step_device_ms', 'train_peak_hbm_gb', 'conv_roofline',
          'setup_trace_s'} <= set(mine)


def test_the_tiny_manifest_of_its_own_differs_by_its_cell_and_the_six():
  traced, mine = cells.load_json(TINY_TRACE), cells.load_json(TINY_GLM)
  for key in ('command', 'paths', 'run_seconds', 'end_to_end'):
    assert traced[key] == mine[key]
  assert mine['per_layer'][:-len(NEW)] == traced['per_layer']
  assert len(mine['configs']) == len(mine['workloads']) == 1


def test_the_real_manifest_holds_the_configuration_and_the_cell():
  """By NAME: entries go at the end of their lists and later PRs add more."""
  manifest = cells.load_json(helpers.REAL)
  entry = next(c for c in manifest['configs'] if c['name'] == CONFIG)
  assert entry['source'] == ('https://huggingface.co/zai-org/GLM-4.7-Flash/'
                             'blob/main/config.json')
  assert entry['file'] == 'benchmark/configs/' + CONFIG + '.json'
  assert entry['reduced'] == list(HELD)
  cell = next(w for w in manifest['workloads'] if w['name'] == CELL)
  assert (cell['config'], cell['traffic'], cell['chips']) == (
      CONFIG, 'packed8k', 1)
  for why in (cell['why'], entry['why']):
    assert len(why) <= 200
  assert 'eighth' in cell['why'] and 'MTP' in cell['why']
  assert '256/256' in cell['why']
  # Nothing that was there moved: the five configurations and cells of PRs
  # 24 to 39 are still the first five, in their order.
  assert [c['name'] for c in manifest['configs']][:5] == [
      'grasp2vec_resnet50', 'smallthinker_21b_a3b_ep4share',
      'sdar_30b_a3b_ep8share', 'lfm2_8b_a1b_ep4share',
      'xing4_29b_a4b_ep8share']
  assert [w['name'] for w in manifest['workloads']][:5] == [
      'grasp2vec_train_disk', 'smallthinker_train_packed8k',
      'sdar_train_bd4_packed8k', 'lfm2_train_packed8k',
      'xing4_train_packed4k']


# -- the configuration's file ----------------------------------------------------


def test_the_configuration_file_keeps_the_catalog_rows_keys():
  """Every key of the public config.json (the catalog row) under its own
  name; the three reduced keys at what this chip holds, the published counts
  and the deployment beside them; the model's keyword arguments agree."""
  config = cells.load_json(REAL_CONFIG)
  assert config['reduced'] == list(HELD)
  assert config['source'] == ('https://huggingface.co/zai-org/GLM-4.7-Flash/'
                              'blob/main/config.json')
  for key, value in PUBLISHED.items():
    assert config[key] == HELD.get(key, value), key
    if key in HELD:
      assert config['published'][key] == value
  assert config['num_nextn_predict_layers'] == 1
  assert config['deployment']['chips_sharing_each_layer'] == 8
  for key in ('experts', 'vocabulary', 'replicated', 'depth', 'held_here',
              'expert_load', 'router_bias', 'multi_token_prediction'):
    assert config['deployment'][key]
  assert '706,518,528' in config['deployment']['held_here']
  attention = (2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512 +
               512 * 20 * 448 + 5120 * 2048)
  assert attention == 21759232
  dense = attention + 2 * 2048 + 3 * 2048 * 10240
  expert = attention + 2 * 2048 + 9 * 3 * 2048 * 1536 + 2048 * 64
  mtp = 3 * 2048 + 4096 * 2048 + expert
  assert dense + 4 * expert + 2 * 19360 * 2048 + 2048 + mtp == 706518528
  kwargs = config['model']['kwargs']
  for key in PUBLISHED:
    if key not in HELD and key not in ('max_position_embeddings',
                                       'model_type'):
      assert kwargs[key] == config[key], key
  assert kwargs['n_routed_experts'] == 64               # the router's width
  assert kwargs['experts_held'] == [0, config['n_routed_experts']]
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['num_hidden_layers'] == config['num_hidden_layers']
  assert kwargs['sequence_length'] == 8192
  assert kwargs['mtp_loss_weight'] == 0.3
  assert config['train']['batch_per_chip'] == 1
  assert config['train']['gradient_kept_in_state'] == 'mu'
  for key in ('initialisation', 'learning rate', 'experts_held', 'vocab',
              'document mask', 'rotary', 'router', 'multi-token prediction',
              'mtp_loss_weight', 'where the MTP reads h', 'planted faults'):
    assert any(key in name for name in config['assumed']), key
  for tolerance in ('step_rel_tolerance', 'reference_rel_tolerance',
                    'grad_norm_rel_tolerance',
                    'group_grad_norm_rel_tolerance',
                    'gradient_difference_tolerance'):
    assert 0 < config['train'][tolerance] <= 0.1
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
  assert train_tokens._named(plain['cost']) is glm_costs.step_cost
  same = {
      'hidden_size': 'hidden_size', 'num_heads': 'num_attention_heads',
      'q_lora_rank': 'q_lora_rank', 'kv_lora_rank': 'kv_lora_rank',
      'qk_nope_head_dim': 'qk_nope_head_dim',
      'qk_rope_head_dim': 'qk_rope_head_dim', 'v_head_dim': 'v_head_dim',
      'rope_theta': 'rope_theta', 'dense_dim': 'intermediate_size',
      'expert_dim': 'moe_intermediate_size',
      'shared_expert_dim': 'moe_intermediate_size',
      'num_experts': 'n_routed_experts', 'experts_held': 'experts_held',
      'top_k': 'num_experts_per_tok',
      'num_dense_layers': 'first_k_dense_replace',
      'routed_scaling': 'routed_scaling_factor', 'eps': 'rms_norm_eps',
      'vocab_rows': 'vocab_rows', 'mtp_weight': 'mtp_loss_weight',
  }
  for setting, keyword in same.items():
    assert plain['settings'][setting] == kwargs[keyword], setting
  assert plain['settings']['window_layers'] == [False] * kwargs[
      'num_hidden_layers']
  right = {'mtp_target_shift': 2, 'mtp_embedding_shift': 1,
           'mtp_concat': 'embedding_first', 'enorm': True, 'hnorm': True,
           'mtp_reads': 'normed', 'mtp_head_norm': 'own',
           'shared_expert': True, 'scale_width': 'key'}
  for setting, value in right.items():
    assert plain['settings'][setting] == value, setting
  # No bias in the file: the cell's first step starts from zeros.
  assert set(plain['settings']) == set(same) | set(right) | {
      'window_layers', 'query_block', 'head_block'}
  # The model can be built from the file as the driver builds it.
  model = common.build_model(config['model'])
  assert model.traced_step_metrics == (
      'moe/chosen_load_max_over_mean', 'main/loss', 'mtp/loss')
  assert model.report_gradient_norm


def test_the_reference_imports_nothing_of_the_program():
  import ast

  allowed = {'glm_reference.py': {'jax', 'math'},
             'glm_costs.py': {'benchmark'}}
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
