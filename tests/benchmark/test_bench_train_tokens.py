"""Kind ``train_tokens``: the one command end to end at a tiny size on the CPU
(untraced and traced), the token writer, the analytic cost functions, the
new readers on a hand-made observation, and the manifests' new entries."""

import importlib.util
import os

import numpy as np
import pytest

import bench_helpers as helpers
from benchmark.harness import cells, costs, token_costs, token_records

TINY_TOKENS = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                           'BENCHMARK_train_tokens.json')
TINY_TRACE = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                          'BENCHMARK_program_trace.json')
NEW = ('attention_roofline', 'moe_grouped_matmul_roofline',
       'new_kernels_step_share', 'moe_pairs_held_per_token',
       'moe_expert_load_max_over_mean', 'moe_dropped_pairs')
CONFIG = 'smallthinker_21b_a3b_ep4share'
CELL = 'smallthinker_train_packed8k'


def _readers():
  spec = importlib.util.spec_from_file_location(
      'moe_attention_under_test',
      os.path.join(cells.METRICS_DIR, 'moe_attention.py'))
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module.METRICS


# -- the one command ----------------------------------------------------------


@pytest.mark.parametrize('trace', [0, 1], ids=['untraced', 'traced'])
def test_the_tiny_cell_runs_through_the_one_command(tmp_path, trace):
  result = helpers.run_cell(tmp_path, 'tiny_tokens', trace=trace,
                            manifest=TINY_TOKENS)
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
    # The CPU trace has no device plane: the trace readers find nothing and
    # are left out; the counters' readers read.
    assert {'moe_pairs_held_per_token', 'moe_expert_load_max_over_mean',
            'moe_dropped_pairs', 'input_wait_share', 'window_compiles',
            'wire_bytes_per_example'} <= set(metrics)
    assert not {'attention_roofline', 'moe_grouped_matmul_roofline',
                'new_kernels_step_share', 'mfu'} & set(metrics)
    assert metrics['moe_dropped_pairs'] == 0
    assert metrics['window_compiles'] == 0
    # 4 of 8 experts held, 3 of 8 chosen: 1.5 a token a layer expected.
    assert 0.8 < metrics['moe_pairs_held_per_token'] < 2.4
    assert metrics['moe_expert_load_max_over_mean'] >= 1
    assert metrics['wire_bytes_per_example'] == 32 * 4
  for said in ('(1) loss of the first batch', '(2) loss of the first batch',
               '(3) global norm', '(4) norm of the first step\'s gradient by',
               '(5) the first step\'s gradient, read back from',
               '(6) the parameters after the first step',
               'inputs from the seed',
               'expert layers over the window', 'whole steps', 'set-up'):
    assert said in result.stdout, said


_FAULTS = {
    'window_ignored': ('window_layers', [False] * 4),
    'full_layer_given_rotary_positions': ('rope_layers', [True] * 4),
    'experts_paired_with_their_neighbours_routing': ('experts_held', [3, 4]),
}


@pytest.mark.parametrize('fault', sorted(_FAULTS))
def test_a_fault_planted_in_the_reference_comes_out_not_correct(tmp_path,
                                                                fault):
  """The reference computing another model than the program is what a wrong
  layer in the program looks like to the check: the run must say so, by
  the comparisons of the first step's gradient, not by the loss alone."""
  import json
  import shutil

  tiny = os.path.dirname(TINY_TOKENS)
  for part in ('configs', 'traffic'):
    shutil.copytree(os.path.join(tiny, part), str(tmp_path / part))
  shutil.copy(TINY_TOKENS, str(tmp_path / 'BENCHMARK.json'))
  path = str(tmp_path / 'configs' / 'tiny_smallthinker.json')
  config = cells.load_json(path)
  key, value = _FAULTS[fault]
  assert config['reference']['settings'][key] != value
  config['reference']['settings'][key] = value
  with open(path, 'w') as f:
    json.dump(config, f)
  result = helpers.run_cell(tmp_path, 'tiny_tokens',
                            manifest=str(tmp_path / 'BENCHMARK.json'))
  assert result.returncode == 0, result.stdout[-3000:] + result.stderr[-3000:]
  assert helpers.last_json_line(result.stdout)['correct'] is False
  said = [line for line in result.stdout.splitlines() if 'INCORRECT' in line]
  assert any('(5) the first step\'s gradient' in line for line in said), said


# -- the writer -----------------------------------------------------------------

_STREAM = dict(total=4096, vocab=100, zipf_exponent=1.0, median=24, sigma=1.2,
               shortest=4, longest=64)


def test_the_token_stream_is_the_same_for_the_same_seed():
  a, lengths_a = token_records.token_stream(3000000019, **_STREAM)
  b, lengths_b = token_records.token_stream(3000000019, **_STREAM)
  c, _ = token_records.token_stream(3000000020, **_STREAM)
  assert np.array_equal(a, b) and np.array_equal(lengths_a, lengths_b)
  assert not np.array_equal(a, c)
  assert a.dtype == np.int32 and a.shape == (4096,)


def test_every_id_is_inside_the_slice_and_documents_end_where_the_lengths_say():
  tokens, lengths = token_records.token_stream(7, **_STREAM)
  assert tokens.min() == 0 and tokens.max() <= 99
  assert lengths.min() >= 4 and lengths.max() <= 64
  assert lengths.sum() >= 4096 > lengths[:-1].sum()
  ends = np.cumsum(lengths) - 1
  assert np.array_equal(np.flatnonzero(tokens == 0), ends[ends < 4096])
  # Zipf: the commonest id is drawn far more often than a uniform draw would.
  counts = np.bincount(tokens[tokens > 0], minlength=100)
  assert counts[1] == counts.max() > 5 * counts[1:].mean()


def test_records_parse_back_to_the_stream(tmp_path):
  import tensorflow as tf

  path = str(tmp_path / 'tokens.tfrecord')
  size = token_records.write_token_records(
      path, 'tokens', 8, 512, 11, 40000, 1.0, 100, 1.2, 4, 512)
  assert size == os.path.getsize(path)
  tokens, _ = token_records.token_stream(11, 8 * 512, 40000, 1.0, 100, 1.2, 4,
                                         512)
  assert tokens.max() >= 1 << 14   # three-byte varints are exercised
  rows = []
  for raw in tf.data.TFRecordDataset(path):
    example = tf.train.Example.FromString(raw.numpy())
    rows.append(list(example.features.feature['tokens'].int64_list.value))
  assert np.array_equal(np.asarray(rows), tokens.reshape(8, 512))


def test_the_varints_refuse_what_they_cannot_write():
  with pytest.raises(ValueError):
    token_records._varints([1 << 21])


# -- the cost functions -------------------------------------------------------


REAL_CONFIG = os.path.join(helpers.ROOT, 'benchmark', 'configs',
                           CONFIG + '.json')
TINY_CONFIG = os.path.join(helpers.ROOT, 'tests', 'benchmark', 'tiny',
                           'configs', 'tiny_smallthinker.json')


def _settings(path):
  """The plain reference's settings as the driver hands them over."""
  from benchmark.harness import common

  return dict(common._tuples(cells.load_json(path)['reference']['settings']))


def _tiny_settings():
  return _settings(TINY_CONFIG)


def test_band_pairs_by_hand():
  assert token_costs.band_pairs(4, None) == 10
  assert token_costs.band_pairs(4, 4) == 10
  # window 2: rows see 1, 2, 2, 2 columns.
  assert token_costs.band_pairs(4, 2) == 7
  assert token_costs.band_pairs(8192, 4096) == (
      4096 * 4097 // 2 + 4096 * 4096)
  brute = sum(1 for i in range(32) for j in range(32) if j <= i < j + 8)
  assert token_costs.band_pairs(32, 8) == brute


def test_the_dense_count_equals_the_jaxpr_of_the_plain_reference():
  """The plain reference computes dense L x L attention and every held
  expert over every token, so its jaxpr holds the dense products (what is
  checked), the full square of attention and experts x tokens (by hand)."""
  import jax

  from benchmark.harness import smallthinker_reference as plain

  s = _tiny_settings()
  batch, length = 2, 32
  from tensor2robot_tpu.research.smallthinker import SmallThinkerModel

  config = cells.load_json(TINY_CONFIG)
  from benchmark.harness import common

  model = common.build_model(config['model'])
  assert isinstance(model, SmallThinkerModel)
  tokens = jax.ShapeDtypeStruct((batch, length), np.int32)
  params = jax.eval_shape(
      lambda t: model.create_train_state(jax.random.PRNGKey(0), {'tokens': t},
                                         None), tokens).params
  counted = costs.program_cost(
      lambda p, t: plain.loss(p, t, s), params, tokens)
  layers = len(s['window_layers'])
  square = 4.0 * s['head_dim'] * s['num_heads'] * length * length * batch
  every_token = (s['experts_held'][1] * 6.0 * batch * length *
                 s['hidden_size'] * s['expert_dim'])
  assert counted['flops'] == pytest.approx(
      token_costs.dense_forward_flops(s, batch, length) +
      layers * (square + every_token), rel=1e-12)


def test_the_step_cost_by_hand():
  s = _tiny_settings()
  cost = token_costs.step_cost(s, 2, 32, pairs_held=400.0)
  band = token_costs.band_pairs(32, None) + 3 * token_costs.band_pairs(32, 8)
  assert cost['attention']['flops'] == 3 * 4.0 * 16 * 4 * band * 2
  assert cost['experts']['flops'] == 3 * 6.0 * 64 * 32 * 400.0
  assert cost['dot']['flops'] == 3 * token_costs.dense_forward_flops(s, 2, 32)
  assert cost['flops'] == (cost['dot']['flops'] + cost['attention']['flops'] +
                           cost['experts']['flops'])
  assert cost['conv'] == {'flops': 0.0, 'bytes': 0.0, 'calls': 0}
  # The real sizes: the ISSUE's 30.7 TFLOP a step at 24,600 pairs a layer.
  assert token_costs.step_cost(_settings(REAL_CONFIG), 2, 8192,
                               4 * 24600.0)['flops'] == \
      pytest.approx(30.7e12, rel=0.02)


# -- the readers ----------------------------------------------------------------


def _observation():
  peaks = {'bf16_flops_per_s': 100e12, 'hbm_bytes_per_s': 1e12}
  return {
      'chips': 1, 'peaks': peaks, 'window_s': 10.0, 'steps': 20,
      'trace': {'chips': 1,
                'modules': {'jit_step(1)': [0.5, 0.5], 'jit_other': [0.01]},
                'families': {'flash_attention_fwd': 0.08,
                             'flash_attention_bwd_dkv': 0.07,
                             'flash_attention_bwd_dq': 0.05,
                             'moe_grouped_matmul': 0.02,
                             'moe_grouped_matmul_nt': 0.01,
                             'moe_grouped_matmul_dw': 0.01,
                             'fusion kOutput': 0.3}},
      'cost': {'attention': {'flops': 5e12, 'bytes': 1e9},
               'experts': {'flops': 1e12, 'bytes': 5e9}},
      'moe': {'pairs_held_per_step': 96.0, 'tokens_per_step': 64.0,
              'load_max_over_mean': 2.5, 'dropped_pairs': 0.0},
  }


def test_the_readers_on_a_hand_made_observation():
  readers, obs = _readers(), _observation()
  assert set(readers) == set(NEW)
  # Two steps traced: 0.1 s of attention a step for 5e12 FLOPs at 100e12/s.
  assert readers['attention_roofline'](obs) == pytest.approx(50.0)
  # 0.02 s of grouped products a step; 1e12 FLOPs are 0.01 s at the peak
  # and 5e9 bytes 0.005 s: compute bound, 50%.
  assert readers['moe_grouped_matmul_roofline'](obs) == pytest.approx(50.0)
  assert readers['new_kernels_step_share'](obs) == pytest.approx(
      (0.1 + 0.02) / 0.5)
  assert readers['moe_pairs_held_per_token'](obs) == 1.5
  assert readers['moe_expert_load_max_over_mean'](obs) == 2.5
  assert readers['moe_dropped_pairs'](obs) == 0.0


@pytest.mark.parametrize('missing', ['trace', 'cost', 'moe', 'families'])
def test_a_reader_with_nothing_to_read_returns_none(missing):
  readers, obs = _readers(), _observation()
  if missing == 'families':
    obs['trace']['families'] = {'fusion kOutput': 0.3}
  else:
    obs[missing] = None
  expected_none = {
      'trace': NEW[:3], 'cost': NEW[:2], 'moe': NEW[3:], 'families': NEW[:3],
  }[missing]
  for name in NEW:
    value = readers[name](obs)
    assert (value is None) == (name in expected_none), name


# -- the manifests ----------------------------------------------------------------


@pytest.mark.parametrize('path, cell', [(helpers.REAL, CELL),
                                        (TINY_TOKENS, 'tiny_tokens')],
                         ids=['real', 'tiny_train_tokens'])
def test_the_manifests_list_the_six_with_just_the_contracts_keys(path, cell):
  manifest = cells.load_json(path)
  readers = cells.metric_readers()
  assert [m['name'] for m in manifest['per_layer']][-6:] == list(NEW)
  for entry in manifest['per_layer'][-6:]:
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert entry['workloads'] == [cell]
    assert entry['moves'] == 'train_examples_per_s_per_chip'
    assert entry['name'] in readers
  names = cells.Cell(path, cell).metric_names('per_layer')
  assert set(NEW) <= set(names)
  assert cells.Cell(path, cell).traffic['kind'] == 'train_tokens'


def test_the_tiny_manifest_of_its_own_differs_by_its_cell_and_the_six():
  traced, tokens = cells.load_json(TINY_TRACE), cells.load_json(TINY_TOKENS)
  for key in ('command', 'paths', 'run_seconds', 'end_to_end'):
    assert traced[key] == tokens[key]
  assert tokens['per_layer'][:-6] == traced['per_layer']
  assert len(tokens['configs']) == len(tokens['workloads']) == 1


def test_the_real_manifest_gained_one_configuration_and_one_cell():
  manifest = cells.load_json(helpers.REAL)
  entry = manifest['configs'][-1]
  assert entry['name'] == CONFIG
  assert entry['source'].endswith('/config.json') and \
      len(entry['source']) <= 200
  assert entry['reduced'] == ['num_hidden_layers', 'moe_num_primary_experts',
                              'vocab_size']
  cell = manifest['workloads'][-1]
  assert (cell['name'], cell['config'], cell['traffic'], cell['chips']) == (
      CELL, CONFIG, 'packed8k', 1)
  assert len(cell['why']) <= 200 and 'quarter' in cell['why']


def test_the_configuration_file_keeps_the_published_widths():
  """Every number of the public config.json under its own key; the three
  reduced keys at what this chip holds, the published counts and the
  deployment beside them; the model's keyword arguments agree with them."""
  config = cells.load_json(REAL_CONFIG)
  published = {
      'head_dim': 128, 'hidden_size': 2560, 'max_position_embeddings': 16384,
      'moe_ffn_hidden_size': 768, 'moe_num_active_primary_experts': 6,
      'moe_num_primary_experts': 64, 'num_attention_heads': 28,
      'num_hidden_layers': 52, 'num_key_value_heads': 4,
      'rms_norm_eps': 1e-06, 'rope_theta': 1500000,
      'sliding_window_size': 4096, 'vocab_size': 151936,
      'moe_primary_router_apply_softmax': True, 'norm_topk_prob': True,
      'tie_word_embeddings': False, 'rope_scaling': None,
      'rope_layout': [0, 1, 1, 1] * 13,
      'sliding_window_layout': [0, 1, 1, 1] * 13,
  }
  held = {'num_hidden_layers': 4, 'moe_num_primary_experts': 16,
          'vocab_size': 37984}
  assert config['reduced'] == list(held)
  for key, value in published.items():
    assert config[key] == held.get(key, value), key
    if key in held:
      assert config['published'][key] == value
  assert config['deployment']['chips_sharing_each_layer'] == 4
  kwargs = config['model']['kwargs']
  for key in ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
              'head_dim', 'moe_ffn_hidden_size',
              'moe_num_active_primary_experts', 'num_hidden_layers',
              'sliding_window_size', 'rope_theta', 'rms_norm_eps',
              'rope_layout', 'sliding_window_layout'):
    assert kwargs[key] == config[key], key
  assert kwargs['moe_num_primary_experts'] == 64        # the router's width
  assert kwargs['experts_held'] == [0, config['moe_num_primary_experts']]
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['sequence_length'] == 8192
  assert config['train']['batch_per_chip'] == 2
  for key in ('router', 'bias', 'window', 'rotate', 'initialisation',
              'experts_held', 'vocab', 'document mask', 'secondary'):
    assert any(key in name or key in text
               for name, text in config['assumed'].items()), key
  for tolerance in ('step_rel_tolerance', 'reference_rel_tolerance',
                    'grad_norm_rel_tolerance',
                    'group_grad_norm_rel_tolerance'):
    assert 0 < config['train'][tolerance] <= 0.05
    assert 'train.' + tolerance in config['assumed']
  # 1 is what a state left unchanged reads.
  assert 0 < config['train']['parameter_change_tolerance'] < 1
  assert 'train.parameter_change_tolerance' in config['assumed']


@pytest.mark.parametrize('path', [REAL_CONFIG, TINY_CONFIG],
                         ids=['real', 'tiny'])
def test_the_reference_is_named_by_the_file_and_set_as_the_model_is(path):
  """The driver names no model: the file gives the reference's loss, the
  cost function and the settings, which say what the model's keywords say."""
  from benchmark.harness import train_tokens

  config = cells.load_json(path)
  plain, kwargs = config['reference'], config['model']['kwargs']
  assert callable(train_tokens._named(plain['loss']))
  assert train_tokens._named(plain['cost']) is token_costs.step_cost
  layers = config['num_hidden_layers']
  same = {
      'hidden_size': 'hidden_size', 'num_heads': 'num_attention_heads',
      'num_kv_heads': 'num_key_value_heads', 'head_dim': 'head_dim',
      'expert_dim': 'moe_ffn_hidden_size',
      'num_experts': 'moe_num_primary_experts',
      'experts_held': 'experts_held',
      'top_k': 'moe_num_active_primary_experts',
      'window': 'sliding_window_size', 'rope_theta': 'rope_theta',
      'eps': 'rms_norm_eps', 'vocab_rows': 'vocab_rows',
  }
  for setting, keyword in same.items():
    assert plain['settings'][setting] == kwargs[keyword], setting
  for setting, keyword in (('window_layers', 'sliding_window_layout'),
                           ('rope_layers', 'rope_layout')):
    assert plain['settings'][setting] == [
        bool(v) for v in kwargs[keyword][:layers]], setting
  assert set(plain['settings']) == set(same) | {
      'window_layers', 'rope_layers', 'query_block', 'head_block'}
  assert kwargs['vocab_rows'] == config['vocab_size']
  assert kwargs['num_hidden_layers'] == layers


def test_the_driver_of_token_traffic_names_no_model():
  with open(os.path.join(helpers.ROOT, 'benchmark', 'harness',
                         'train_tokens.py')) as f:
    source = f.read().lower()
  assert 'smallthinker' not in source
  assert not os.path.exists(os.path.join(
      helpers.ROOT, 'tensor2robot_tpu', 'research', 'smallthinker',
      'reference.py'))
