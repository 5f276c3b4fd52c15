"""The reduction from a profiler trace to metrics, on a small recorded trace
(``data/recorded_trace.json.gz``: device and host events as ``load_xplane``
gives them, cut from a chip run of PR 24) and on hand-made intervals."""

import gzip
import json
import os

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark.harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data')


class TestNames:

  @pytest.mark.parametrize('instruction,family', [
      ('%convert_reduce_fusion.1.remat = (f32[]) fusion(%a), kind=kOutput, '
       'calls=%fused', 'convert_reduce_fusion kOutput'),
      ('%fusion.12 = bf16[8] fusion(%b), kind=kLoop, calls=%f', 'fusion kLoop'),
      ('%all-reduce.5 = f32[4] all-reduce(%x), replica_groups={}',
       'all-reduce'),
      ('%copy-start.1 = (bf16[64,8]{0,1}) copy-start(%w)', 'copy-start'),
      ('%select-and-scatter.3 = f32[2] select-and-scatter(%a, %b)',
       'select-and-scatter'),
      ('select-and-scatter', 'select-and-scatter'),
  ])
  def test_an_instruction_folds_to_its_family(self, instruction, family):
    assert trace.op_family(instruction) == family

  @pytest.mark.parametrize('instruction,collective,conv', [
      ('%fusion.3 = bf16[8] fusion(%a), kind=kOutput, calls=%f', False, True),
      ('%convolution.3 = bf16[1] convolution(%a, %b), window={}', False,
       True),
      ('%fusion.3 = bf16[8] fusion(%a), kind=kLoop, calls=%f', False, False),
      ('%all-reduce-start.1 = f32[] all-reduce-start(%y)', True, False),
      ('%all-to-all.7 = f32[] all-to-all(%y)', True, False),
      ('%copy.2 = bf16[4] copy(%x)', False, False),
  ])
  def test_collectives_and_convolutions_are_told_apart(self, instruction,
                                                       collective, conv):
    assert trace.is_collective(instruction) is collective
    assert trace.is_conv(instruction) is conv


def _device(ops, async_ops=(), modules=()):
  return {'name': '/device:TPU:0', 'ops': list(ops), 'async': list(async_ops),
          'modules': list(modules)}


class TestHandMadeIntervals:

  def test_busy_is_the_union_and_idle_its_complement(self):
    reduced = trace.reduce_trace({'devices': [_device([
        ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 0, 400],
        ['%fusion.2 = f32[] fusion(%a), kind=kLoop', 300, 300],  # overlaps
        ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 800, 200],
    ])], 'host': []})
    assert reduced['window_s'] == pytest.approx(1000e-9)
    assert reduced['busy_s'] == pytest.approx(800e-9)
    assert reduced['idle_share'] == pytest.approx(0.2)
    assert reduced['families'] == {'fusion kLoop': pytest.approx(900e-9)}

  def test_exposed_collective_time_is_what_no_compute_covers(self):
    reduced = trace.reduce_trace({'devices': [_device(
        ops=[
            ['%all-reduce-start.1 = f32[] all-reduce-start(%g)', 0, 10],
            ['%fusion.1 = f32[] fusion(%a), kind=kOutput', 10, 490],
            ['%all-reduce-done.1 = f32[] all-reduce-done(%s)', 500, 300],
            ['%all-reduce.2 = f32[] all-reduce(%h)', 800, 200],
        ],
        async_ops=[['%all-reduce-start.1 = f32[] all-reduce-start(%g)', 0,
                    800]])], 'host': []})
    # Open 0..1000; compute covers 10..500.
    assert reduced['collective_s'] == pytest.approx(1000e-9)
    assert reduced['collective_exposed_s'] == pytest.approx(510e-9)
    # A -done half is not a second collective.
    assert reduced['collective_ops'] == 2
    assert reduced['conv_s'] == pytest.approx(490e-9)

  def test_gaps_are_named_for_the_innermost_host_annotation(self):
    ops = [['%fusion.1 = f32[] fusion(%a), kind=kLoop', 0, 1000],
           ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 11000, 1000],
           ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 12500, 1000],
           ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 63500, 1000],
           ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 94500, 1000]]
    host = [['data.put_batch+dispatch', 500, 11000],
            ['data.next', 2000, 5000], ['train.step', 14000, 40000]]
    reduced = trace.reduce_trace({'devices': [_device(ops)], 'host': host})
    gaps = dict(reduced['breakdown']['idle_gaps'])
    assert gaps['data.next'] == pytest.approx(10000e-9)
    assert gaps['train.step'] == pytest.approx(50000e-9)
    assert gaps['no_host_event'] == pytest.approx(30000e-9)
    assert gaps['gaps_shorter_than_2_us'] == pytest.approx(500e-9)

  def test_chips_are_averaged_and_modules_pooled(self):
    def device(name, busy):
      d = _device([['%fusion.1 = f32[] fusion(%a), kind=kLoop', 0, busy],
                   ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 900, 100]],
                  modules=[['jit_step(123)', 0, 1000]])
      d['name'] = name
      return d

    reduced = trace.reduce_trace({'devices': [device('/device:TPU:0', 700),
                                              device('/device:TPU:1', 500)],
                                  'host': []})
    assert reduced['chips'] == 2
    assert reduced['busy_s'] == pytest.approx(700e-9)
    assert trace.main_module(reduced) == ('jit_step',
                                          [pytest.approx(1e-6)] * 2)

  def test_host_spans_are_tied_to_the_trace_by_the_marker(self):
    """The host saw the marker end at 12.000 s of its clock; the trace says
    it ended at 5,000 ns. A host span from 12.001 to 12.003 s is then at
    1,005,000 ns for 2,000,000 ns, and the marker is cut out."""
    loaded = {'devices': [_device(
        ops=[['%add.1 = f32[] add(%x, %y)', 4000, 1000],
             ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 2000000, 1000],
             ['%fusion.1 = f32[] fusion(%a), kind=kLoop', 4000000, 1000]],
        modules=[['jit_bench_marker(77)', 3900, 1100],
                 ['jit_step(5)', 2000000, 1000]])], 'host': []}
    aligned = trace.align_host_spans(loaded, 'bench_marker', 12.0,
                                     [('data.next', 12.001, 12.003)])
    assert [e[1] for e in aligned['devices'][0]['ops']] == [2000000, 4000000]
    assert [e[0] for e in aligned['devices'][0]['modules']] == ['jit_step(5)']
    (name, start, duration), = aligned['host']
    assert name == 'data.next'
    assert start == pytest.approx(1005000.0, abs=1.0)
    assert duration == pytest.approx(2000000.0, abs=1.0)
    gaps = dict(trace.reduce_trace(aligned)['breakdown']['idle_gaps'])
    assert gaps == {'data.next': pytest.approx(1999000e-9)}

  def test_without_a_marker_the_trace_is_left_as_it_is(self):
    loaded = {'devices': [_device(
        [['%fusion.1 = f32[] fusion(%a), kind=kLoop', 0, 10]])], 'host': []}
    assert trace.align_host_spans(loaded, 'bench_marker', 1.0, []) == loaded

  def test_a_trace_with_no_device_plane_reduces_to_nothing(self):
    assert trace.reduce_trace({'devices': [], 'host': []}) is None
    assert trace.main_module(None) == (None, [])


@pytest.fixture(scope='module')
def recorded():
  with gzip.open(os.path.join(DATA, 'recorded_trace.json.gz'), 'rt',
                 encoding='utf-8') as f:
    return json.load(f)


class TestRecordedTrace:

  def test_it_is_a_tpu_trace_with_whole_instructions_for_names(self, recorded):
    assert recorded['devices'][0]['name'].startswith('/device:TPU:')
    assert any(' = ' in name and 'fusion(' in name
               for name, _, _ in recorded['devices'][0]['ops'])

  def test_the_reduction_of_the_recorded_trace(self, recorded):
    reduced = trace.reduce_trace(recorded)
    expected = recorded['expected']
    for key in ('window_s', 'busy_s', 'idle_share', 'conv_s'):
      assert reduced[key] == pytest.approx(expected[key], rel=1e-9), key
    assert 0.0 < reduced['idle_share'] < 1.0
    assert reduced['busy_s'] <= reduced['window_s']
    name, runs = trace.main_module(reduced)
    assert name == expected['main_module'] and len(runs) == expected['runs']
    top = reduced['breakdown']['device_ops']
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert top[0][0] == expected['top_family']
    assert sum(reduced['families'].values()) >= reduced['busy_s'] * 0.999
    gaps = reduced['breakdown']['idle_gaps']
    assert {label for label, _ in gaps} <= {
        'data.next', 'data.put_batch+dispatch', 'no_host_event',
        'gaps_shorter_than_2_us'}
    # The cell's finding, in the recorded trace itself: the device idles
    # while the training thread waits in data.next.
    assert gaps[0][0] == expected['top_gap'] == 'data.next'
    assert gaps[0][1] > 0.9 * (reduced['window_s'] - reduced['busy_s'])
