"""Layers "kernels", "train step", "expert layers" and "residual streams" of a
cell whose model carries several residual streams a token beside latent
attention and a shared expert (``research/xing``), kind ``train_tokens``.

``xing_hc_roofline`` (%): the least time the chip could take for one step's
stream kernels (memory-bound: the bytes of their operands and results, a
forward and a backward of each a sublayer, over the bandwidth; ``harness/
xing_costs.py``; rematerialised forwards not counted) over the trace time a
step of ``hc_pre_fwd``, ``hc_post_fwd``, ``hc_post_bwd`` and ``hc_pre_bwd``,
found by kernel name.
``xing_attention_roofline`` (%): the same for the attention over the causal
band at the key width 192 and the value width 128, over the three flash
kernels. ``xing_expert_matmul_roofline`` (%): the experts' grouped products
(3 x 3 x 2 x hidden x expert width x PAIRS HELD) over the three grouped
kernels.
``xing_kernels_step_share``: ALL twelve Pallas kernels of the step (three
flash, three grouped, ``moe_take_rows``, ``moe_sum_rows``, the four stream
kernels) over the step's device time.
``xing_pairs_held_per_token``: pairs this chip's experts computed, a token an
EXPERT layer (the driver's ``tokens_per_step`` counts every layer held; the
cost function says how many hold experts), mean over the window; 0.5
expected at 8 of 64 experts and top 4. ``xing_dropped_pairs``: pairs of held
experts not computed, summed over the window; must be 0.
``xing_hc_res_stochastic_error``: mean over the window's ``train.step_done``
events of ``hc/res_stochastic_error``: the largest |row or column sum - 1|
of a stream-mixing matrix over the step's tokens and layers (the distance
of the Sinkhorn-Knopp iterations from a doubly stochastic map).
``xing_expert_load_max_over_mean``: the largest HELD expert's pairs over the
held experts' mean, a layer, mean over the window (what the grouped
kernels' tiles follow). ``xing_chosen_load_max_over_mean``: mean over the
window's ``train.step_done`` events of ``moe/chosen_load_max_over_mean``:
the tokens that chose the most chosen of ALL the router's experts over the
mean, a layer, which is what the routers' selection bias balances (1 is
even).

A program that has none of this (no such kernel in the trace, no attribute
on the event, no ``hc`` in the cost) reads ``None`` and the metric is left
out.
"""

from benchmark.metrics import moe_attention as token_cell

_STREAMS = ('hc_pre_fwd', 'hc_post_fwd', 'hc_post_bwd', 'hc_pre_bwd')
_KERNELS = (token_cell._ATTENTION + token_cell._EXPERTS +
            ('moe_take_rows', 'moe_sum_rows') + _STREAMS)
_ERROR = 'hc/res_stochastic_error'
_CHOSEN_LOAD = 'moe/chosen_load_max_over_mean'


def _layers(obs):
  """What only this kind of cell's cost function hands over."""
  cost = obs.get('cost') or {}
  return cost.get('layers') if 'hc' in cost else None


def _roofline(obs, family, names):
  return token_cell._roofline(obs, family, names) if _layers(obs) else None


def kernels_step_share(obs):
  seconds = token_cell._family_seconds_per_step(obs, _KERNELS)
  _, runs = token_cell._steps_traced(obs)
  if not (seconds and runs and _layers(obs)):
    return None
  return seconds / (sum(runs) / len(runs))


def _step_done_mean(obs, attribute):
  """Mean of ``attribute`` over the ``train.step_done`` events of the window
  ``program_trace`` finds in the ring."""
  from benchmark.metrics import program_trace

  counters = obs.get('counters')
  ring = program_trace.read_ring()
  if not (_layers(obs) and counters and ring and obs.get('window_s')) or \
      'span/train.step/count' not in counters['after']:
    return None
  records, dropped = ring
  if dropped:
    return None
  found, _ = program_trace.find_window(
      records, int(counters['before']['span/train.step/count']),
      int(counters['after']['span/train.step/count']), obs['window_s'])
  if found is None:
    return None
  start_ns, end_ns, _ = found
  read = [r.attrs[attribute] for r in records
          if r.name == 'train.step_done' and attribute in r.attrs and
          start_ns <= r.end_ns <= end_ns]
  return sum(read) / len(read) if read else None


def _moe(obs, read):
  moe, layers = obs.get('moe'), _layers(obs)
  return read(moe, layers) if moe and layers else None


METRICS = {
    'xing_hc_roofline': lambda obs: _roofline(obs, 'hc', _STREAMS),
    'xing_attention_roofline':
        lambda obs: _roofline(obs, 'attention', token_cell._ATTENTION),
    'xing_expert_matmul_roofline':
        lambda obs: _roofline(obs, 'experts', token_cell._EXPERTS),
    'xing_kernels_step_share': kernels_step_share,
    # ``tokens_per_step`` is batch x L x layers HELD; experts sit in some.
    'xing_pairs_held_per_token':
        lambda obs: _moe(obs, lambda m, layers: m['pairs_held_per_step'] /
                         (m['tokens_per_step'] * layers['experts'] /
                          layers['held'])),
    'xing_dropped_pairs':
        lambda obs: _moe(obs, lambda m, _: m['dropped_pairs']),
    'xing_hc_res_stochastic_error': lambda obs: _step_done_mean(obs, _ERROR),
    'xing_expert_load_max_over_mean':
        lambda obs: _moe(obs, lambda m, _: m['load_max_over_mean']),
    'xing_chosen_load_max_over_mean':
        lambda obs: _step_done_mean(obs, _CHOSEN_LOAD),
}
