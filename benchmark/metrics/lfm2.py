"""Layers "kernels", "train step" and "expert layers" of a cell whose model is
the hybrid of gated short convolutions, attention and bias-routed experts
(``research/lfm2``), kind ``train_tokens``.

``lfm2_short_conv_roofline`` (%): the least time the chip could take for one
step's convolution cores (memory-bound: the bytes one forward and one
backward call a layer must move, over the bandwidth; ``harness/
lfm2_costs.py``; rematerialised forwards not counted) over the trace time a
step of ``short_conv_fwd`` and ``short_conv_bwd``, found by kernel name.
``lfm2_attention_roofline`` (%): the same for the attention over the causal
band, over the three flash kernels. ``lfm2_expert_matmul_roofline`` (%): the
experts' grouped products (3 x 3 x 2 x hidden x expert width x PAIRS HELD)
over the three grouped kernels.
``lfm2_kernels_step_share``: ALL ten Pallas kernels of the step (three
flash, three grouped, ``moe_take_rows``, ``moe_sum_rows``, the two of the
short convolution) over the step's device time.
``lfm2_pairs_held_per_token``: pairs this chip's experts computed, a token an
EXPERT layer (the driver's ``tokens_per_step`` counts every layer held; the
cost function says how many hold experts), mean over the window; 1.0
expected at 8 of 32 experts and top 4. ``lfm2_expert_load_max_over_mean``,
``lfm2_dropped_pairs``: as the other token cells'.
``lfm2_chosen_load_max_over_mean``: mean over the window's
``train.step_done`` events of ``moe/chosen_load_max_over_mean``: the tokens
that chose the most chosen of ALL the router's experts over the mean, a
layer, which is what the routers' selection bias balances (1 is even; flat
once the bias has balanced the router, where the bias itself keeps growing
with the steps run). ``lfm2_expert_load_max_over_mean`` is over the experts
HELD, which is what the grouped kernels' tiles follow.

A program that has none of this (no such kernel in the trace, no attribute
on the event, no ``short_conv`` in the cost) reads ``None`` and the metric
is left out.
"""

from benchmark.metrics import moe_attention as token_cell

_SHORT_CONV = ('short_conv_fwd', 'short_conv_bwd')
_KERNELS = (token_cell._ATTENTION + token_cell._EXPERTS +
            ('moe_take_rows', 'moe_sum_rows') + _SHORT_CONV)
_CHOSEN_LOAD = 'moe/chosen_load_max_over_mean'


def _layers(obs):
  """What only this kind of cell's cost function hands over."""
  cost = obs.get('cost') or {}
  return cost.get('layers') if 'short_conv' in cost else None


def _roofline(obs, family, names):
  return token_cell._roofline(obs, family, names) if _layers(obs) else None


def kernels_step_share(obs):
  seconds = token_cell._family_seconds_per_step(obs, _KERNELS)
  _, runs = token_cell._steps_traced(obs)
  if not (seconds and runs and _layers(obs)):
    return None
  return seconds / (sum(runs) / len(runs))


def chosen_load_max_over_mean(obs):
  """Read from the ring, in the window ``program_trace`` finds there."""
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
  read = [r.attrs[_CHOSEN_LOAD] for r in records
          if r.name == 'train.step_done' and _CHOSEN_LOAD in r.attrs and
          start_ns <= r.end_ns <= end_ns]
  return sum(read) / len(read) if read else None


def _moe(obs, read):
  moe, layers = obs.get('moe'), _layers(obs)
  return read(moe, layers) if moe and layers else None


METRICS = {
    'lfm2_short_conv_roofline':
        lambda obs: _roofline(obs, 'short_conv', _SHORT_CONV),
    'lfm2_attention_roofline':
        lambda obs: _roofline(obs, 'attention', token_cell._ATTENTION),
    'lfm2_expert_matmul_roofline':
        lambda obs: _roofline(obs, 'experts', token_cell._EXPERTS),
    'lfm2_kernels_step_share': kernels_step_share,
    # ``tokens_per_step`` is batch x L x layers HELD; experts sit in some.
    'lfm2_pairs_held_per_token':
        lambda obs: _moe(obs, lambda m, layers: m['pairs_held_per_step'] /
                         (m['tokens_per_step'] * layers['experts'] /
                          layers['held'])),
    'lfm2_expert_load_max_over_mean':
        lambda obs: _moe(obs, lambda m, _: m['load_max_over_mean']),
    'lfm2_dropped_pairs':
        lambda obs: _moe(obs, lambda m, _: m['dropped_pairs']),
    'lfm2_chosen_load_max_over_mean': chosen_load_max_over_mean,
}
