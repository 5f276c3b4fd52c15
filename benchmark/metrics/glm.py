"""Layers "kernels", "train step" and "expert layers" of a cell whose model
trains latent attention at keys and values of 256 with a multi-token-
prediction module beside its trunk (``research/glm``), kind
``train_tokens``.

``glm_attention_roofline`` (%): the least time the chip could take for one
step's attention over the causal band at the key and value widths 256, in
the trunk's latent layers and the MTP's (``harness/glm_costs.py``), over the
trace time a step of the three flash kernels, found by kernel name.
``glm_expert_matmul_roofline`` (%): the experts' grouped products (3 x 3 x 2
x hidden x expert width x PAIRS HELD, the MTP's layer among them) over the
three grouped kernels.
``glm_kernels_step_share``: ALL eight Pallas kernels of the step (three
flash, three grouped, ``moe_take_rows``, ``moe_sum_rows``) over the step's
device time.
``glm_pairs_held_per_token``: pairs this chip's experts computed, a token an
EXPERT layer, the MTP's included (the driver's ``tokens_per_step`` counts
the trunk's layers held; the cost function says how many layers hold
experts), mean over the window; 0.5 expected at 8 of 64 experts and top 4.
``glm_dropped_pairs``: pairs of held experts not computed, summed over the
window; must be 0.
``glm_chosen_load_max_over_mean``: mean over the window's
``train.step_done`` events of ``moe/chosen_load_max_over_mean``: the tokens
that chose the most chosen of ALL the router's experts over the mean, a
layer, the MTP's among the layers, which is what the routers' selection
bias balances (1 is even).

A program that has none of this (no such kernel in the trace, no attribute
on the event, no ``mtp`` in the cost's layers) reads ``None`` and the metric
is left out.
"""

from benchmark.metrics import moe_attention as token_cell

_KERNELS = (token_cell._ATTENTION + token_cell._EXPERTS +
            ('moe_take_rows', 'moe_sum_rows'))
_CHOSEN_LOAD = 'moe/chosen_load_max_over_mean'


def _layers(obs):
  """What only this kind of cell's cost function hands over."""
  layers = (obs.get('cost') or {}).get('layers') or {}
  return layers if 'mtp' in layers else None


def _roofline(obs, family, names):
  return token_cell._roofline(obs, family, names) if _layers(obs) else None


def kernels_step_share(obs):
  seconds = token_cell._family_seconds_per_step(obs, _KERNELS)
  _, runs = token_cell._steps_traced(obs)
  if not (seconds and runs and _layers(obs)):
    return None
  return seconds / (sum(runs) / len(runs))


def _moe(obs, read):
  moe, layers = obs.get('moe'), _layers(obs)
  return read(moe, layers) if moe and layers else None


def _step_done_mean(obs, attribute):
  """Mean of ``attribute`` over the ``train.step_done`` events of the window
  ``program_trace`` finds in the ring."""
  from benchmark.metrics import program_trace

  counters = obs.get('counters')
  if not (_layers(obs) and counters and obs.get('window_s')) or \
      'span/train.step/count' not in counters['after']:
    return None
  ring = program_trace.read_ring()
  if not ring or ring[1]:
    return None
  records = ring[0]
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


METRICS = {
    'glm_attention_roofline':
        lambda obs: _roofline(obs, 'attention', token_cell._ATTENTION),
    'glm_expert_matmul_roofline':
        lambda obs: _roofline(obs, 'experts', token_cell._EXPERTS),
    'glm_kernels_step_share': kernels_step_share,
    # ``tokens_per_step`` is batch x L x trunk layers HELD; experts sit in
    # some of them and in the MTP's block.
    'glm_pairs_held_per_token':
        lambda obs: _moe(obs, lambda m, layers: m['pairs_held_per_step'] /
                         (m['tokens_per_step'] * layers['experts'] /
                          layers['held'])),
    'glm_dropped_pairs':
        lambda obs: _moe(obs, lambda m, _: m['dropped_pairs']),
    'glm_chosen_load_max_over_mean':
        lambda obs: _step_done_mean(obs, _CHOSEN_LOAD),
}
