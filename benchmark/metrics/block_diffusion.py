"""Layers "kernels", "train step" and "expert layers" of a cell whose model is
trained by block diffusion (2L positions [noised ; clean] a sequence under
the block-diffusion mask), kind ``train_tokens``.

``bd_attention_roofline`` (%): the least time the chip could take for one
step's attention over the MASK's pairs (the larger of needed FLOPs over the
peak and least bytes over the bandwidth; ``harness/sdar_costs.py``) over the
trace time a step of the three flash kernels, found by kernel name.
``bd_expert_matmul_roofline`` (%): the same for the experts' grouped products
(3 x 3 x 2 x hidden x expert width x PAIRS HELD) over the three grouped
kernels' trace time.
``bd_kernels_step_share``: ALL Pallas kernels of the step (the three flash,
the three grouped, ``moe_take_rows``, ``moe_sum_rows``) over the step's
device time.
``bd_attention_pairs_computed_over_needed``: pairs of the tiles the flash
kernels compute over the pairs of the mask, from the gauges the program
sets while the attention is traced (``attention/mask_pairs_needed``,
``attention/mask_pairs_computed`` for the forward kernel and
``..._computed_bwd`` for each of the two backward kernels): one forward and
two backward sweeps over three times the mask.
``bd_masked_position_share``: ``diffusion/masked_positions`` of the window's
``train.step_done`` events in the program's span ring over L x sequences a
step x steps the events cover (each event stands for ``steps_covered``
steps; half the positions are masked on average, a little more with eps).
``bd_pairs_held_per_position``: pairs this chip's experts computed, a
position a layer, over the 2L positions a sequence, mean over the window
(``observations['moe']``). ``bd_expert_load_max_over_mean``,
``bd_dropped_pairs``: as the other token cell's.

A program that has none of this (no such kernel in the trace, no gauge, no
attribute on the event, no ``sequence`` in the cost) reads ``None`` and the
metric is left out.
"""

from benchmark.metrics import moe_attention as token_cell

_KERNELS = (token_cell._ATTENTION + token_cell._EXPERTS +
            ('moe_take_rows', 'moe_sum_rows'))
_MASKED = 'diffusion/masked_positions'


def _sequence(obs):
  """What only this kind of cell's cost function hands over."""
  return (obs.get('cost') or {}).get('sequence')


def _roofline(obs, family, names):
  return token_cell._roofline(obs, family, names) if _sequence(obs) else None


def kernels_step_share(obs):
  seconds = token_cell._family_seconds_per_step(obs, _KERNELS)
  _, runs = token_cell._steps_traced(obs)
  if not (seconds and runs and _sequence(obs)):
    return None
  return seconds / (sum(runs) / len(runs))


def _gauge(name):
  """The program's gauge, or None where it has no registry or never set
  it (a gauge nothing set reads 0)."""
  try:
    from tensor2robot_tpu.observability import get_registry
  except ImportError:
    return None
  return float(get_registry().gauge(name).value) or None


def pairs_computed_over_needed(obs):
  if not _sequence(obs):
    return None
  needed = _gauge('attention/mask_pairs_needed')
  forward = _gauge('attention/mask_pairs_computed')
  backward = _gauge('attention/mask_pairs_computed_bwd')
  if not (needed and forward and backward):
    return None
  return (forward + 2 * backward) / (3 * needed)


def masked_position_share(obs):
  """Read from the ring, in the window ``program_trace`` finds there."""
  from benchmark.metrics import program_trace

  sequence, counters = _sequence(obs), obs.get('counters')
  ring = program_trace.read_ring()
  if not (sequence and counters and ring and obs.get('window_s')) or \
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
  masked = [r.attrs[_MASKED] for r in records
            if r.name == 'train.step_done' and _MASKED in r.attrs and
            start_ns <= r.end_ns <= end_ns]
  if not masked:
    return None
  return sum(masked) / (
      len(masked) * sequence['length'] * obs['examples_per_step'])


def _moe(obs, read):
  moe = obs.get('moe')
  return read(moe) if moe and _sequence(obs) else None


METRICS = {
    'bd_attention_roofline':
        lambda obs: _roofline(obs, 'attention', token_cell._ATTENTION),
    'bd_attention_pairs_computed_over_needed': pairs_computed_over_needed,
    'bd_expert_matmul_roofline':
        lambda obs: _roofline(obs, 'experts', token_cell._EXPERTS),
    'bd_kernels_step_share': kernels_step_share,
    'bd_masked_position_share': masked_position_share,
    # ``tokens_per_step`` is batch x L x layers; the stack sees 2L a sequence.
    'bd_pairs_held_per_position':
        lambda obs: _moe(obs, lambda m: m['pairs_held_per_step'] /
                         (2 * m['tokens_per_step'])),
    'bd_expert_load_max_over_mean':
        lambda obs: _moe(obs, lambda m: m['load_max_over_mean']),
    'bd_dropped_pairs': lambda obs: _moe(obs, lambda m: m['dropped_pairs']),
}
