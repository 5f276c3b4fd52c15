"""Layers "kernels", "train step" and the expert layers' counters, for cells
of kind ``train_tokens``.

``attention_roofline`` (%): the least time the chip could take for one
step's attention over the causal BAND (the larger of needed FLOPs over the
peak and least bytes over the bandwidth; ``harness/token_costs.py``) over the
trace time a step of the three flash kernels, found by kernel name (a Pallas
kernel's instruction is named for the kernel, so ``trace.op_family`` keeps
each kernel a family of its own).
``moe_grouped_matmul_roofline`` (%): the same for the experts' grouped
products: 3 x 2 x hidden x expert width x PAIRS HELD, forward and twice
backward, over the trace time of the three grouped-product kernels.
``new_kernels_step_share``: both families' trace time a step over the step's
device time (``step_device_ms``'s reading).
``moe_pairs_held_per_token``: pairs this chip's experts computed, a token a
layer, mean over the window's steps (the program's ``moe/pairs_held``).
``moe_expert_load_max_over_mean``: the largest held expert's pairs over the
mean, mean over layers and steps. ``moe_dropped_pairs``: pairs of held
experts not computed, summed over the window; must be 0.

A program whose trace holds none of the named kernels, or whose driver hands
over no ``moe`` observations, reads ``None`` and the metric is left out.
"""

from benchmark.harness import costs, trace

_ATTENTION = ('flash_attention_fwd', 'flash_attention_bwd_dkv',
              'flash_attention_bwd_dq')
_EXPERTS = ('moe_grouped_matmul', 'moe_grouped_matmul_nt',
            'moe_grouped_matmul_dw')


def _steps_traced(obs):
  reduced = obs.get('trace')
  if not reduced:
    return None, None
  _, runs = trace.main_module(reduced)
  if not runs:
    return None, None
  return len(runs) / reduced['chips'], runs


def _family_seconds_per_step(obs, names):
  steps, _ = _steps_traced(obs)
  families = (obs.get('trace') or {}).get('families')
  if not steps or not families:
    return None
  seconds = sum(families.get(name, 0.0) for name in names)
  return seconds / steps if seconds else None


def _roofline(obs, family, names):
  seconds = _family_seconds_per_step(obs, names)
  cost = (obs.get('cost') or {}).get(family)
  if not (seconds and cost and obs.get('peaks')):
    return None
  share, _ = costs.roofline(cost['flops'] / obs['chips'],
                            cost['bytes'] / obs['chips'], seconds,
                            obs['peaks'])
  return share


def new_kernels_step_share(obs):
  both = [_family_seconds_per_step(obs, names)
          for names in (_ATTENTION, _EXPERTS)]
  _, runs = _steps_traced(obs)
  if None in both or not runs:
    return None
  return sum(both) / (sum(runs) / len(runs))


def _moe(obs, read):
  moe = obs.get('moe')
  return read(moe) if moe else None


METRICS = {
    'attention_roofline':
        lambda obs: _roofline(obs, 'attention', _ATTENTION),
    'moe_grouped_matmul_roofline':
        lambda obs: _roofline(obs, 'experts', _EXPERTS),
    'new_kernels_step_share': new_kernels_step_share,
    'moe_pairs_held_per_token':
        lambda obs: _moe(obs, lambda m: m['pairs_held_per_step'] /
                         m['tokens_per_step']),
    'moe_expert_load_max_over_mean':
        lambda obs: _moe(obs, lambda m: m['load_max_over_mean']),
    'moe_dropped_pairs': lambda obs: _moe(obs, lambda m: m['dropped_pairs']),
}
