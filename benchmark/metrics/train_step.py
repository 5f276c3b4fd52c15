"""Layer "train step" and "kernels".

``step_device_ms``: median device time of one execution of the program that
took most of the traced window (the train step), from the trace's
``XLA Modules`` line. ``mfu``: the FLOPs one step needs (benchmark's count,
``harness/costs.py``) times the window's step rate, over chips x peak.
``conv_roofline``: the least time the convolutions and matrix products of one
step could take on one chip (the larger of FLOPs/peak and bytes/bandwidth,
per chip) over the trace time of the convolution families per step.
"""

import statistics

from benchmark.harness import costs, trace


def _step_runs(obs):
  _, runs = trace.main_module(obs.get('trace'))
  return runs


def step_device_ms(obs):
  runs = _step_runs(obs)
  return statistics.median(runs) * 1e3 if runs else None


def mfu(obs):
  if not (obs.get('cost') and obs.get('peaks') and obs.get('steps')):
    return None
  flops_per_s = obs['cost']['flops'] * obs['steps'] / obs['window_s']
  return flops_per_s / (obs['chips'] * obs['peaks']['bf16_flops_per_s'])


def conv_roofline(obs):
  reduced, runs = obs.get('trace'), _step_runs(obs)
  if not (reduced and runs and obs.get('cost') and obs.get('peaks')):
    return None
  # Executions per chip in the traced window; the cost is of the global
  # batch, so one chip does 1/chips of it.
  steps_traced = len(runs) / reduced['chips']
  if not reduced['conv_s'] or not steps_traced:
    return None
  cost = obs['cost']
  share, _ = costs.roofline(
      (cost['conv']['flops'] + cost['dot']['flops']) / obs['chips'],
      (cost['conv']['bytes'] + cost['dot']['bytes']) / obs['chips'],
      reduced['conv_s'] / steps_traced, obs['peaks'])
  return share


METRICS = {
    'step_device_ms': step_device_ms,
    'mfu': mfu,
    'conv_roofline': conv_roofline,
}
