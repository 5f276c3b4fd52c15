"""Operations and bytes a step NEEDS, counted from its jaxpr.

The count walks the traced program (before XLA), so it holds what the
algorithm asks for: the forward and backward convolutions and matrix
products, once each. What the compiler recomputes (rematerialisation) or
pads is not in it. Conventions, the same as XLA's ``cost_analysis`` on a
plain program (checked in tests against ``hlo_analysis.program_cost``):

  dot_general          2 x output elements x contracted elements
  conv_general_dilated 2 x output elements x kernel spatial elements x
                       input features per group / product(lhs_dilation)

The division by ``lhs_dilation`` is the one departure: the gradient of a
strided convolution with respect to its input is written as a convolution
over an input dilated with zeros, and a product with an inserted zero is
not an operation the algorithm needs.

Bytes are the least traffic of each counted op: every operand read once and
the output written once, at their own dtypes.
"""

import math

import jax
import numpy as np


def _nbytes(aval):
  return int(np.prod(aval.shape, dtype=np.int64)) * np.dtype(aval.dtype).itemsize


def _conv_cost(eqn):
  lhs, rhs = (v.aval for v in eqn.invars[:2])
  out = eqn.outvars[0].aval
  params = eqn.params
  dims = params['dimension_numbers']
  kernel_spatial = math.prod(rhs.shape[d] for d in dims.rhs_spec[2:])
  in_features_per_group = rhs.shape[dims.rhs_spec[1]]
  dilation = math.prod(params.get('lhs_dilation') or (1,))
  batch_groups = params.get('batch_group_count', 1)
  flops = (2.0 * math.prod(out.shape) * kernel_spatial *
           in_features_per_group / dilation / batch_groups)
  return flops, _nbytes(lhs) + _nbytes(rhs) + _nbytes(out)


def _dot_cost(eqn):
  lhs, rhs = (v.aval for v in eqn.invars[:2])
  out = eqn.outvars[0].aval
  (lhs_contract, _), _ = eqn.params['dimension_numbers']
  contracted = math.prod(lhs.shape[d] for d in lhs_contract)
  return (2.0 * math.prod(out.shape) * contracted,
          _nbytes(lhs) + _nbytes(rhs) + _nbytes(out))


_COUNTED = {'conv_general_dilated': ('conv', _conv_cost),
            'dot_general': ('dot', _dot_cost)}


def _sub_jaxprs(eqn):
  for value in eqn.params.values():
    values = value if isinstance(value, (tuple, list)) else (value,)
    for v in values:
      # A ClosedJaxpr holds its Jaxpr under ``.jaxpr``; a Jaxpr has ``eqns``.
      inner = getattr(v, 'jaxpr', v)
      if hasattr(inner, 'eqns'):
        yield inner


def _walk(jaxpr, times, totals):
  for eqn in jaxpr.eqns:
    name = eqn.primitive.name
    if name in _COUNTED:
      family, cost = _COUNTED[name]
      flops, nbytes = cost(eqn)
      totals[family]['flops'] += times * flops
      totals[family]['bytes'] += times * nbytes
      totals[family]['calls'] += times
      continue
    inner_times = times
    if name == 'scan':
      inner_times = times * int(eqn.params['length'])
    elif name == 'cond':
      # Count the dearest branch once.
      best = None
      for branch in eqn.params['branches']:
        trial = {k: dict(flops=0.0, bytes=0.0, calls=0) for k in totals}
        _walk(branch.jaxpr, times, trial)
        if best is None or (sum(t['flops'] for t in trial.values()) >
                            sum(t['flops'] for t in best.values())):
          best = trial
      for family, t in (best or {}).items():
        for key in t:
          totals[family][key] += t[key]
      continue
    for sub in _sub_jaxprs(eqn):
      _walk(sub, inner_times, totals)


def program_cost(fn, *abstract_args):
  """{'conv'|'dot': {'flops', 'bytes', 'calls'}, 'flops': total} of one call
  of ``fn`` on arguments of those shapes. Nothing runs."""
  closed = jax.make_jaxpr(fn)(*abstract_args)
  totals = {family: dict(flops=0.0, bytes=0.0, calls=0)
            for family, _ in _COUNTED.values()}
  _walk(closed.jaxpr, 1, totals)
  totals['flops'] = sum(t['flops'] for t in totals.values())
  return totals


def roofline(flops, nbytes, seconds, peaks):
  """(share of the roofline in %, which bound) of work that took ``seconds``:
  the least time the chip could take is the larger of flops over the peak
  rate and bytes over the peak bandwidth."""
  compute_s = flops / peaks['bf16_flops_per_s']
  memory_s = nbytes / peaks['hbm_bytes_per_s']
  bound = 'compute' if compute_s >= memory_s else 'memory'
  return 100.0 * max(compute_s, memory_s) / seconds, bound
