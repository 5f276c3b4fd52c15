"""Operations and bytes one train step of a routed, banded-attention token
model NEEDS, from its shapes and from the pairs its expert layers computed.

``costs.py`` counts a jaxpr; it sees the body of a ``pallas_call`` once
whatever its grid. So the step of such a model is counted here by formula:

  dense products   2 x rows x in x out: the q, k, v, out projections and the
                   router of every layer, and the head over positions 0..L-2
  attention        the causal band only: 4 x head_dim x query heads x (pairs
                   (i, j) with j <= i and, in a window layer, i - j < window)
  experts          3 products x 2 x hidden x expert width x PAIRS HELD, the
                   pairs as the program's counter reports them for the step
  backward pass    2 x forward; rematerialisation is not counted

``settings`` is the dict the reference takes (hidden_size and vocab_rows
added). Bytes are each kernel's least traffic: every operand read once and
every result written once at the compute dtype's width.
"""


def band_pairs(length, window):
  """Pairs (i, j), j <= i < length, and with a window i - j < window."""
  if window is None or window >= length:
    return length * (length + 1) // 2
  return window * (window + 1) // 2 + (length - window) * window


def dense_forward_flops(settings, batch, length):
  s = settings
  rows = batch * length
  q_width = s['num_heads'] * s['head_dim']
  kv_width = s['num_kv_heads'] * s['head_dim']
  layer = 2.0 * rows * s['hidden_size'] * (
      2 * q_width + 2 * kv_width + s['num_experts'])
  head = 2.0 * batch * (length - 1) * s['hidden_size'] * s['vocab_rows']
  return len(s['window_layers']) * layer + head


def dense_forward_bytes(settings, batch, length, itemsize=2):
  """Least traffic of the dense products: operands and results once."""
  s = settings
  rows = batch * length
  d = s['hidden_size']
  q_width = s['num_heads'] * s['head_dim']
  kv_width = s['num_kv_heads'] * s['head_dim']
  products = [(rows, d, q_width), (rows, d, kv_width), (rows, d, kv_width),
              (rows, q_width, d)]
  layer = sum(m * k + k * n + m * n for m, k, n in products) * itemsize
  layer += (rows * d + d * s['num_experts'] + rows * s['num_experts']) * 4
  head_rows = batch * (length - 1)
  head = (head_rows * d + d * s['vocab_rows']) * itemsize + (
      head_rows * s['vocab_rows'] * 4)
  return len(s['window_layers']) * layer + head


def attention_forward_flops(settings, batch, length):
  s = settings
  pairs = sum(band_pairs(length, s['window'] if windowed else None)
              for windowed in s['window_layers'])
  return 4.0 * s['head_dim'] * s['num_heads'] * pairs * batch


def attention_step_bytes(settings, batch, length, itemsize=2):
  """Forward (q, k, v in, o out), dk/dv kernel (q, k, v, do in, dk, dv out)
  and dq kernel (q, k, v, do in, dq out), every layer."""
  s = settings
  q = batch * length * s['num_heads'] * s['head_dim'] * itemsize
  kv = batch * length * s['num_kv_heads'] * s['head_dim'] * itemsize
  forward = 2 * q + 2 * kv
  backward = (2 * q + 2 * kv + 2 * kv) + (2 * q + 2 * kv + q)
  return len(s['window_layers']) * (forward + backward)


def expert_forward_flops(settings, pairs_held):
  """``pairs_held``: pairs computed in one step, summed over the layers."""
  return 3 * 2.0 * settings['hidden_size'] * settings['expert_dim'] * \
      pairs_held


def expert_step_bytes(settings, pairs_held, itemsize=2):
  """Rows in and out of the three products, forward and backward, and every
  held expert's weights once forward, once for each of d rows and d
  weights."""
  s = settings
  rows = pairs_held * (2 * s['hidden_size'] + 3 * s['expert_dim']) * itemsize
  weights = (len(s['window_layers']) * s['experts_held'][1] * 3 *
             s['hidden_size'] * s['expert_dim'] * itemsize)
  return 3 * (rows + weights)


def step_cost(settings, batch, length, pairs_held):
  """The ``cost`` the metric readers see. ``dot`` holds only the work that
  XLA output fusions do (the dense products), so that a reader that divides
  by the time of those fusions does not count a Pallas kernel's work;
  ``attention`` and ``experts`` are the two kernel families'."""
  dense = 3 * dense_forward_flops(settings, batch, length)
  attention = 3 * attention_forward_flops(settings, batch, length)
  experts = 3 * expert_forward_flops(settings, pairs_held)
  return {
      'flops': dense + attention + experts,
      'conv': {'flops': 0.0, 'bytes': 0.0, 'calls': 0},
      'dot': {'flops': dense,
              'bytes': 3.0 * dense_forward_bytes(settings, batch, length),
              'calls': 3 * (5 * len(settings['window_layers']) + 1)},
      'attention': {'flops': attention,
                    'bytes': float(attention_step_bytes(settings, batch,
                                                        length))},
      'experts': {'flops': experts,
                  'bytes': float(expert_step_bytes(settings, pairs_held))},
  }
