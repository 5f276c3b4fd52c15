"""Operations and bytes one train step of the block-diffusion token model
NEEDS, from its shapes and from the pairs its expert layers computed.

``costs.py`` counts a jaxpr and sees the body of a ``pallas_call`` once
whatever its grid, so the step is counted by formula, as ``token_costs.py``
counts the next-token model's. A sequence of L tokens goes through the
blocks as 2L positions, [noised ; clean]:

  dense products   2 x rows x in x out: q, k, v, out and the router of every
                   layer over the 2L positions; the head over the L noised
                   positions only
  attention        the block-diffusion mask only: 4 x head_dim x query heads
                   x (L^2 + L x B) pairs a sequence a layer (noised to its
                   own block L x B, noised to earlier clean blocks and clean
                   to clean block-causal together L^2)
  experts          3 products x 2 x hidden x expert width x PAIRS HELD, the
                   pairs as the program's counter reports them for the step
  backward pass    2 x forward; rematerialisation is not counted

``settings`` is the dict the reference takes; ``length`` is L, the clean
sequence's. Bytes are each kernel's least traffic: every operand read once
and every result written once at the compute dtype's width.
"""


def mask_pairs(length, block):
  """Pairs (i, j) of the [2L, 2L] square the block-diffusion mask keeps."""
  blocks = length // block
  own = length * block
  earlier = block * block * blocks * (blocks - 1) // 2
  causal = block * block * blocks * (blocks + 1) // 2
  return own + earlier + causal


def _layers(settings):
  return len(settings['window_layers'])


def dense_forward_flops(settings, batch, length):
  s = settings
  q_width = s['num_heads'] * s['head_dim']
  kv_width = s['num_kv_heads'] * s['head_dim']
  layer = 2.0 * batch * 2 * length * s['hidden_size'] * (
      2 * q_width + 2 * kv_width + s['num_experts'])
  head = 2.0 * batch * length * s['hidden_size'] * s['vocab_rows']
  return _layers(s) * layer + head


def dense_forward_bytes(settings, batch, length, itemsize=2):
  """Least traffic of the dense products: operands and results once."""
  s = settings
  rows = batch * 2 * length
  d = s['hidden_size']
  q_width = s['num_heads'] * s['head_dim']
  kv_width = s['num_kv_heads'] * s['head_dim']
  products = [(rows, d, q_width), (rows, d, kv_width), (rows, d, kv_width),
              (rows, q_width, d)]
  layer = sum(m * k + k * n + m * n for m, k, n in products) * itemsize
  layer += (rows * d + d * s['num_experts'] + rows * s['num_experts']) * 4
  head_rows = batch * length
  head = (head_rows * d + d * s['vocab_rows']) * itemsize + (
      head_rows * s['vocab_rows'] * 4)
  return _layers(s) * layer + head


def attention_forward_flops(settings, batch, length):
  s = settings
  return (4.0 * s['head_dim'] * s['num_heads'] * batch * _layers(s) *
          mask_pairs(length, s['block_length']))


def attention_step_bytes(settings, batch, length, itemsize=2):
  """Forward (q, k, v in, o out), dk/dv kernel (q, k, v, do in, dk, dv out)
  and dq kernel (q, k, v, do in, dq out), every layer, 2L positions."""
  s = settings
  q = batch * 2 * length * s['num_heads'] * s['head_dim'] * itemsize
  kv = batch * 2 * length * s['num_kv_heads'] * s['head_dim'] * itemsize
  forward = 2 * q + 2 * kv
  backward = (2 * q + 2 * kv + 2 * kv) + (2 * q + 2 * kv + q)
  return _layers(s) * (forward + backward)


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
  weights = (_layers(s) * s['experts_held'][1] * 3 * s['hidden_size'] *
             s['expert_dim'] * itemsize)
  return 3 * (rows + weights)


def step_cost(settings, batch, length, pairs_held):
  """The ``cost`` the metric readers see: ``token_costs.step_cost``'s keys
  (``dot`` holds only what XLA's output fusions do), and ``sequence`` with
  the sizes the block-diffusion readers divide by."""
  dense = 3 * dense_forward_flops(settings, batch, length)
  attention = 3 * attention_forward_flops(settings, batch, length)
  experts = 3 * expert_forward_flops(settings, pairs_held)
  return {
      'flops': dense + attention + experts,
      'conv': {'flops': 0.0, 'bytes': 0.0, 'calls': 0},
      'dot': {'flops': dense,
              'bytes': 3.0 * dense_forward_bytes(settings, batch, length),
              'calls': 3 * (5 * _layers(settings) + 1)},
      'attention': {'flops': attention,
                    'bytes': float(attention_step_bytes(settings, batch,
                                                        length))},
      'experts': {'flops': experts,
                  'bytes': float(expert_step_bytes(settings, pairs_held))},
      'sequence': {'length': length, 'positions': 2 * length,
                   'block_length': settings['block_length'],
                   'mask_pairs': mask_pairs(length,
                                            settings['block_length'])},
  }
