"""Operations and bytes one train step of the LFM2-style hybrid token model
NEEDS, from its shapes and from the pairs its expert layers computed.

``costs.py`` counts a jaxpr and sees the body of a ``pallas_call`` once
whatever its grid, so the step is counted by formula, as ``token_costs.py``
counts the routed attention model's. A layer is one of four kinds (mixer
conv | full_attention, feed-forward dense | experts):

  dense products   2 x rows x in x out: a conv layer's in_proj (d -> 3 d) and
                   out_proj, an attention layer's q, k, v, out, a dense
                   layer's three SwiGLU matrices, an expert layer's router,
                   and the tied head over positions 0..L-2
  short conv       the core between the two projections, ELEMENTWISE and
                   memory-bound: z = B * X (1), three taps (3 multiplies, 2
                   adds), the C gate (1): 7 operations a token a channel
                   forward. Bytes are what decides: the forward kernel must
                   read the [rows, 3 d] projection and write [rows, d]
                   (4 elements a token a channel), the backward kernel must
                   read the projection and dy and write dB, dC, dX (7), the
                   filter and its gradient once
  attention        the causal band only: 4 x head_dim x query heads x
                   L (L + 1) / 2 pairs a sequence a layer
  experts          3 products x 2 x hidden x expert width x PAIRS HELD, the
                   pairs as the program's counter reports them for the step
  backward pass    2 x forward; rematerialisation is not counted

``settings`` is the dict the reference takes. Bytes are each kernel's least
traffic: every operand read once and every result written once at the
compute dtype's width. The attention's and the experts' counts are
``token_costs``'s own, over the layers of each kind.
"""

from benchmark.harness import token_costs

CONV_CORE_OPS = 7       # a token a channel, forward
TAPS = 3


def _kinds(settings):
  """[(mixer, dense feed-forward?)] a layer held."""
  return [(kind, index < settings['num_dense_layers'])
          for index, kind in enumerate(settings['layer_types'])]


def expert_layers(settings):
  return sum(1 for _, dense in _kinds(settings) if not dense)


def conv_layers(settings):
  return sum(1 for kind, _ in _kinds(settings) if kind == 'conv')


def attention_layers(settings):
  return sum(1 for kind, _ in _kinds(settings) if kind == 'full_attention')


def _products(settings, rows):
  """(m, k, n) of every dense product of the blocks, forward."""
  s = settings
  d = s['hidden_size']
  q_width = s['num_heads'] * s['head_dim']
  kv_width = s['num_kv_heads'] * s['head_dim']
  out = []
  for kind, dense in _kinds(s):
    if kind == 'conv':
      out += [(rows, d, 3 * d), (rows, d, d)]
    else:
      out += [(rows, d, q_width), (rows, d, kv_width), (rows, d, kv_width),
              (rows, q_width, d)]
    if dense:
      out += [(rows, d, s['dense_dim'])] * 2 + [(rows, s['dense_dim'], d)]
  return out


def dense_forward_flops(settings, batch, length):
  s = settings
  rows = batch * length
  blocks = sum(2.0 * m * k * n for m, k, n in _products(s, rows))
  routers = expert_layers(s) * 2.0 * rows * s['hidden_size'] * \
      s['num_experts']
  head = 2.0 * batch * (length - 1) * s['hidden_size'] * s['vocab_rows']
  return blocks + routers + head


def dense_forward_bytes(settings, batch, length, itemsize=2):
  """Least traffic of the dense products: operands and results once (the
  router's in float32)."""
  s = settings
  rows = batch * length
  d = s['hidden_size']
  blocks = sum(m * k + k * n + m * n
               for m, k, n in _products(s, rows)) * itemsize
  routers = expert_layers(s) * 4 * (
      rows * d + d * s['num_experts'] + rows * s['num_experts'])
  head_rows = batch * (length - 1)
  head = (head_rows * d + d * s['vocab_rows']) * itemsize + (
      head_rows * s['vocab_rows'] * 4)
  return blocks + routers + head


def short_conv_forward_flops(settings, batch, length):
  return (float(CONV_CORE_OPS) * batch * length * settings['hidden_size'] *
          conv_layers(settings))


def short_conv_call_bytes(settings, batch, length, backward, itemsize=2):
  """Least traffic of ONE call of the forward (or backward) kernel."""
  d = settings['hidden_size']
  return ((7 if backward else 4) * batch * length * d * itemsize +
          TAPS * d * 4)


def short_conv_step_bytes(settings, batch, length, itemsize=2):
  """One forward and one backward call a convolution layer."""
  return conv_layers(settings) * sum(
      short_conv_call_bytes(settings, batch, length, backward, itemsize)
      for backward in (False, True))


def _attention_alone(settings):
  """The settings of a stack of this model's attention layers alone (all
  full: no window), for ``token_costs``'s counts over ``window_layers``."""
  return dict(settings,
              window_layers=(False,) * attention_layers(settings))


def attention_forward_flops(settings, batch, length):
  """The causal band, every attention layer."""
  return token_costs.attention_forward_flops(_attention_alone(settings),
                                             batch, length)


def attention_step_bytes(settings, batch, length, itemsize=2):
  """The three flash kernels' least traffic, every attention layer."""
  return token_costs.attention_step_bytes(_attention_alone(settings), batch,
                                          length, itemsize)


expert_forward_flops = token_costs.expert_forward_flops


def expert_step_bytes(settings, pairs_held, itemsize=2):
  """As ``token_costs``'s, the weights over the layers that HOLD experts."""
  return token_costs.expert_step_bytes(
      dict(settings, window_layers=(False,) * expert_layers(settings)),
      pairs_held, itemsize)


def step_cost(settings, batch, length, pairs_held):
  """The ``cost`` the metric readers see: ``token_costs.step_cost``'s keys
  (``dot`` holds only what XLA's output fusions do), ``short_conv`` for the
  convolution kernels, and ``layers`` with the counts the readers of this
  kind of cell divide by."""
  dense = 3 * dense_forward_flops(settings, batch, length)
  conv = 3 * short_conv_forward_flops(settings, batch, length)
  attention = 3 * attention_forward_flops(settings, batch, length)
  experts = 3 * expert_forward_flops(settings, pairs_held)
  products = len(_products(settings, 1)) + expert_layers(settings) + 1
  return {
      'flops': dense + conv + attention + experts,
      'conv': {'flops': 0.0, 'bytes': 0.0, 'calls': 0},
      'dot': {'flops': dense,
              'bytes': 3.0 * dense_forward_bytes(settings, batch, length),
              'calls': 3 * products},
      'short_conv': {'flops': conv,
                     'bytes': float(short_conv_step_bytes(settings, batch,
                                                          length))},
      'attention': {'flops': attention,
                    'bytes': float(attention_step_bytes(settings, batch,
                                                        length))},
      'experts': {'flops': experts,
                  'bytes': float(expert_step_bytes(settings, pairs_held))},
      'layers': {'held': len(settings['layer_types']),
                 'conv': conv_layers(settings),
                 'attention': attention_layers(settings),
                 'experts': expert_layers(settings)},
  }
