"""Operations and bytes one train step of the Xing4.0-style token model NEEDS,
from its shapes and from the pairs its expert layers computed.

``costs.py`` counts a jaxpr and sees the body of a ``pallas_call`` once
whatever its grid, so the step is counted by formula, as ``token_costs.py``
and ``lfm2_costs.py`` count theirs. Every layer held has latent attention and
two sublayers over the residual streams; the first ``num_dense_layers`` have
a dense SwiGLU, the others routed experts beside a shared one:

  dense products   2 x rows x in x out: latent attention's q_a, q_b, kv_a,
                   kv_b and out, a dense layer's three SwiGLU matrices, an
                   expert layer's router and shared expert's three, and the
                   untied head over positions 0..L-2
  attention        the causal band only: 2 x (key width + value width) x
                   heads a pair (s = q k^T at 192, p v at 128), L (L + 1) / 2
                   pairs a sequence a layer
  experts          3 products x 2 x hidden x expert width x PAIRS HELD, the
                   pairs as the program's counter reports them for the step
  hc               the stream kernels, MEMORY-bound: what counts is the bytes
                   of their operands and results, each read or written once
                   (``hc_call_bytes``): a forward (pre: X in, h and the maps
                   out; post: X, f and the maps in, X' out) and a backward
                   (post: X, f, the maps and dX' in, dX, df and d maps out;
                   pre: X, dh, dX and d maps in, dX out, phi and its
                   gradient once) a sublayer, rematerialised forwards NOT
                   counted; their operations are the projection x' phi (2 x
                   n C x n (n + 2) a token) and the two mixes, x3 for the
                   step
  backward pass    2 x forward; rematerialisation is not counted

``settings`` is the dict the reference takes. Bytes of the dense, attention
and expert families are each kernel's least traffic at the compute dtype's
width (the router's in float32), as ``token_costs`` counts them; the
streams, h and the maps are float32, f and df at the compute dtype.
"""

from benchmark.harness import token_costs

MAP_ROWS = 32           # the maps a token, padded: pre, post, res of n <= 4


def layers(settings):
  return len(settings['window_layers'])


def expert_layers(settings):
  return layers(settings) - settings['num_dense_layers']


def _products(settings, rows):
  """(m, k, n) of every dense product of the blocks, forward (the router,
  in float32, apart)."""
  s = settings
  d, heads = s['hidden_size'], s['num_heads']
  nope, rope, value = (s['qk_nope_head_dim'], s['qk_rope_head_dim'],
                       s['v_head_dim'])
  attention = [(rows, d, s['q_lora_rank']),
               (rows, s['q_lora_rank'], heads * (nope + rope)),
               (rows, d, s['kv_lora_rank'] + rope),
               (rows, s['kv_lora_rank'], heads * (nope + value)),
               (rows, heads * value, d)]
  out = []
  for index in range(layers(s)):
    width = (s['dense_dim'] if index < s['num_dense_layers'] else
             s['shared_expert_dim'])
    out += attention + [(rows, d, width)] * 2 + [(rows, width, d)]
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


def attention_forward_flops(settings, batch, length):
  s = settings
  per_pair = 2.0 * (s['qk_nope_head_dim'] + s['qk_rope_head_dim'] +
                    s['v_head_dim']) * s['num_heads']
  return per_pair * token_costs.band_pairs(length, None) * batch * layers(s)


def attention_step_bytes(settings, batch, length, itemsize=2):
  """Forward (q, k, v in, o out), and the backward as the pair of kernels
  ``token_costs`` counts (q, k, v, do in, dk, dv out; q, k, v, do in, dq
  out), every layer; q and k at the key width, v, o and do at the value
  width."""
  s = settings
  token = batch * length * s['num_heads'] * itemsize
  key = token * (s['qk_nope_head_dim'] + s['qk_rope_head_dim'])
  value = token * s['v_head_dim']
  reads = 2 * key + 2 * value
  return layers(s) * ((reads + value) + (reads + key + value) +
                      (reads + key))


def expert_forward_flops(settings, pairs_held):
  return token_costs.expert_forward_flops(settings, pairs_held)


def expert_step_bytes(settings, pairs_held, itemsize=2):
  """As ``token_costs``'s, the weights over the layers that HOLD experts."""
  return token_costs.expert_step_bytes(
      dict(settings, window_layers=(False,) * expert_layers(settings)),
      pairs_held, itemsize)


def hc_call_bytes(settings, kernel, rows, f_itemsize=2, phi_itemsize=2):
  """Bytes of ONE call of a stream kernel: its operands and results, each
  once, at ``rows`` tokens (``kernel``: pre_fwd, post_fwd, post_bwd,
  pre_bwd)."""
  s = settings
  n, c = s['streams'], s['hidden_size']
  count = n * (n + 2)
  state, h, f = rows * n * c * 4, rows * c * 4, rows * c * f_itemsize
  maps = rows * MAP_ROWS * 4
  phi, scalars = n * c * count * phi_itemsize, (3 + count) * 4
  return {
      'pre_fwd': state + phi + scalars + h + maps,
      'post_fwd': state + f + maps + state,
      'post_bwd': state + f + maps + state + state + f + maps,
      'pre_bwd': (state + phi + scalars + h + state + maps + state +
                  n * c * count * 4 + scalars),
  }[kernel]


def hc_step_bytes(settings, batch, length):
  """A forward and a backward of both kernels, two sublayers a layer."""
  rows = batch * length
  return 2 * layers(settings) * sum(
      hc_call_bytes(settings, kernel, rows)
      for kernel in ('pre_fwd', 'post_fwd', 'post_bwd', 'pre_bwd'))


def hc_forward_flops(settings, batch, length):
  """Two sublayers a layer: the projection x' phi (2 n C x n (n + 2) a
  token), the read h = sum_j pre_j X_j (2 n C) and the write res X + post f
  (2 n^2 C + 2 n C)."""
  s = settings
  n, c = s['streams'], s['hidden_size']
  token = 2.0 * n * c * n * (n + 2) + 2.0 * n * c + 2.0 * n * n * c + \
      2.0 * n * c
  return 2 * layers(s) * batch * length * token


def step_cost(settings, batch, length, pairs_held):
  """The ``cost`` the metric readers see: ``token_costs.step_cost``'s keys
  (``dot`` holds only what XLA's output fusions do), ``hc`` for the stream
  kernels, and ``layers`` with the counts the readers of this kind of cell
  divide by."""
  dense = 3 * dense_forward_flops(settings, batch, length)
  attention = 3 * attention_forward_flops(settings, batch, length)
  experts = 3 * expert_forward_flops(settings, pairs_held)
  hc = 3 * hc_forward_flops(settings, batch, length)
  products = len(_products(settings, 1)) + expert_layers(settings) + 1
  return {
      'flops': dense + attention + experts + hc,
      'conv': {'flops': 0.0, 'bytes': 0.0, 'calls': 0},
      'dot': {'flops': dense,
              'bytes': 3.0 * dense_forward_bytes(settings, batch, length),
              'calls': 3 * products},
      'attention': {'flops': attention,
                    'bytes': float(attention_step_bytes(settings, batch,
                                                        length))},
      'experts': {'flops': experts,
                  'bytes': float(expert_step_bytes(settings, pairs_held))},
      'hc': {'flops': hc, 'bytes': float(hc_step_bytes(settings, batch,
                                                       length))},
      'layers': {'held': layers(settings), 'attention': layers(settings),
                 'experts': expert_layers(settings),
                 'streams': settings['streams']},
  }
