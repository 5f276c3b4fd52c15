"""Operations and bytes one train step of the GLM-4.7-Flash-style token model
NEEDS, its multi-token-prediction module counted as model work, from its
shapes and from the pairs its expert layers computed.

``costs.py`` counts a jaxpr and sees the body of a ``pallas_call`` once
whatever its grid, so the step is counted by formula, as ``xing_costs.py``
counts its own. The trunk's layers and the MTP's block have latent
attention; the first ``num_dense_layers`` trunk layers a dense SwiGLU, the
other trunk layers and the MTP's block routed experts beside a shared one:

  dense products   2 x rows x in x out: latent attention's q_a, q_b, kv_a,
                   kv_b and out in every latent layer (trunk and MTP), a
                   dense layer's three SwiGLU matrices, an expert layer's
                   router and shared expert's three, the MTP's eh_proj (2d
                   -> d) over every position, and the untied head TWICE:
                   over positions 0..L-2 for the next token and 0..L-3 for
                   the MTP's token two ahead
  attention        the causal band only: 2 x (key width + value width) x
                   heads a pair (s = q k^T, p v), L (L + 1) / 2 pairs a
                   sequence a latent layer
  experts          3 products x 2 x hidden x expert width x PAIRS HELD, the
                   pairs as the program's counter reports them for the step
                   (the MTP's layer among them)
  backward pass    2 x forward; rematerialisation is not counted

``settings`` is the dict the reference takes. Bytes are each kernel's least
traffic at the compute dtype's width (the router's in float32), as
``token_costs`` counts them.
"""

from benchmark.harness import token_costs

MTP_LAYERS = 1


def layers(settings):
  """Trunk layers held (the driver's ``num_hidden_layers``)."""
  return len(settings['window_layers'])


def latent_layers(settings):
  return layers(settings) + MTP_LAYERS


def expert_layers(settings):
  return layers(settings) - settings['num_dense_layers'] + MTP_LAYERS


def _products(settings, rows):
  """(m, k, n) of every dense product of the blocks and of eh_proj, forward
  (the routers, in float32, and the head apart)."""
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
  for index in range(latent_layers(s)):
    width = (s['dense_dim'] if index < s['num_dense_layers'] else
             s['shared_expert_dim'])
    out += attention + [(rows, d, width)] * 2 + [(rows, width, d)]
  return out + [(rows, 2 * d, d)] * MTP_LAYERS


def _head_rows(batch, length):
  """Rows the two head passes need: L - 1 and L - 2 a sequence."""
  return batch * (length - 1) + batch * (length - 2)


def dense_forward_flops(settings, batch, length):
  s = settings
  rows = batch * length
  blocks = sum(2.0 * m * k * n for m, k, n in _products(s, rows))
  routers = expert_layers(s) * 2.0 * rows * s['hidden_size'] * \
      s['num_experts']
  head = 2.0 * _head_rows(batch, length) * s['hidden_size'] * s['vocab_rows']
  return blocks + routers + head


def dense_forward_bytes(settings, batch, length, itemsize=2):
  s = settings
  rows = batch * length
  d = s['hidden_size']
  blocks = sum(m * k + k * n + m * n
               for m, k, n in _products(s, rows)) * itemsize
  routers = expert_layers(s) * 4 * (
      rows * d + d * s['num_experts'] + rows * s['num_experts'])
  head = 0
  for head_rows in (batch * (length - 1), batch * (length - 2)):
    head += (head_rows * d + d * s['vocab_rows']) * itemsize + (
        head_rows * s['vocab_rows'] * 4)
  return blocks + routers + head


def attention_forward_flops(settings, batch, length):
  s = settings
  per_pair = 2.0 * (s['qk_nope_head_dim'] + s['qk_rope_head_dim'] +
                    s['v_head_dim']) * s['num_heads']
  return per_pair * token_costs.band_pairs(length, None) * batch * \
      latent_layers(s)


def attention_step_bytes(settings, batch, length, itemsize=2):
  """Forward (q, k, v in, o out), and the backward as the pair of kernels
  ``token_costs`` counts (q, k, v, do in, dk, dv out; q, k, v, do in, dq
  out), every latent layer; q and k at the key width, v, o and do at the
  value width."""
  s = settings
  token = batch * length * s['num_heads'] * itemsize
  key = token * (s['qk_nope_head_dim'] + s['qk_rope_head_dim'])
  value = token * s['v_head_dim']
  reads = 2 * key + 2 * value
  return latent_layers(s) * ((reads + value) + (reads + key + value) +
                             (reads + key))


def expert_forward_flops(settings, pairs_held):
  return token_costs.expert_forward_flops(settings, pairs_held)


def expert_step_bytes(settings, pairs_held, itemsize=2):
  """As ``token_costs``'s, the weights over the layers that HOLD experts."""
  return token_costs.expert_step_bytes(
      dict(settings, window_layers=(False,) * expert_layers(settings)),
      pairs_held, itemsize)


def step_cost(settings, batch, length, pairs_held):
  """The ``cost`` the metric readers see: ``token_costs.step_cost``'s keys
  (``dot`` holds only what XLA's output fusions do) and ``layers`` with the
  counts the readers of this kind of cell divide by, ``mtp`` among them."""
  dense = 3 * dense_forward_flops(settings, batch, length)
  attention = 3 * attention_forward_flops(settings, batch, length)
  experts = 3 * expert_forward_flops(settings, pairs_held)
  products = len(_products(settings, 1)) + expert_layers(settings) + 2
  return {
      'flops': dense + attention + experts,
      'conv': {'flops': 0.0, 'bytes': 0.0, 'calls': 0},
      'dot': {'flops': dense,
              'bytes': 3.0 * dense_forward_bytes(settings, batch, length),
              'calls': 3 * products},
      'attention': {'flops': attention,
                    'bytes': float(attention_step_bytes(settings, batch,
                                                        length))},
      'experts': {'flops': experts,
                  'bytes': float(expert_step_bytes(settings, pairs_held))},
      'layers': {'held': layers(settings), 'attention': latent_layers(settings),
                 'experts': expert_layers(settings), 'mtp': MTP_LAYERS},
  }
