"""Kernels of the main path compiled at their real widths for the real chip,
WITHOUT the chip: the TPU's compiler is installed here and compiles for a
v5e that is described, not attached. What the interpreter cannot show (a
slice off the tiling, too much VMEM) is refused here at no chip time.

The topology is described inside a fixture (never at import: one process
at a time may load the TPU's library, and every xdist worker imports every
test file), and these tests stay in ONE file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.parallel import short_conv

flash_lib = transformer_lib.flash_lib  # the module; the package exports the function


@pytest.fixture(scope='module')
def one_chip():
  import os

  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  from jax.experimental import topologies
  try:
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
  except Exception as e:  # pylint: disable=broad-except
    pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
  return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, *shapes):
  """Compiled for the described chip, the persistent cache off (an entry
  written without a chip cannot be read back and only warns)."""
  from jax.experimental.compilation_cache import compilation_cache

  was = jax.config.jax_enable_compilation_cache
  jax.config.update('jax_enable_compilation_cache', False)
  compilation_cache.reset_cache()
  try:
    return jax.jit(fn).lower(*shapes).compile()
  finally:
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize('batch, length, d', [(3, 8192, 2048), (1, 48, 128)],
                         ids=['the_cells_shape', 'tiles_of_16_rows'])
def test_the_short_convolution_pair_compiles_for_the_v5e(one_chip, batch,
                                                         length, d):
  shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
      dims, dtype, sharding=one_chip)
  bcx, dy = shape(batch, length, 3 * d), shape(batch, length, d)
  taps = shape(d, 3, dtype=jnp.float32)
  forward = _compiled(
      lambda b, w: short_conv.short_conv_fwd(b, w, interpret=False), bcx,
      taps)
  backward = _compiled(
      lambda b, w, g: short_conv.short_conv_bwd(b, w, g, interpret=False),
      bcx, taps, dy)
  for program, name in ((forward, 'short_conv_fwd'),
                        (backward, 'short_conv_bwd')):
    text = program.as_text()
    assert 'tpu_custom_call' in text and name in text
    # One pass: nothing but the kernel's own operands and results.
    assert program.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize('heads, kv_heads, length, mask, resident_mb', [
    (32, 4, 16384, dict(causal=False, diffusion=(8192, 4)), 32),
    (28, 4, 8192, dict(causal=True, window=4096), 16),
    (8, 8, 32768, dict(causal=True), 64),
], ids=['third_cell', 'second_cell_window_layer', 'the_longest_fused'])
def test_the_fused_attention_backward_compiles_for_the_v5e(
    one_chip, heads, kv_heads, length, mask, resident_mb):
  """ONE Mosaic kernel with dk and dv of a whole k/v head resident in VMEM:
  the scoped limit it asks for has to hold at the cells' shapes and at the
  longest length the budget keeps fused, which the interpreter cannot
  show."""
  assert flash_lib._fused_bwd_resident_bytes(
      length, 128, jnp.bfloat16) == resident_mb << 20 <= \
      flash_lib.FUSED_BWD_RESIDENT_BYTES
  shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
      dims, dtype, sharding=one_chip)
  q, kv = shape(heads, length, 128), shape(kv_heads, length, 128)
  block_q, block_k = flash_lib._bwd_default_blocks(length, length)
  program = _compiled(
      lambda q, k, v, out, lse, d_out: flash_lib._flash_bwd_pallas(
          q, k, v, out, lse, d_out, scale=0.088, block_q=block_q,
          block_k=block_k, interpret=False, **mask),
      q, kv, kv, q, shape(heads, length, dtype=jnp.float32), q)
  text = program.as_text()
  assert text.count('tpu_custom_call') == 1
  assert 'flash_attention_bwd_dq' in text
  assert 'flash_attention_bwd_dkv' not in text


def test_the_latent_attention_widths_compile_for_the_v5e(one_chip):
  """q and k of 192 against v of 128 at the Xing4.0 cell's shape (32 heads,
  4,096 tokens), forward and the fused backward: 192 runs whole, one block
  of the array's full width, and nothing is padded."""
  shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                             sharding=one_chip)
  attend = lambda q, k, v: flash_lib.flash_attention(
      q, k, v, causal=True, scale=0.14468, interpret=False)
  program = _compiled(lambda q, k, v, g: jax.vjp(attend, q, k, v)[1](g),
                      shape(1, 4096, 32, 192), shape(1, 4096, 32, 192),
                      shape(1, 4096, 32, 128), shape(1, 4096, 32, 128))
  text = program.as_text()
  assert 'flash_attention_fwd' in text and 'flash_attention_bwd_dq' in text
  assert 'flash_attention_bwd_dkv' not in text
  assert ' pad(' not in text


def test_keys_and_values_of_256_compile_for_the_v5e(one_chip):
  """q, k and v all 256 wide at the GLM-4.7-Flash cell's shape (20 heads,
  8,192 tokens), forward and the fused backward at the blocks every width
  gets: 256 runs whole and fits the scoped VMEM the kernels ask for."""
  shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                             sharding=one_chip)
  attend = lambda q, k, v: flash_lib.flash_attention(
      q, k, v, causal=True, scale=0.0625, interpret=False)
  operand = shape(1, 8192, 20, 256)
  program = _compiled(lambda q, k, v, g: jax.vjp(attend, q, k, v)[1](g),
                      operand, operand, operand, operand)
  text = program.as_text()
  assert 'flash_attention_fwd' in text and 'flash_attention_bwd_dq' in text
  assert 'flash_attention_bwd_dkv' not in text
  assert ' pad(' not in text


@pytest.mark.parametrize('kernel', ['hc_pre_fwd', 'hc_post_fwd',
                                    'hc_post_bwd', 'hc_pre_bwd'])
def test_the_stream_kernels_compile_for_the_v5e(one_chip, kernel):
  """The four hyper-connection kernels at the Xing4.0 cell's shape (4,096
  tokens, four streams of 3,584): tiles of whole rows at the full width of
  14,336 float32 lanes, the blocks and temporaries under the scoped VMEM
  limit they ask for."""
  from tensor2robot_tpu.parallel import hyper_connections as hc

  rows, c = 4096, 3584
  shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
      dims, dtype, sharding=one_chip)
  x, f = shape(rows, 4 * c), shape(rows, c, dtype=jnp.bfloat16)
  maps, dh = shape(rows, 32), shape(rows, c)
  phi = shape(4 * c, 24, dtype=jnp.bfloat16)
  alpha, bias = shape(3), shape(24)
  kw = dict(n=4, iters=20, eps=1e-6, clamp=30.0, interpret=False)
  calls = {
      'hc_pre_fwd': (lambda *a: hc.hc_pre_fwd(*a, **kw),
                     (x, phi, alpha, bias)),
      'hc_post_fwd': (lambda *a: hc.hc_post_fwd(*a, n=4, interpret=False),
                      (x, f, maps)),
      'hc_post_bwd': (lambda *a: hc.hc_post_bwd(*a, n=4, interpret=False),
                      (x, f, maps, x)),
      'hc_pre_bwd': (lambda *a: hc.hc_pre_bwd(*a, **kw),
                     (x, phi, alpha, bias, dh, x, maps)),
  }
  call, operands = calls[kernel]
  text = _compiled(call, *operands).as_text()
  assert 'tpu_custom_call' in text and kernel in text
