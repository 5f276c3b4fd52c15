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

from tensor2robot_tpu.parallel import short_conv


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
