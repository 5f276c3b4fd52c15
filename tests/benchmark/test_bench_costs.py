"""The benchmark's own yardsticks: FLOP and byte functions against the
program's ``hlo_analysis.program_cost`` on a toy program, the peaks table,
the roofline arithmetic, the record writer against the program's parser."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark.harness import costs, peaks, records


def _toy_step(w1, w2, x):
  def loss(w1, w2, x):
    y = jax.lax.conv_general_dilated(
        x, w1, (1, 1), 'VALID', dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
    y = jnp.mean(jax.nn.relu(y), axis=(1, 2))
    return jnp.mean((y @ w2) ** 2)
  return jax.value_and_grad(loss, argnums=(0, 1, 2))(w1, w2, x)


_TOY_ARGS = (jax.ShapeDtypeStruct((3, 3, 16, 64), jnp.float32),
             jax.ShapeDtypeStruct((64, 8), jnp.float32),
             jax.ShapeDtypeStruct((4, 32, 32, 16), jnp.float32))


class TestCosts:

  def test_conv_and_dot_counts_by_hand(self):
    cost = costs.program_cost(_toy_step, *_TOY_ARGS)
    forward_conv = 2 * (4 * 30 * 30 * 64) * (3 * 3 * 16)
    # Forward, the gradient to the weights and the gradient to the input.
    assert cost['conv']['calls'] == 3
    assert cost['conv']['flops'] == pytest.approx(3 * forward_conv, rel=0.15)
    forward_dot = 2 * 4 * 8 * 64
    assert cost['dot']['calls'] == 3
    assert cost['dot']['flops'] == pytest.approx(3 * forward_dot)
    assert cost['flops'] == cost['conv']['flops'] + cost['dot']['flops']

  def test_agrees_with_the_programs_cost_model_on_a_toy_program(self):
    from tensor2robot_tpu.parallel import hlo_analysis

    cost = costs.program_cost(_toy_step, *_TOY_ARGS)
    compiled = jax.jit(_toy_step).lower(*_TOY_ARGS).compile()
    theirs = hlo_analysis.program_cost(compiled)
    # The program's count also holds the elementwise work and XLA's own
    # treatment of the padded input gradient; 10% bounds both here.
    assert cost['flops'] == pytest.approx(theirs['flops'], rel=0.10)

  def test_a_strided_convolutions_input_gradient_counts_no_inserted_zeros(self):
    def forward(w, x):
      return jax.lax.conv_general_dilated(
          x, w, (2, 2), 'VALID', dimension_numbers=('NHWC', 'HWIO', 'NHWC'))

    def step(w, x):
      return jax.grad(lambda w, x: jnp.sum(forward(w, x) ** 2),
                      argnums=(0, 1))(w, x)

    args = (jax.ShapeDtypeStruct((3, 3, 8, 16), jnp.float32),
            jax.ShapeDtypeStruct((2, 33, 33, 8), jnp.float32))
    forward_flops = costs.program_cost(forward, *args)['conv']['flops']
    both = costs.program_cost(step, *args)['conv']
    assert both['calls'] == 3
    assert both['flops'] == pytest.approx(3 * forward_flops, rel=0.2)

  def test_scan_multiplies_by_its_length(self):
    def body(w, x):
      def one(carry, _):
        return carry @ w, None
      return jax.lax.scan(one, x, None, length=5)[0]

    cost = costs.program_cost(body, jax.ShapeDtypeStruct((8, 8), jnp.float32),
                              jax.ShapeDtypeStruct((4, 8), jnp.float32))
    assert cost['dot']['flops'] == 5 * 2 * 4 * 8 * 8

  def test_bytes_are_operands_and_output_at_their_dtypes(self):
    cost = costs.program_cost(
        lambda a, b: a @ b, jax.ShapeDtypeStruct((4, 8), jnp.bfloat16),
        jax.ShapeDtypeStruct((8, 2), jnp.bfloat16))
    assert cost['dot']['bytes'] == 2 * (4 * 8 + 8 * 2 + 4 * 2)

  def test_roofline_names_its_bound_and_does_not_clamp(self):
    row = {'bf16_flops_per_s': 100.0, 'hbm_bytes_per_s': 10.0}
    assert costs.roofline(50.0, 1.0, 1.0, row) == (50.0, 'compute')
    assert costs.roofline(1.0, 5.0, 1.0, row) == (50.0, 'memory')
    share, _ = costs.roofline(300.0, 1.0, 1.0, row)
    assert share == 300.0  # a count that is too high must show, not hide


class TestPeaks:

  def test_the_v5e_row_has_its_source(self):
    row = peaks.peaks_for('TPU v5 lite')
    assert row['bf16_flops_per_s'] == 197e12
    assert row['hbm_bytes_per_s'] == 819e9
    assert 'TPU v5e' in row['source']

  def test_an_unknown_device_kind_is_an_error(self):
    with pytest.raises(LookupError):
      peaks.peaks_for('TPU v9 imaginary')


class TestRecords:

  def test_the_programs_parser_reads_what_the_benchmark_writes(self, tmp_path):
    from tensor2robot_tpu.data import tfrecord

    specs = [('image_1', (64, 80, 3), np.dtype('uint8'), True),
             ('vector', (3,), np.dtype('float32'), False),
             ('count', (2,), np.dtype('int64'), False)]
    path = str(tmp_path / 'r.tfrecord')
    size = records.write_records(path, specs, 5, seed=2**31 + 3, threads=2)
    raw = list(tfrecord.tfrecord_iterator(path, verify_crc=True))
    assert len(raw) == 5 and size > 0
    import tensorflow as tf

    example = tf.train.Example.FromString(raw[0])
    features = example.features.feature
    assert sorted(features) == ['count', 'image_1', 'vector']
    assert len(features['vector'].float_list.value) == 3
    assert len(features['count'].int64_list.value) == 2
    frame = tf.io.decode_jpeg(features['image_1'].bytes_list.value[0])
    assert tuple(frame.shape) == (64, 80, 3)

  def test_the_file_depends_on_the_seed_and_not_on_the_threads(self, tmp_path):
    specs = [('image_1', (32, 40, 3), np.dtype('uint8'), True),
             ('vector', (3,), np.dtype('float32'), False)]
    paths = [str(tmp_path / name) for name in 'abc']
    records.write_records(paths[0], specs, 4, seed=11, threads=1)
    records.write_records(paths[1], specs, 4, seed=11, threads=4)
    records.write_records(paths[2], specs, 4, seed=12, threads=1)
    contents = [open(p, 'rb').read() for p in paths]
    assert contents[0] == contents[1]
    assert contents[0] != contents[2]

  def test_frames_compress_like_camera_frames_not_like_noise(self):
    rng = np.random.default_rng(0)
    jpeg = records.camera_like_jpeg(rng, 512, 640)
    assert 15_000 < len(jpeg) < 120_000
