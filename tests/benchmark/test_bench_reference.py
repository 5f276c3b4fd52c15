"""What decides ``correct``: the comparison rule, and that ``--seed`` fixes
the batch the comparison is made on."""

import numpy as np
import pytest

import bench_helpers as helpers
from benchmark.harness import cells, common, records, reference, train_disk


@pytest.mark.parametrize('got,want,rel,ok', [
    (100.0, 100.0, 1e-3, True),
    (100.09, 100.0, 1e-3, True),
    (100.11, 100.0, 1e-3, False),
    (99.0, 100.0, 1e-3, False),     # a loss that reads LOW fails as well
    (float('nan'), 100.0, 1.0, False),
    (float('inf'), 100.0, 1.0, False),
    (0.0, 0.0, 1e-3, True),
])
def test_agreement_is_relative_to_the_reference(got, want, rel, ok):
  agrees, report = reference.agree(got, want, rel, 'loss')
  assert agrees is ok
  assert report.startswith('loss: ') and 'tolerance' in report


def _first_batches(tmp_path, seeds):
  from tensor2robot_tpu.data.input_generators import (
      DefaultRecordInputGenerator,
  )
  from tensor2robot_tpu.modes import ModeKeys

  cell = cells.Cell(helpers.TINY, 'tiny_train')
  model = common.build_model(cell.config['model'])
  specs = records.flat_specs([
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      model.preprocessor.get_in_label_specification(ModeKeys.TRAIN)])
  path = str(tmp_path / 'r.tfrecord')
  records.write_records(path, specs, 12, seed=5)
  out = []
  for seed in seeds:
    generator = train_disk.RecordedInput(
        DefaultRecordInputGenerator(file_patterns=path, batch_size=2), None)
    features, _ = generator.prime(model, ModeKeys.TRAIN, seed)
    out.append({k: np.array(v) for k, v in features.to_dict().items()})
    assert next(generator)[0] is features  # the trainer gets it back first
  return out


def test_the_seed_fixes_the_first_batch(tmp_path):
  """``Trainer.train`` asks for an unseeded shuffle, so two runs of one seed
  drew different first batches and ``correct`` turned on the draw (a run
  failed its tolerance that way, PERF.md, Findings PR 24)."""
  a, b, c = _first_batches(tmp_path, [7, 7, 8])
  for key in a:
    np.testing.assert_array_equal(a[key], b[key])
  assert any(not np.array_equal(a[key], c[key]) for key in a)


def test_the_batch_checksums_tell_another_order_from_other_examples():
  """The line a reader compares between two runs of one seed."""
  rng = np.random.default_rng(0)
  images = rng.integers(0, 255, (4, 3, 5, 3), dtype=np.uint8)
  vectors = rng.normal(size=(4, 2)).astype(np.float32)
  same = train_disk._batch_checksums([images, vectors])
  assert same == train_disk._batch_checksums([images.copy(), vectors.copy()])
  order = [2, 0, 3, 1]
  moved = train_disk._batch_checksums([images[order], vectors[order]])
  assert moved[0] != same[0] and moved[1] == same[1]
  images[1, 0, 0, 0] ^= 1
  other = train_disk._batch_checksums([images, vectors])
  assert other[0] != same[0] and other[1] != same[1]
