"""One file of peaks per device kind, under ``benchmark/peaks``. A device
kind with no file is an error, never a default."""

import os

from benchmark.harness import cells

PEAKS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'peaks')


def peaks_for(device_kind, peaks_dir=PEAKS_DIR):
  path = os.path.join(peaks_dir, device_kind.replace(' ', '_') + '.json')
  if not os.path.exists(path):
    raise LookupError(
        'no peaks file for device_kind {!r} (looked for {}): add one with '
        'its source, do not guess'.format(device_kind, path))
  row = cells.load_json(path)
  if row['device_kind'] != device_kind:
    raise LookupError('{} is for {!r}, not {!r}'.format(
        path, row['device_kind'], device_kind))
  return row
