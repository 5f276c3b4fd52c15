"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything a cell is made of is data
(see README.md beside this file); the drivers are one per KIND of traffic.
"""

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.common import log  # noqa: E402


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, default=0)
  parser.add_argument('--seconds', type=float, default=None)
  parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
  parser.add_argument('--manifest', default=cells.MANIFEST,
                      help='another BENCHMARK.json (the tests\' tiny one)')
  args = parser.parse_args(argv)

  cell = cells.Cell(args.manifest, args.workload)
  seconds = (args.seconds if args.seconds is not None
             else float(cell.manifest['run_seconds']))
  log('cell {} = configuration {} x traffic {} ({}) on {} chip(s), seed {}, '
      '{} s, trace {}', cell.name, cell.config_name, cell.traffic_name,
      cell.traffic['kind'], cell.chips, args.seed, seconds, args.trace)
  driver = importlib.import_module(
      'benchmark.harness.' + cell.traffic['kind'])
  result = driver.run(cell, args.seed, seconds, bool(args.trace))

  observations = result.pop('observations')
  names = cell.metric_names('per_layer' if args.trace else 'end_to_end')
  result['metrics'] = cells.read_metrics(
      names, observations, cells.units_of(cell.manifest))
  missing = [n for n in names if n not in result['metrics']]
  if missing:
    log('metrics with nothing to read, left out: {}', ', '.join(missing))
  reduced = observations.get('trace')
  if args.trace and reduced:
    result['device']['busy_s'] = reduced['busy_s']
    result['device']['window_s'] = reduced['window_s']
    result['breakdown'] = reduced['breakdown']
  print(json.dumps(result), flush=True)
  return 0


if __name__ == '__main__':
  sys.exit(main())
