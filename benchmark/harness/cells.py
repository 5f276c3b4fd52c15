"""A cell is data: one entry of ``workloads`` naming a configuration file, a
traffic file and the metrics that list it. Nothing here knows a cell's name."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, 'BENCHMARK.json')
METRICS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), 'metrics')


def load_json(path):
  with open(path, encoding='utf-8') as f:
    return json.load(f)


class Cell:
  """One workload of a manifest, with its files read."""

  def __init__(self, manifest_path, name):
    self.manifest_path = os.path.abspath(manifest_path)
    self.base = os.path.dirname(self.manifest_path)
    self.manifest = load_json(self.manifest_path)
    entries = [w for w in self.manifest['workloads'] if w['name'] == name]
    if len(entries) != 1:
      raise SystemExit('{}: {} workloads named {!r} (have: {})'.format(
          manifest_path, len(entries), name,
          ', '.join(w['name'] for w in self.manifest['workloads'])))
    self.entry = entries[0]
    self.name = name
    self.chips = int(self.entry['chips'])
    config_entry = next(c for c in self.manifest['configs']
                        if c['name'] == self.entry['config'])
    self.config = load_json(os.path.join(self.base, config_entry['file']))
    self.config_name = config_entry['name']
    # The traffic file sits beside the configurations' directory:
    # <dir>/configs/<config>.json, <dir>/traffic/<traffic>.json.
    traffic_dir = os.path.join(
        os.path.dirname(os.path.dirname(
            os.path.join(self.base, config_entry['file']))), 'traffic')
    self.traffic_name = self.entry['traffic']
    self.traffic = load_json(
        os.path.join(traffic_dir, self.traffic_name + '.json'))

  def metric_names(self, group):
    """Names of the ``end_to_end`` or ``per_layer`` metrics of this cell."""
    return [m['name'] for m in self.manifest[group]
            if 'workloads' not in m or self.name in m['workloads']]


def metric_readers(metrics_dir=METRICS_DIR):
  """{metric name: reader} over every ``*.py`` of the metrics directory.

  A metric file holds ``METRICS = {name: read}`` with
  ``read(observations) -> number or None``; a later PR adds a file."""
  readers = {}
  for filename in sorted(os.listdir(metrics_dir)):
    if not filename.endswith('.py') or filename.startswith('_'):
      continue
    spec = importlib.util.spec_from_file_location(
        'benchmark_metric_' + filename[:-3],
        os.path.join(metrics_dir, filename))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for name, read in module.METRICS.items():
      if name in readers:
        raise ValueError('metric {!r} has two readers'.format(name))
      readers[name] = read
  return readers


def read_metrics(names, observations, units, metrics_dir=METRICS_DIR):
  """{name: {'value', 'unit'}} for every named metric whose reader found
  something to read; the others are left out of the line."""
  readers = metric_readers(metrics_dir)
  out = {}
  for name in names:
    read = readers.get(name)
    value = read(observations) if read else None
    if value is not None:
      out[name] = {'value': float(value), 'unit': units[name]}
  return out


def units_of(manifest):
  return {m['name']: m['unit']
          for group in ('end_to_end', 'per_layer') for m in manifest[group]}
