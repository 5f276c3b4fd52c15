"""Profiler trace -> device busy and idle, op families, exposed collective
time, and idle gaps named for what the host was doing.

Two steps, so that the reduction can be checked on a small recorded trace:
``load_xplane`` turns the profiler's ``.xplane.pb`` into plain lists (the
device planes' op, async-op and module lines), ``align_host_spans`` adds the
host spans the drivers noted on their own clock, and ``reduce_trace`` does
the arithmetic on those.

What the lines hold on this installation (looked at by hand, PR 24): a plane
``/device:TPU:<n>`` per chip with a serial line ``XLA Ops`` whose events are
named by their whole HLO instruction (``%fusion.3 = ... fusion(...),
kind=kOutput, calls=...``), a line ``Async XLA Ops`` of overlapping windows
(copies, asynchronous collectives), and a line ``XLA Modules`` with one event
per execution of a compiled program. The host's own lines are not read:
the host tracer is off (``common.ProfilerWindow`` says why).
"""

import re

import numpy as np

OPS_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'
MODULES_LINE = 'XLA Modules'
_COLLECTIVES = ('all-reduce', 'all-gather', 'all-to-all', 'reduce-scatter',
                'collective-permute', 'collective-broadcast')
_SHORT_GAP_NS = 2000


def load_xplane(path):
  """{'devices': [{'name', 'ops', 'async', 'modules'}], 'host': []} with
  every event as [name, start_ns, duration_ns]."""
  from jax.profiler import ProfileData

  devices = []
  for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith('/device:') and 'CUSTOM' not in plane.name:
      lines = {line.name: line for line in plane.lines}
      if OPS_LINE not in lines:
        continue
      devices.append({
          'name': plane.name,
          **{key: [[e.name, e.start_ns, e.duration_ns]
                   for e in lines[line_name].events]
             if line_name in lines else []
             for key, line_name in (('ops', OPS_LINE), ('async', ASYNC_LINE),
                                    ('modules', MODULES_LINE))}})
  devices.sort(key=lambda d: d['name'])
  return {'devices': devices, 'host': []}


def align_host_spans(loaded, marker, marker_done_s, spans):
  """Puts host spans [(name, start_s, end_s)] by the host's clock on the
  trace's clock, and cuts the marker out of the trace.

  The marker is a tiny program the host ran and waited for right after the
  profiler started: the host saw it end at ``marker_done_s``, the first
  device's ``XLA Modules`` line says when it ended on the trace's clock, and
  the difference of the two is the offset between the clocks. Everything up
  to the marker's end is dropped from every device, so that the marker is
  neither busy time nor the start of the window."""
  if not loaded['devices']:
    return loaded
  ends = [start + duration
          for name, start, duration in loaded['devices'][0]['modules']
          if marker in name]
  if not ends:
    return loaded
  marker_end_ns = min(ends)
  offset_ns = marker_done_s * 1e9 - marker_end_ns
  for device in loaded['devices']:
    for key in ('ops', 'async', 'modules'):
      device[key] = [e for e in device[key] if e[1] > marker_end_ns]
  loaded['host'] = [[name, start_s * 1e9 - offset_ns,
                     (end_s - start_s) * 1e9]
                    for name, start_s, end_s in spans]
  return loaded


_NAME_RE = re.compile(r'^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.remat\d*)?(?:\.\d+)*\s*=')
_KIND_RE = re.compile(r'kind=(k\w+)')
_OPCODE_RE = re.compile(r'\s([a-z][a-z\-]*)\(')


def op_family(instruction):
  """'%convert_reduce_fusion.1.remat = ... fusion(...), kind=kOutput, ...'
  -> 'convert_reduce_fusion kOutput'; '%all-reduce.5 = ...' -> 'all-reduce'.
  A name with no ' = ' (already short) is folded the same way."""
  text = instruction if '=' in instruction else instruction + ' ='
  match = _NAME_RE.match(text)
  family = match.group(1) if match else instruction.split(' ')[0].lstrip('%')
  kind = _KIND_RE.search(instruction)
  return '{} {}'.format(family, kind.group(1)) if kind else family


def is_collective(instruction):
  family = op_family(instruction)
  return family.startswith(_COLLECTIVES)


def is_conv(instruction):
  """The convolution families: a bare ``convolution`` op, or an OUTPUT fusion,
  which on the TPU is a convolution (matrix products are convolutions there
  too) with its elementwise epilogue fused behind it."""
  if 'kind=kOutput' in instruction:
    return True
  opcode = _OPCODE_RE.search(instruction.split(' = ', 1)[-1])
  return bool(opcode) and opcode.group(1) == 'convolution'


def _merge(intervals):
  """Sorted, disjoint [start, end) pairs covering the same time."""
  merged = []
  for start, end in sorted(intervals):
    if end <= start:
      continue
    if merged and start <= merged[-1][1]:
      merged[-1][1] = max(merged[-1][1], end)
    else:
      merged.append([start, end])
  return merged


def _total(intervals):
  return sum(end - start for start, end in intervals)


def _subtract(intervals, holes):
  """The part of merged ``intervals`` that merged ``holes`` do not cover."""
  out, j = [], 0
  for start, end in intervals:
    while j < len(holes) and holes[j][1] <= start:
      j += 1
    k, cursor = j, start
    while k < len(holes) and holes[k][0] < end:
      if holes[k][0] > cursor:
        out.append([cursor, holes[k][0]])
      cursor = max(cursor, holes[k][1])
      k += 1
    if cursor < end:
      out.append([cursor, end])
  return out


def _spans(events, keep=lambda name: True):
  return [[s, s + d] for name, s, d in events if keep(name)]


def reduce_device(device):
  """One chip's numbers, all in seconds."""
  ops = device['ops']
  if not ops:
    return None
  busy = _merge(_spans(ops))
  start, end = busy[0][0], busy[-1][1]
  compute = _merge(_spans(ops, lambda n: not is_collective(n)))
  collective = _merge(_spans(ops, is_collective) +
                      _spans(device.get('async', []), is_collective))
  families = {}
  conv_ns = collective_ops = 0
  for name, _, duration in ops:
    family = op_family(name)
    families[family] = families.get(family, 0) + duration
    if is_conv(name):
      conv_ns += duration
    if is_collective(name) and not family.endswith('-done'):
      collective_ops += 1
  modules = {}
  for name, _, duration in device.get('modules', []):
    modules.setdefault(name.split('(')[0], []).append(duration / 1e9)
  return {
      'window_s': (end - start) / 1e9,
      'busy_s': _total(busy) / 1e9,
      'busy': busy,
      'families': {k: v / 1e9 for k, v in families.items()},
      'conv_s': conv_ns / 1e9,
      'collective_ops': collective_ops,
      'collective_s': _total(collective) / 1e9,
      'collective_exposed_s': _total(_subtract(collective, compute)) / 1e9,
      'modules': modules,
  }


def name_gaps(busy, host):
  """{label: seconds} of the idle gaps of one chip: each gap of 2 us or more
  is named for the innermost host annotation open at its middle
  (``no_host_event`` if none was)."""
  labelled = sorted(host, key=lambda e: e[1])
  starts = np.array([e[1] for e in labelled], np.float64)
  out = {}
  short_ns = 0
  for (_, gap_start), (gap_end, _) in zip(busy[:-1], busy[1:]):
    length = gap_end - gap_start
    if length < _SHORT_GAP_NS:
      short_ns += length
      continue
    middle = (gap_start + gap_end) / 2
    label = 'no_host_event'
    # Innermost: the latest-started annotation that is still open.
    for i in range(int(np.searchsorted(starts, middle, 'right')) - 1, -1, -1):
      name, s, d = labelled[i]
      if s + d >= middle:
        label = name
        break
    out[label] = out.get(label, 0) + length
  out = {k: v / 1e9 for k, v in out.items()}
  if short_ns:
    out['gaps_shorter_than_2_us'] = short_ns / 1e9
  return out


def reduce_trace(trace, top=10):
  """The traced window's numbers: averages over the chips, seconds."""
  per_device = [r for r in map(reduce_device, trace['devices']) if r]
  if not per_device:
    return None
  mean = lambda key: float(np.mean([r[key] for r in per_device]))
  families = {}
  for r in per_device:
    for family, seconds in r['families'].items():
      families[family] = families.get(family, 0.0) + seconds / len(per_device)
  modules = {}
  for r in per_device:
    for name, durations in r['modules'].items():
      modules.setdefault(name, []).extend(durations)
  gaps = name_gaps(per_device[0]['busy'], trace['host'])
  rank = lambda table: [[k, v] for k, v in sorted(
      table.items(), key=lambda kv: -kv[1])[:top]]
  return {
      'chips': len(per_device),
      'window_s': mean('window_s'),
      'busy_s': mean('busy_s'),
      'idle_share': 1.0 - mean('busy_s') / mean('window_s'),
      'conv_s': mean('conv_s'),
      'collective_ops': mean('collective_ops'),
      'collective_s': mean('collective_s'),
      'collective_exposed_s': mean('collective_exposed_s'),
      'families': families,
      'modules': modules,
      'breakdown': {'device_ops': rank(families), 'idle_gaps': rank(gaps)},
  }


def main_module(reduced):
  """(name, [seconds of each execution]) of the program that took most of
  the device's time: the train step."""
  if not reduced or not reduced['modules']:
    return None, []
  name = max(reduced['modules'], key=lambda k: sum(reduced['modules'][k]))
  return name, reduced['modules'][name]
