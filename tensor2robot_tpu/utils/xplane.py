"""Dependency-free xplane.pb reader: per-op device-time attribution.

``jax.profiler.trace`` writes TensorBoard xplane protos, but this image
(and many serving hosts) carries no profiler proto bindings — so
per-op attribution walks the wire format directly, on the SAME
protobuf-free primitives the framework's tf.Example codec uses
(`data/wire.py` `_iter_fields`, which raises on
malformed varints and unsupported wire types, so truncated or corrupt
captures fail loudly instead of desynchronizing into garbage totals).

Wire schema subset (tensorflow/tsl profiler xplane.proto):

    XSpace  { repeated XPlane planes = 1; }
    XPlane  { string name = 2; repeated XLine lines = 3;
              map<int64, XEventMetadata> event_metadata = 4; }
    XLine   { string name = 2; int64 timestamp_ns = 3;
              repeated XEvent events = 4; }
    XEvent  { int64 metadata_id = 1; int64 offset_ps = 2;
              int64 duration_ps = 3; }
    XEventMetadata { string name = 2; }

Typical use::

    jax.profiler.start_trace(logdir); ...steps...; jax.profiler.stop_trace()
    path = glob.glob(logdir + '/**/*.xplane.pb', recursive=True)[0]
    for name, ms in op_families(path, n_steps=3)[:20]:
        print(name, ms)

Caveats: summing a line's events assumes the line is a serial stream —
true for the TensorCore ``XLA Ops`` line; the ``Async XLA Ops`` line
holds overlapping DMA windows and must not be summed as wall time. The
aggregators operate on exactly ONE plane and raise when ``plane_substr``
matches several (a multi-chip capture has one TPU plane per chip;
summing across them would multiply ms/step by the chip count).
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Tuple

# Shared protobuf wire walker (loud on malformed input) — the one place
# varint/field framing is implemented in this codebase.
from tensor2robot_tpu.data.wire import _iter_fields

_WIRE_VARINT = 0
_WIRE_BYTES = 2


def _parse_event(buf, start, end) -> Tuple[int, int, int]:
  metadata_id = offset_ps = duration_ps = 0
  for field, wire, value in _iter_fields(buf, start, end):
    if field == 1 and wire == _WIRE_VARINT:
      metadata_id = value
    elif field == 2 and wire == _WIRE_VARINT:
      offset_ps = value
    elif field == 3 and wire == _WIRE_VARINT:
      duration_ps = value
  return metadata_id, duration_ps, offset_ps


def _parse_line(buf, start, end):
  name = ''
  timestamp_ns = 0
  events: List[Tuple[int, int, int]] = []
  for field, wire, value in _iter_fields(buf, start, end):
    if field == 2 and wire == _WIRE_BYTES:
      name = bytes(buf[value[0]:value[1]]).decode('utf-8', 'replace')
    elif field == 3 and wire == _WIRE_VARINT:
      timestamp_ns = value
    elif field == 4 and wire == _WIRE_BYTES:
      events.append(_parse_event(buf, *value))
  return name, events, timestamp_ns


def _parse_metadata_entry(buf, start, end) -> Tuple[int, str]:
  key = 0
  name = ''
  for field, wire, value in _iter_fields(buf, start, end):
    if field == 1 and wire == _WIRE_VARINT:
      key = value
    elif field == 2 and wire == _WIRE_BYTES:
      for f2, w2, v2 in _iter_fields(buf, *value):
        if f2 == 2 and w2 == _WIRE_BYTES:
          name = bytes(buf[v2[0]:v2[1]]).decode('utf-8', 'replace')
  return key, name


def _parse_plane(buf, start, end):
  name = ''
  lines = []
  metadata: Dict[int, str] = {}
  for field, wire, value in _iter_fields(buf, start, end):
    if field == 2 and wire == _WIRE_BYTES:
      name = bytes(buf[value[0]:value[1]]).decode('utf-8', 'replace')
    elif field == 3 and wire == _WIRE_BYTES:
      lines.append(_parse_line(buf, *value))
    elif field == 4 and wire == _WIRE_BYTES:
      key, meta_name = _parse_metadata_entry(buf, *value)
      metadata[key] = meta_name
  return name, lines, metadata


def _parse_planes(path: str):
  """As ``parse_xspace``, each line with its ``timestamp_ns`` as a third
  entry (event offsets count from it)."""
  with open(path, 'rb') as f:
    buf = f.read()
  planes = []
  for field, wire, value in _iter_fields(buf, 0, len(buf)):
    if field == 1 and wire == _WIRE_BYTES:
      planes.append(_parse_plane(buf, *value))
  return planes


def parse_xspace(path: str):
  """[(plane_name, [(line_name, [(metadata_id, duration_ps,
  offset_ps)])], meta)]."""
  return [(name, [line[:2] for line in lines], metadata)
          for name, lines, metadata in _parse_planes(path)]


def timed_events(path: str, plane_substr: str = 'TPU'
                 ) -> Dict[str, List[Tuple[str, float, float]]]:
  """{line name: [(event name, start_ns, duration_ns)]} of the FIRST plane
  (by name) matching ``plane_substr``, every line on the capture's one
  clock (line timestamp + event offset). {} when no plane matches.

  One plane on purpose: a chip's ``XLA Ops`` (serial device stream) and
  ``XLA Modules`` (one event per program execution) lines share a clock,
  which is what lining host records up against device idle gaps needs.
  """
  planes = sorted((p for p in _parse_planes(path) if plane_substr in p[0]),
                  key=lambda p: p[0])
  if not planes:
    return {}
  _, lines, metadata = planes[0]
  return {
      line_name: [(metadata.get(metadata_id, str(metadata_id)),
                   timestamp_ns + offset_ps / 1e3, duration_ps / 1e3)
                  for metadata_id, duration_ps, offset_ps in events]
      for line_name, events, timestamp_ns in lines}


def op_totals(path: str,
              n_steps: int = 1,
              plane_substr: str = 'TPU',
              line_name: str = 'XLA Ops') -> Dict[str, float]:
  """{full op name: ms per step} over ONE plane's selected serial line.

  Raises when ``plane_substr`` is ambiguous (several matching planes
  with that line — e.g. one per chip on a multi-chip capture): summing
  across chips would report chip_count x the per-chip step time.
  """
  matches = []
  for name, lines, metadata in parse_xspace(path):
    if plane_substr not in name:
      continue
    totals: Dict[str, float] = {}
    for lname, events in lines:
      if lname != line_name:
        continue
      for metadata_id, duration_ps, _ in events:
        key = metadata.get(metadata_id, str(metadata_id))
        totals[key] = totals.get(key, 0.0) + duration_ps / 1e9 / n_steps
    if totals:
      matches.append((name, totals))
  if len(matches) > 1:
    raise ValueError(
        'plane_substr {!r} matches {} planes with a {!r} line ({}); '
        'narrow it to one device (e.g. "/device:TPU:0").'.format(
            plane_substr, len(matches), line_name,
            [name for name, _ in matches]))
  return matches[0][1] if matches else {}


def line_stats(path: str) -> List[Dict[str, object]]:
  """Per-line busy/extent/occupancy digest for every plane in a capture.

  For each (plane, line) with at least one event::

      {'plane': str, 'line': str, 'events': int,
       'busy_ms':   sum of event durations,
       'extent_ms': max(offset+duration) - min(offset),
       'occupancy': busy_ms / extent_ms (0.0 when the extent is empty)}

  ``occupancy`` is only meaningful for SERIAL lines (the TensorCore
  ``XLA Ops`` line, a CPU executor thread): there it is the fraction of
  the line's active window the device/thread was busy — the idle-gap
  complement is what host-side stalls look like from the device.
  Nested/overlapping lines (the host ``python`` line holds enclosing
  TraceMes) can exceed 1.0; report, don't assert, on those.
  """
  out: List[Dict[str, object]] = []
  for plane_name, lines, _ in parse_xspace(path):
    for line_name, events in lines:
      if not events:
        continue
      busy_ps = 0
      lo = math.inf
      hi = -math.inf
      for _, duration_ps, offset_ps in events:
        busy_ps += duration_ps
        if offset_ps < lo:
          lo = offset_ps
        if offset_ps + duration_ps > hi:
          hi = offset_ps + duration_ps
      extent_ps = max(hi - lo, 0)
      out.append({
          'plane': plane_name,
          'line': line_name,
          'events': len(events),
          'busy_ms': busy_ps / 1e9,
          'extent_ms': extent_ps / 1e9,
          'occupancy': (busy_ps / extent_ps) if extent_ps else 0.0,
      })
  return out


_FAMILY_RE = re.compile(r'\.\d+$')


def op_families(path: str, n_steps: int = 1,
                plane_substr: str = 'TPU',
                line_name: str = 'XLA Ops'
                ) -> List[Tuple[str, float]]:
  """[(op family, ms/step)] descending — '%fusion.12' folds to '%fusion'."""
  families: Dict[str, float] = {}
  for key, ms in op_totals(path, n_steps, plane_substr, line_name).items():
    fam = _FAMILY_RE.sub('', key.split(' = ')[0])
    families[fam] = families.get(fam, 0.0) + ms
  return sorted(families.items(), key=lambda kv: -kv[1])
