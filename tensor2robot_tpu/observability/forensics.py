"""Structured trace reports: raw xplane capture -> forensics/<step>.json.

A profiler window that ends as an unread ``.xplane.pb`` proto answered
nothing. This module turns each capture into the report a human (or
``t2r_telemetry doctor``) actually wants, using only in-tree readers:

  * top-k op families by device time (`utils/xplane.py` — the round-5
    attribution machinery, now automated), with a host-executor fallback
    for captures without a TPU plane (CPU runs name their XLA thunks on
    ``tf_...`` executor thread lines);
  * device occupancy, and every device idle gap of the capture named for
    the program span (`spans.py` ring) the capturing thread had open in
    its middle — the ring's clock and the trace's are tied by the clock
    marker the ``AutoProfiler`` runs as the trace starts;
  * collective counts/bytes from the compiled step's HLO
    (`parallel/hlo_analysis.py`), when the trainer can provide it;
  * the goodput split of the surrounding run with a ranked attribution
    ("lost to data 34% -> prefetch queue empty at sample time");
  * the registry counter delta across the capture window.

``build_report`` NEVER raises: every section degrades to a ``warnings``
entry on torn/truncated/ambiguous captures (tests/test_xplane.py drives
those paths), because it runs inside the trainer loop where an exception
would cost the training run a profiler bug was supposed to explain.

Report schema (``schema`` field, versioned): docs/observability.md.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

from tensor2robot_tpu.observability import registry as registry_lib

__all__ = ['FORENSICS_DIRNAME', 'REPORT_SCHEMA', 'CLOCK_MARKER',
           'NO_HOST_EVENT', 'build_report', 'write_report', 'read_reports',
           'find_latest_xplane', 'attribute_goodput', 'name_idle_gaps',
           'split_collective_wait']

FORENSICS_DIRNAME = 'forensics'
REPORT_SCHEMA = 't2r.forensics.v1'
DEFAULT_TOP_K = 15

# Fractions below this are noise, not a diagnosis.
_ATTRIBUTION_FLOOR = 0.05

# The tiny program ``AutoProfiler`` runs and waits for right after
# ``start_trace``: the host notes ``perf_counter_ns`` at its end, the
# trace's ``XLA Modules`` line holds the same end on the trace's clock.
CLOCK_MARKER = 't2r_clock_marker'
NO_HOST_EVENT = 'no_host_event'
# Gaps between back-to-back device ops (launch latency) are not stalls.
_SHORT_GAP_NS = 2000
# A report stays a readable file: the newest records of a long capture.
_MAX_HOST_SPANS = 4096


def find_latest_xplane(model_dir: str,
                       newer_than: Optional[float] = None) -> Optional[str]:
  """Newest ``*.xplane.pb`` under model_dir's profile plugin dir, or None.

  ``newer_than`` (st_mtime) filters out captures from EARLIER windows of
  the same run — stop_trace always writes a fresh file.
  """
  pattern = os.path.join(model_dir, 'plugins', 'profile', '**',
                         '*.xplane.pb')
  best: Tuple[float, Optional[str]] = (-1.0, None)
  for path in glob.glob(pattern, recursive=True):
    try:
      mtime = os.stat(path).st_mtime
    except OSError:
      continue
    if newer_than is not None and mtime < newer_than:
      continue
    if mtime > best[0]:
      best = (mtime, path)
  return best[1]


def _device_top_ops(xplane_path: str, n_steps: int, top_k: int):
  """(top_ops, occupancy, warnings, families) from one capture.

  Prefers the TPU ``XLA Ops`` line (serial device stream). A capture
  with several TPU planes (multi-chip) is narrowed to the first plane —
  summing across chips would multiply ms/step by the chip count — with a
  warning naming the unanalyzed planes. Captures without a TPU plane
  (CPU backend) fall back to the busiest ``tf_...`` executor thread line
  so auto-analysis still names the hot thunks.
  """
  from tensor2robot_tpu.utils import xplane

  warnings: List[str] = []
  top_ops: List[Dict[str, object]] = []
  occupancy = None
  source = None
  try:
    families = xplane.op_families(xplane_path, n_steps=n_steps)
    source = 'device'
  except ValueError as e:
    if 'matches' not in str(e):
      raise
    # Multi-chip capture: analyze exactly one plane, loudly.
    plane_names = [name for name, _, _ in xplane.parse_xspace(xplane_path)
                   if 'TPU' in name]
    warnings.append('multi-plane capture ({}); analyzed {} only'.format(
        ', '.join(plane_names), plane_names[0]))
    families = xplane.op_families(xplane_path, n_steps=n_steps,
                                  plane_substr=plane_names[0])
    source = 'device'
  stats = xplane.line_stats(xplane_path)
  if not families:
    # No TPU plane (CPU run): the executor thread lines hold the thunks.
    executor = [s for s in stats if str(s['line']).startswith('tf_')]
    if executor:
      busiest = max(executor, key=lambda s: s['busy_ms'])
      totals: Dict[str, float] = {}
      for name, lines, metadata in xplane.parse_xspace(xplane_path):
        if name != busiest['plane']:
          continue
        for line_name, events in lines:
          if line_name != busiest['line']:
            continue
          for metadata_id, duration_ps, _ in events:
            key = metadata.get(metadata_id, str(metadata_id))
            totals[key] = totals.get(key, 0.0) + duration_ps / 1e9 / n_steps
      families = sorted(totals.items(), key=lambda kv: -kv[1])
      source = 'host_executor'
      warnings.append('no TPU plane in capture; op times come from host '
                      'executor line {!r}'.format(busiest['line']))
  if families:
    total_ms = sum(ms for _, ms in families)
    top_ops = [{'name': name, 'ms_per_step': ms,
                'fraction': (ms / total_ms) if total_ms else 0.0,
                'source': source}
               for name, ms in families[:top_k]]
  # Occupancy of the analyzed serial line.
  device_lines = [s for s in stats
                  if (s['line'] == 'XLA Ops' and 'TPU' in str(s['plane']))
                  or (source == 'host_executor'
                      and str(s['line']).startswith('tf_'))]
  if device_lines:
    occupancy = dict(max(device_lines, key=lambda s: s['busy_ms']))
  if not top_ops:
    warnings.append('capture held no attributable op events')
  return top_ops, occupancy, warnings, families


def name_idle_gaps(busy: List[Tuple[float, float]], host_spans
                   ) -> Dict[str, float]:
  """{span name: seconds} over the idle gaps of one serial device stream.

  ``busy``: sorted, disjoint (start_ns, end_ns) intervals in which an op
  ran, on the SAME clock as ``host_spans`` (ring records of one thread).
  Each gap of 2 us or more between two intervals is named for the
  innermost span open at its middle — of those that contain the moment,
  the one that started last — or ``no_host_event`` if none was. The one
  gap-naming rule of the program (the benchmark keeps its own copy).
  """
  spans = sorted((r for r in host_spans if r.end_ns > r.start_ns),
                 key=lambda r: (r.start_ns, r.id))
  out: Dict[str, float] = {}
  for (_, gap_start), (gap_end, _) in zip(busy[:-1], busy[1:]):
    if gap_end - gap_start < _SHORT_GAP_NS:
      continue
    middle = (gap_start + gap_end) / 2
    label = NO_HOST_EVENT
    for record in spans:
      if record.start_ns > middle:
        break
      if record.end_ns >= middle:
        label = record.name
    out[label] = out.get(label, 0.0) + (gap_end - gap_start) / 1e9
  return out


def _host_device_overlap(xplane_path: str, host_trace: Dict[str, object]):
  """The capture's idle gaps by what the capturing thread was doing, or
  None where the two clocks cannot be tied: no device plane (CPU), or no
  marker execution in it."""
  from tensor2robot_tpu.utils import xplane

  lines = xplane.timed_events(xplane_path)
  marker_ends = [start + duration
                 for name, start, duration in lines.get('XLA Modules', [])
                 if CLOCK_MARKER in name]
  if not marker_ends or not lines.get('XLA Ops'):
    return None
  marker_end = min(marker_ends)
  # trace clock + offset = the ring's clock.
  offset_ns = float(host_trace['marker_done_ns']) - marker_end
  busy: List[List[float]] = []
  for _, start, duration in sorted(lines['XLA Ops'], key=lambda e: e[1]):
    if start <= marker_end:  # the marker is neither work nor the start
      continue
    start, end = start + offset_ns, start + duration + offset_ns
    if busy and start <= busy[-1][1]:
      busy[-1][1] = max(busy[-1][1], end)
    else:
      busy.append([start, end])
  if not busy:
    return None
  thread = host_trace.get('thread')
  gaps = name_idle_gaps(
      busy, [r for r in host_trace['records'] if r.thread == thread])
  busy_ms = sum(end - start for start, end in busy) / 1e6
  extent_ms = (busy[-1][1] - busy[0][0]) / 1e6
  return {
      'device_busy_ms': busy_ms,
      'device_extent_ms': extent_ms,
      # Device idle inside its own active window == time the host
      # failed to keep it fed (dispatch gaps, data waits).
      'device_idle_fraction': 1.0 - min(busy_ms / max(extent_ms, 1e-9), 1.0),
      'idle_gaps_ms': {name: seconds * 1e3 for name, seconds in sorted(
          gaps.items(), key=lambda kv: -kv[1])},
      'clock_offset_ns': offset_ns,
      'host_thread': thread,
  }


_COLLECTIVE_TOKENS = ('all-reduce', 'all-gather', 'all-to-all',
                      'collective-permute', 'reduce-scatter',
                      'collective-broadcast')


def _collective_kind(op_family: str) -> Optional[str]:
  """The collective kind an op family name carries, or None for compute."""
  for token in _COLLECTIVE_TOKENS:
    if token in op_family:
      return token
  return None


def split_collective_wait(families: List[Tuple[str, float]],
                          hlo_collectives: Optional[List[Dict[str, object]]]
                          = None) -> Dict[str, object]:
  """Device time split: compute vs. time spent inside collectives.

  ``families`` is the capture's full [(op family, ms/step)] table. A
  collective op's device time is transfer PLUS the wait for every
  other participant to arrive — which is exactly why this is the fleet
  straggler's signature: on the straggling host the step is long in
  COMPUTE, on every other host it is long in collective-wait. The
  fraction here, read per host across a fleet's captures, names which
  hosts waited and which one they waited for; ``gating_collective`` is
  the collective family that burned the most device time.
  ``hlo_collectives`` (``hlo_analysis.collective_ops``) attaches the
  per-step payload bytes each named collective moves.
  """
  hlo_bytes: Dict[str, int] = {}
  hlo_kind_bytes: Dict[str, int] = {}
  for op in hlo_collectives or []:
    family = '%' + _FAMILY_SUFFIX_RE.sub('', str(op.get('name', '')))
    hlo_bytes[family] = hlo_bytes.get(family, 0) + int(op.get('bytes', 0))
    kind = str(op.get('kind', ''))
    hlo_kind_bytes[kind] = hlo_kind_bytes.get(kind, 0) + \
        int(op.get('bytes', 0))
  compute_ms = 0.0
  collectives: List[Dict[str, object]] = []
  for name, ms in families:
    kind = _collective_kind(name)
    if kind is None:
      compute_ms += ms
      continue
    nbytes = hlo_bytes.get(name)
    if nbytes is None:
      # '-start' device events vs sync HLO names (or vice versa): fall
      # back to the kind's total payload as the best available figure.
      nbytes = hlo_kind_bytes.get(kind)
    collectives.append({'name': name, 'kind': kind, 'ms_per_step': ms,
                        'bytes': nbytes})
  collective_ms = sum(c['ms_per_step'] for c in collectives)
  total = compute_ms + collective_ms
  collectives.sort(key=lambda c: -c['ms_per_step'])
  for entry in collectives:
    entry['fraction'] = (entry['ms_per_step'] / total) if total else 0.0
  return {
      'compute_ms_per_step': compute_ms,
      'collective_ms_per_step': collective_ms,
      'collective_wait_fraction': (collective_ms / total) if total else 0.0,
      'collectives': collectives,
      'gating_collective': collectives[0]['name'] if collectives else None,
  }


_FAMILY_SUFFIX_RE = re.compile(r'\.\d+$')


def attribute_goodput(fractions: Dict[str, float],
                      scalars: Dict[str, float]
                      ) -> List[Dict[str, object]]:
  """Ranked non-productive goodput categories with evidence.

  ``fractions`` from ``GoodputTracker.fractions()``; ``scalars`` from
  ``TelemetryRegistry.scalars()`` — pure inputs so doctor can reuse this
  on telemetry.jsonl records without a live registry.
  """
  out: List[Dict[str, object]] = []
  lost = sorted(((cat, frac) for cat, frac in fractions.items()
                 if cat != 'productive' and frac >= _ATTRIBUTION_FLOOR),
                key=lambda kv: -kv[1])
  for category, fraction in lost:
    detail = ''
    if category == 'data':
      p95 = scalars.get('span/data.next/p95')
      depths = [(tag, value) for tag, value in scalars.items()
                if tag.startswith('data/prefetch_queue_depth')]
      parts = []
      if p95 is not None:
        parts.append('span/data.next p95 {:.1f} ms'.format(p95))
      if depths:
        if all(value <= 0.0 for _, value in depths):
          parts.append('prefetch queue empty at sample time: host decode '
                       'is the bottleneck')
        else:
          parts.append('prefetch depth ' + ', '.join(
              '{}={:g}'.format(tag.rsplit('/', 1)[-1], value)
              for tag, value in depths))
      detail = '; '.join(parts)
    elif category == 'checkpoint':
      p95 = scalars.get('span/ckpt.save/p95')
      count = scalars.get('span/ckpt.save/count')
      if p95 is not None:
        detail = 'span/ckpt.save p95 {:.1f} ms over {:g} saves'.format(
            p95, count or 0)
    elif category == 'retry':
      parts = []
      for tag, label in (('reliability/nan_rollbacks', 'nan rollbacks'),
                         ('reliability/preemptions', 'preemptions')):
        value = scalars.get(tag, 0.0)
        if value:
          parts.append('{} {:g}'.format(label, value))
      retries = sum(value for tag, value in scalars.items()
                    if tag.startswith('reliability/io_retries'))
      if retries:
        parts.append('io retries {:g}'.format(retries))
      detail = ', '.join(parts)
    out.append({'category': category, 'fraction': fraction,
                'detail': detail})
  return out


def build_report(step: int,
                 reason: str = 'static',
                 trigger: Optional[Dict[str, object]] = None,
                 window: Optional[Dict[str, object]] = None,
                 xplane_path: Optional[str] = None,
                 n_steps: int = 1,
                 hlo_text_fn: Optional[Callable[[], Optional[str]]] = None,
                 goodput_fractions: Optional[Dict[str, float]] = None,
                 counters_delta: Optional[Dict[str, float]] = None,
                 registry: Optional[registry_lib.TelemetryRegistry] = None,
                 tuned_config: Optional[str] = None,
                 pipeline: Optional[Dict[str, object]] = None,
                 host: Optional[Dict[str, object]] = None,
                 host_trace: Optional[Dict[str, object]] = None
                 ) -> Dict[str, object]:
  """Assembles the forensics report dict. Never raises: torn captures,
  missing HLO, or reader bugs each degrade to a ``warnings`` entry.

  ``tuned_config``: the active compile-config id (tuning/), or None for
  the stock compile — carried verbatim so a step-time regression is
  attributable to the config that compiled the step it profiled.
  ``pipeline``: the latest ``t2r.pipeline.v1`` X-ray record (stage
  capacity table + gating-stage attribution), carried verbatim so a
  data-path incident's report names the stage, not just the symptom.
  ``host``: this process's fleet identity (``signals.host_identity()``)
  — with the ``collective_wait`` split below, a straggler capture names
  WHICH host gated WHICH collective, not just that a step got slow.
  ``host_trace``: ``{'records', 'marker_done_ns', 'thread'}`` from the
  ``AutoProfiler`` — the span ring's records of the captured interval
  (carried as ``host_spans``), the ring-clock time at which the clock
  marker ended, and the capturing thread, whose spans name the device's
  idle gaps (``host_device_overlap``; absent without a device plane)."""
  registry = registry or registry_lib.get_registry()
  warnings: List[str] = []
  report: Dict[str, object] = {
      'schema': REPORT_SCHEMA,
      'step': int(step),
      'reason': reason,
      'trigger': dict(trigger or {}),
      'window': dict(window or {}),
      'xplane_path': xplane_path,
      'host': dict(host) if host else None,
      'top_ops': [],
      'device_occupancy': None,
      'host_device_overlap': None,
      'host_spans': [],
      'collectives': {},
      'collective_bytes_total': 0,
      'collective_wait': None,
      'goodput': dict(goodput_fractions or {}),
      'attribution': [],
      'counters_delta': dict(counters_delta or {}),
      'memory': {},
      'tuned_config': tuned_config,
      'pipeline': dict(pipeline) if pipeline else None,
      'roofline': None,
      'warnings': warnings,
  }
  try:
    scalars = registry.scalars()
  except Exception as e:  # noqa: BLE001
    scalars = {}
    warnings.append('registry scalars unavailable: {}'.format(e))
  families: List[Tuple[str, float]] = []
  if xplane_path is None:
    warnings.append('no xplane capture found for this window')
  else:
    try:
      top_ops, occupancy, op_warnings, families = \
          _device_top_ops(xplane_path, max(n_steps, 1), DEFAULT_TOP_K)
      report['top_ops'] = top_ops
      report['device_occupancy'] = occupancy
      warnings.extend(op_warnings)
      if host_trace is not None:
        report['host_device_overlap'] = _host_device_overlap(
            xplane_path, host_trace)
    except Exception as e:  # noqa: BLE001 — torn/truncated capture
      warnings.append('xplane analysis failed ({}: {}); raw capture kept '
                      'at {}'.format(type(e).__name__, e, xplane_path))
  if host_trace is not None:
    report['host_spans'] = [
        record._asdict()
        for record in host_trace['records'][-_MAX_HOST_SPANS:]]
  hlo_collectives = None
  hlo_text = None
  if hlo_text_fn is not None:
    try:
      hlo_text = hlo_text_fn()
      if hlo_text:
        from tensor2robot_tpu.parallel import hlo_analysis
        stats = hlo_analysis.collective_stats(hlo_text)
        report['collectives'] = stats
        report['collective_bytes_total'] = \
            hlo_analysis.total_collective_bytes(stats)
        hlo_collectives = hlo_analysis.collective_ops(hlo_text)
    except Exception as e:  # noqa: BLE001 — HLO is best-effort evidence
      warnings.append('collective analysis failed: {}'.format(e))
  if hlo_text:
    # Roofline attribution (t2r.roofline.v1): join the capture's
    # measured op-family ms with the per-family FLOPs/bytes cost table
    # parsed from the same program's post-opt HLO. Works even when the
    # capture produced no families (record carries costs, all
    # unattributed) — the step's intensity profile is evidence either
    # way. MFU/bandwidth headlines come from the live gauges the
    # trainer publishes from the SAME shared cost model.
    try:
      from tensor2robot_tpu.observability import roofline as roofline_lib
      from tensor2robot_tpu.parallel import hlo_analysis
      record = roofline_lib.build_record(
          families,
          hlo_analysis.op_cost_table(hlo_text),
          str((host or {}).get('device_kind', 'unknown')),
          step=int(step),
          cost_source='hlo_parse')
      for key, gauge in (('mfu', roofline_lib.MFU_GAUGE),
                         ('hbm_bw_util', roofline_lib.HBM_BW_GAUGE)):
        if record.get(key) is None and scalars.get(gauge):
          record[key] = scalars[gauge]
      report['roofline'] = record
    except Exception as e:  # noqa: BLE001 — attribution is best-effort
      warnings.append('roofline attribution failed: {}'.format(e))
  if families:
    try:
      report['collective_wait'] = split_collective_wait(
          families, hlo_collectives)
    except Exception as e:  # noqa: BLE001
      warnings.append('collective-wait split failed: {}'.format(e))
  try:
    report['attribution'] = attribute_goodput(
        report['goodput'], scalars)
  except Exception as e:  # noqa: BLE001
    warnings.append('goodput attribution failed: {}'.format(e))
  report['memory'] = {tag: value for tag, value in scalars.items()
                      if tag.startswith('memory/')}
  return report


def write_report(model_dir: str, step: int,
                 report: Dict[str, object]) -> str:
  """Atomically writes ``forensics/<step>.json``; returns the path."""
  directory = os.path.join(model_dir, FORENSICS_DIRNAME)
  os.makedirs(directory, exist_ok=True)
  path = os.path.join(directory, '{}.json'.format(int(step)))
  tmp = path + '.tmp'
  with open(tmp, 'w', encoding='utf-8') as f:
    json.dump(report, f, indent=2, sort_keys=True)
  os.replace(tmp, path)
  return path


def read_reports(model_dir: str) -> List[Tuple[int, Dict[str, object]]]:
  """All forensics reports under model_dir, sorted by step ascending.

  Unreadable/malformed report files are skipped (a doctor run must not
  die on one torn report), not raised.
  """
  directory = os.path.join(model_dir, FORENSICS_DIRNAME)
  out: List[Tuple[int, Dict[str, object]]] = []
  if not os.path.isdir(directory):
    return out
  for name in os.listdir(directory):
    base, ext = os.path.splitext(name)
    if ext != '.json':
      continue
    try:
      step = int(base)
      with open(os.path.join(directory, name), encoding='utf-8') as f:
        out.append((step, json.load(f)))
    except (ValueError, OSError):
      continue
  out.sort(key=lambda pair: pair[0])
  return out
