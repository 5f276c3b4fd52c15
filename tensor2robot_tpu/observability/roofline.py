"""Roofline observatory: live MFU ledger and per-op-family attribution.

The forensics top-op tables rank op families by measured milliseconds
only — enough to say WHERE the step time goes, not whether a family is
compute- or memory-bound, nor how much a hand-fused kernel could
recover. This module closes that gap with the standard roofline model
(arithmetic intensity = flops/bytes vs the device ridge point =
peak_flops/peak_bandwidth):

  * ``build_record`` joins a capture's measured op-family ms (from
    `utils/xplane`) with the per-family FLOPs/HBM-bytes cost table
    parsed from the SAME program's post-opt HLO
    (`parallel/hlo_analysis.op_cost_table`) and emits a
    ``t2r.roofline.v1`` record: ranked families with intensity, bound
    class (compute / memory / ragged), % of device peak, and roofline
    headroom — measured ms minus the roofline-bound ms, i.e. the
    predicted win from fusing that family to the roofline.
  * ``publish_perf_gauges`` makes MFU a LIVE signal: the trainer calls
    it every log window and the ``perf/mfu`` / ``perf/hbm_bw_util``
    gauges feed TensorBoard, telemetry.jsonl, and the watchdog's
    ``mfu_regression`` anomaly.
  * ``PEAKS`` is the small per-``device_kind`` peaks table (dense bf16
    FLOP/s + HBM GB/s). Unknown kinds — CPU above all — degrade to
    ``mode='intensity-only'``: intensities still rank and classify by
    ratio ordering, but % peak / headroom / MFU are withheld rather
    than fabricated from a made-up peak.

Everything here is stdlib + `parallel/hlo_analysis` (pure re/hashlib) —
importable jax-free, so ``doctor`` and ``bin/check_roofline_doctor``
can render roofline verdicts offline.

Accounting invariant: the families table always sum-reconciles with the
program totals — cost-table families that no measured event joined
(fused away, renamed by the backend, or a host-executor capture whose
event names never match) aggregate into one ``unattributed`` pseudo-row
(ms=None), so ``sum(row.flops) == flops_per_step`` by construction and
a reader can SEE how much of the program the measurement explained.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

ROOFLINE_SCHEMA = 't2r.roofline.v1'

# Registry gauge names the trainer publishes every log window.
MFU_GAUGE = 'perf/mfu'
HBM_BW_GAUGE = 'perf/hbm_bw_util'

UNATTRIBUTED = 'unattributed'

# (device_kind substring, peak dense bf16 FLOP/s, peak HBM GB/s).
# Matched case-insensitively, first hit wins — keep more specific
# substrings (v5p) ahead of shorter ones that would shadow them.
# Sources: public TPU spec sheets; these are DENSE peaks, so MFU here is
# comparable with the training-at-scale literature's convention.
PEAKS: Tuple[Tuple[str, float, float], ...] = (
    ('v6e', 918e12, 1640.0),
    ('trillium', 918e12, 1640.0),
    ('v5p', 459e12, 2765.0),
    ('v5 lite', 197e12, 819.0),
    ('v5litepod', 197e12, 819.0),
    ('v5e', 197e12, 819.0),
    ('v4', 275e12, 1228.0),
    ('v3', 123e12, 900.0),
    ('v2', 46e12, 700.0),
)

# Bound-class hysteresis band around the ridge point: families within
# +/-25% of the ridge are 'ragged' — close enough that fusing them
# flips which wall they hit, so neither label would be honest.
_RAGGED_BAND = 0.25

_FAMILY_SUFFIX_RE = re.compile(r'\.\d+$')


def normalize_family(name: str) -> str:
  """Canonical op-family key used on BOTH sides of the ms<->cost join.

  Measured names (xplane event metadata, host-executor thunk names) and
  HLO instruction names differ in '%' prefix and '.N' uniquifier
  suffixes; fold both to ``'%' + bare name`` so they join.
  """
  bare = str(name).split(' = ')[0].strip().lstrip('%')
  return '%' + _FAMILY_SUFFIX_RE.sub('', bare)


def device_peaks(device_kind: str) -> Optional[Tuple[float, float]]:
  """(peak FLOP/s, peak HBM bytes/s) for a device kind, else None.

  None — the CPU case — selects intensity-only mode everywhere
  downstream: no entry is ever guessed.
  """
  kind = str(device_kind or '').lower()
  for substr, flops, gbps in PEAKS:
    if substr in kind:
      return flops, gbps * 1e9
  return None


def ridge_intensity(peak_flops: float, peak_bw: float) -> float:
  """Flops/byte at which a kernel leaves the bandwidth roof."""
  return peak_flops / peak_bw if peak_bw else 0.0


def classify_bound(intensity: Optional[float], ridge: float) -> Optional[str]:
  """'compute' | 'memory' | 'ragged' against a device ridge point."""
  if intensity is None or ridge <= 0:
    return None
  if intensity > ridge * (1.0 + _RAGGED_BAND):
    return 'compute'
  if intensity < ridge * (1.0 - _RAGGED_BAND):
    return 'memory'
  return 'ragged'


def mfu(flops_per_step: float, step_time_s: float, peak_flops: float,
        n_chips: int = 1) -> float:
  """Model-flops utilization: achieved FLOP/s over the installed peak."""
  if step_time_s <= 0 or peak_flops <= 0 or n_chips <= 0:
    return 0.0
  return flops_per_step / step_time_s / (peak_flops * n_chips)


def build_record(families: Sequence[Tuple[str, float]],
                 cost_table: Dict[str, Dict[str, float]],
                 device_kind: str,
                 *,
                 step: Optional[int] = None,
                 step_time_s: Optional[float] = None,
                 totals: Optional[Dict[str, float]] = None,
                 cost_source: str = 'hlo_parse',
                 top_k: int = 15) -> Dict[str, object]:
  """The ``t2r.roofline.v1`` record for one forensics capture.

  Args:
    families: ``[(name, ms_per_step)]`` measured device attribution
      (``utils/xplane.op_families`` order — or the host-executor
      fallback; names are normalized before joining).
    cost_table: ``parallel/hlo_analysis.op_cost_table(hlo_text)`` of
      the SAME program the capture timed.
    device_kind: ``signals.host_identity()['device_kind']``.
    step: trainer step the capture closed at.
    step_time_s: measured wall seconds per step — enables MFU and the
      bandwidth-utilization headline when peaks are known.
    totals: program totals ``{'flops','bytes',...}`` from the shared
      cost helper; defaults to summing ``cost_table`` (the two agree
      exactly when both come from the HLO parse — passing the
      ``cost_analysis()`` totals here keeps the record anchored to the
      backend's own count while the table explains it).
    cost_source: provenance label ('cost_analysis' | 'hlo_parse').
    top_k: measured rows kept (the tail folds into ``unattributed``).

  Never raises on ragged input — unjoined measurements get cost zeros,
  unjoined costs fold into ``unattributed`` — so forensics can call it
  inside the trainer's capture path.
  """
  peaks = device_peaks(device_kind)
  mode = 'roofline' if peaks else 'intensity-only'
  table_totals = {'flops': 0.0, 'bytes': 0.0}
  for row in cost_table.values():
    table_totals['flops'] += float(row.get('flops', 0.0))
    table_totals['bytes'] += float(row.get('bytes', 0.0))
  if totals is None:
    totals = table_totals
  flops_per_step = float(totals.get('flops', 0.0))
  bytes_per_step = float(totals.get('bytes', 0.0))

  costs = {}
  for name, row in cost_table.items():
    key = normalize_family(name)
    agg = costs.setdefault(key, {'flops': 0.0, 'bytes': 0.0})
    agg['flops'] += float(row.get('flops', 0.0))
    agg['bytes'] += float(row.get('bytes', 0.0))

  peak_flops, peak_bw = peaks if peaks else (0.0, 0.0)
  ridge = ridge_intensity(peak_flops, peak_bw) if peaks else 0.0

  def _row(family, ms, flops, nbytes):
    intensity = (flops / nbytes) if nbytes else None
    row = {
        'family': family,
        'ms': None if ms is None else round(float(ms), 6),
        'flops': flops,
        'bytes': nbytes,
        'intensity': None if intensity is None else round(intensity, 4),
        'bound': classify_bound(intensity, ridge) if peaks else None,
        'pct_peak': None,
        'roofline_ms': None,
        'headroom_ms': None,
    }
    if peaks:
      roofline_s = max(flops / peak_flops if peak_flops else 0.0,
                       nbytes / peak_bw if peak_bw else 0.0)
      row['roofline_ms'] = round(roofline_s * 1e3, 6)
      if ms:
        row['headroom_ms'] = round(float(ms) - roofline_s * 1e3, 6)
        achieved = flops / (float(ms) / 1e3) if ms else 0.0
        row['pct_peak'] = round(achieved / peak_flops, 6) if peak_flops else None
    return row

  # Aggregate measured ms BY family first: a capture times each
  # uniquified instruction (%dot.1, %dot.5, ...) separately, and a
  # per-event join would hand every event the whole family's cost —
  # double counting that breaks the sum-reconciliation invariant.
  measured: Dict[str, float] = {}
  for name, ms in families:
    key = normalize_family(name)
    measured[key] = measured.get(key, 0.0) + float(ms)

  rows: List[Dict[str, object]] = []
  matched = set()
  ranked = sorted(measured.items(), key=lambda kv: -kv[1])
  folded_ms = 0.0
  for key, ms in ranked:
    cost = costs.get(key)
    if len(rows) >= top_k:
      # Beyond-top_k tail: its ms AND its cost both fold into the
      # unattributed row (marking it matched without moving the cost
      # would silently drop flops from the table).
      folded_ms += ms
      continue
    if cost is not None:
      matched.add(key)
      rows.append(_row(key, ms, cost['flops'], cost['bytes']))
    else:
      rows.append(_row(key, ms, 0.0, 0.0))

  # Everything the measurement didn't explain — costs with no event
  # (plus beyond-top_k tails) — lands in ONE reconciling pseudo-row.
  rest_flops = sum(c['flops'] for k, c in costs.items() if k not in matched)
  rest_bytes = sum(c['bytes'] for k, c in costs.items() if k not in matched)
  # Anchor the reconciliation to the record's own totals: when `totals`
  # came from cost_analysis() the parse-vs-backend delta is real program
  # cost the table must not drop.
  rest_flops += max(flops_per_step - table_totals['flops'], 0.0)
  rest_bytes += max(bytes_per_step - table_totals['bytes'], 0.0)
  if rest_flops or rest_bytes or folded_ms:
    rows.append(_row(UNATTRIBUTED, folded_ms if folded_ms else None,
                     rest_flops, rest_bytes))

  gating = None
  best_headroom = 0.0
  for row in rows:
    if row['family'] == UNATTRIBUTED or row['bound'] != 'memory':
      continue
    headroom = row['headroom_ms'] if row['headroom_ms'] is not None else 0.0
    score = headroom if headroom > 0 else (row['ms'] or 0.0) * 1e-6
    if gating is None or score > best_headroom:
      gating = row['family']
      best_headroom = score

  record = {
      'schema': ROOFLINE_SCHEMA,
      'step': step,
      'device_kind': device_kind,
      'mode': mode,
      'cost_source': cost_source,
      'flops_per_step': flops_per_step,
      'bytes_per_step': bytes_per_step,
      'arithmetic_intensity': round(flops_per_step / bytes_per_step, 4)
                              if bytes_per_step else None,
      'peak_flops': peak_flops if peaks else None,
      'peak_hbm_gbps': (peak_bw / 1e9) if peaks else None,
      'ridge_intensity': round(ridge, 4) if peaks else None,
      'step_time_s': step_time_s,
      'mfu': None,
      'hbm_bw_util': None,
      'families': rows,
      'gating_memory_bound_family': gating,
  }
  if peaks and step_time_s:
    record['mfu'] = round(mfu(flops_per_step, step_time_s, peak_flops), 6)
    record['hbm_bw_util'] = round(
        bytes_per_step / step_time_s / peak_bw, 6) if peak_bw else None
  return record


def static_gating_family(cost_table: Dict[str, Dict[str, float]],
                         device_kind: str) -> Optional[str]:
  """Memory-bound family with the largest roofline-bound ms — from the
  cost table ALONE, no measurement: the family whose best-case
  (roofline) time is the biggest memory-bound share of the step, i.e.
  where a fused kernel has the most predicted room. None when the
  device kind has no peaks entry (intensity alone cannot place the
  ridge) or nothing is memory-bound.
  """
  peaks = device_peaks(device_kind)
  if not peaks:
    return None
  peak_flops, peak_bw = peaks
  ridge = ridge_intensity(peak_flops, peak_bw)
  best = None
  best_s = 0.0
  for name, row in cost_table.items():
    flops = float(row.get('flops', 0.0))
    nbytes = float(row.get('bytes', 0.0))
    intensity = (flops / nbytes) if nbytes else None
    if classify_bound(intensity, ridge) != 'memory':
      continue
    bound_s = max(flops / peak_flops if peak_flops else 0.0,
                  nbytes / peak_bw if peak_bw else 0.0)
    if bound_s > best_s:
      best = normalize_family(name)
      best_s = bound_s
  return best


def publish_perf_gauges(registry, flops_per_step: float,
                        bytes_per_step: float, step_time_s: float,
                        device_kind: str,
                        n_chips: int = 1) -> Optional[Tuple[float, float]]:
  """Set ``perf/mfu`` + ``perf/hbm_bw_util`` gauges for one log window.

  Returns ``(mfu, hbm_bw_util)`` when the device kind has a peaks entry,
  else None WITHOUT touching the gauges — a zero would read as "0% MFU"
  on hosts where the truthful statement is "no peak known" (CPU), and
  the watchdog treats an absent/non-positive gauge as not-applicable.
  """
  peaks = device_peaks(device_kind)
  if not peaks or step_time_s <= 0:
    return None
  peak_flops, peak_bw = peaks
  value = mfu(flops_per_step, step_time_s, peak_flops, n_chips=1)
  bw_util = (bytes_per_step / step_time_s / peak_bw) if peak_bw else 0.0
  registry.gauge(MFU_GAUGE).set(value)
  registry.gauge(HBM_BW_GAUGE).set(bw_util)
  return value, bw_util


def telemetry_payload(record: Dict[str, object],
                      top_k: int = 5) -> Dict[str, object]:
  """Compact ``kind='roofline'`` telemetry.jsonl payload from a record.

  Full records live in the forensics report; the jsonl line keeps the
  headline + the top families so ``t2r_telemetry tail``/``summarize``
  and doctor stay useful without opening report files.
  """
  families = [
      {'family': row.get('family'), 'ms': row.get('ms'),
       'intensity': row.get('intensity'), 'bound': row.get('bound'),
       'headroom_ms': row.get('headroom_ms')}
      for row in list(record.get('families') or [])[:top_k]
  ]
  return {
      'schema': record.get('schema', ROOFLINE_SCHEMA),
      'mode': record.get('mode'),
      'device_kind': record.get('device_kind'),
      'mfu': record.get('mfu'),
      'hbm_bw_util': record.get('hbm_bw_util'),
      'flops_per_step': record.get('flops_per_step'),
      'bytes_per_step': record.get('bytes_per_step'),
      'arithmetic_intensity': record.get('arithmetic_intensity'),
      'gating_memory_bound_family': record.get('gating_memory_bound_family'),
      'families': families,
  }
