"""Collective-op accounting from compiled HLO: the scale-out evidence tool.

The reference ships communication as opaque library calls (NCCL/MPI via
TF's distributed runtime); what its graphs actually move per step is
invisible without vendor profilers. Here the communication schedule IS
the compiled program: GSPMD lowers sharding constraints to named HLO
collectives, so the per-step communication volume can be read — and
asserted — straight from the executable. Used by ``__graft_entry__``'s
multichip dryrun (each parallelism family asserts the collectives its
design predicts) and by ``docs/parallelism.md``'s pod-scale projection.

Counting rules:
  * Async pairs (``all-reduce-start``/``-done``) count ONCE, at start.
  * Bytes are the op's RESULT payload (tuple elements summed): for
    all-reduce that equals the reduced tensor size; for all-gather the
    gathered (output) size; for all-to-all the shuffled size;
    reduce-scatter the scattered (smaller) output. This is the
    device-local traffic entering/leaving the op, the quantity an ICI
    bandwidth model consumes; link-level traffic additionally depends on
    the algorithm (ring all-reduce moves ~2x(N-1)/N of the payload).
"""

from __future__ import annotations

import hashlib
import re
from typing import Dict

COLLECTIVE_KINDS = ('all-reduce', 'all-gather', 'all-to-all',
                    'collective-permute', 'reduce-scatter')

_DTYPE_BYTES = {
    'pred': 1, 's8': 1, 'u8': 1, 'f8e4m3fn': 1, 'f8e5m2': 1,
    's16': 2, 'u16': 2, 'f16': 2, 'bf16': 2,
    's32': 4, 'u32': 4, 'f32': 4,
    's64': 8, 'u64': 8, 'f64': 8, 'c64': 8,
    'c128': 16,
}

_SHAPE_RE = re.compile(r'([a-z]+[0-9a-z]*)\[([0-9,]*)\]')
_OP_RE = re.compile(
    r'=\s*(?P<shapes>[^=]*?)\s'
    r'(?P<kind>all-reduce|all-gather|all-to-all|collective-permute|'
    r'reduce-scatter)(?P<variant>-start)?\(')


def _shape_bytes(shapes_str: str) -> int:
  total = 0
  for dtype, dims in _SHAPE_RE.findall(shapes_str):
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
      continue  # token[], opaque[] etc.
    n = 1
    for dim in dims.split(','):
      if dim:
        n *= int(dim)
    total += n * size
  return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, int]]:
  """{kind: {'count': n, 'bytes': result_payload_bytes}} from HLO text.

  ``hlo_text``: ``jit(fn).lower(*args).compile().as_text()`` (post-SPMD —
  the collectives only exist after partitioning, so analyze the COMPILED
  module, not the lowered StableHLO).
  """
  stats = {kind: {'count': 0, 'bytes': 0} for kind in COLLECTIVE_KINDS}
  for line in hlo_text.splitlines():
    m = _OP_RE.search(line)
    if not m:
      continue
    kind = m.group('kind')
    nbytes = _shape_bytes(m.group('shapes'))
    if m.group('variant'):
      # Async `-start` ops return an (operands..., results...) tuple —
      # symmetric halves — where the sync lowering returns only the
      # result; halve so the payload is lowering-invariant.
      nbytes //= 2
    stats[kind]['count'] += 1
    stats[kind]['bytes'] += nbytes
  return {k: v for k, v in stats.items() if v['count']}


_INSTR_NAME_RE = re.compile(r'^\s*(?:ROOT\s+)?%(?P<name>[\w.-]+)\s*=')


def collective_ops(hlo_text: str):
  """Per-INSTRUCTION collective index: [{'name', 'kind', 'bytes'}].

  ``collective_stats`` aggregates by kind; this keeps the instruction
  names (``all-reduce.1`` — the same names the profiler's device line
  carries as op events), so forensics can join "which op burned the
  time" (xplane) with "what that op moves" (HLO) and name the gating
  collective of a straggler capture. Async ``-start`` ops keep the
  start name (that is where the device time lands) with the same
  halved-tuple byte rule as ``collective_stats``.
  """
  ops = []
  for line in hlo_text.splitlines():
    m = _OP_RE.search(line)
    if not m:
      continue
    name_match = _INSTR_NAME_RE.match(line)
    nbytes = _shape_bytes(m.group('shapes'))
    if m.group('variant'):
      nbytes //= 2
    ops.append({
        'name': name_match.group('name') if name_match else m.group('kind'),
        'kind': m.group('kind'),
        'bytes': nbytes,
    })
  return ops


def compiled_collective_stats(jitted_fn, *args, **kwargs):
  """Convenience: lower+compile a jitted fn and analyze its collectives."""
  compiled = jitted_fn.lower(*args, **kwargs).compile()
  return collective_stats(compiled.as_text())


def total_collective_bytes(stats: Dict[str, Dict[str, int]]) -> int:
  return sum(v['bytes'] for v in stats.values())


def format_stats(stats: Dict[str, Dict[str, int]]) -> str:
  if not stats:
    return 'no collectives'
  return ', '.join('{}: {}x / {:.2f} MiB'.format(
      kind, v['count'], v['bytes'] / 2**20) for kind, v in stats.items())


# ── Per-op cost model (roofline observatory) ─────────────────────────
#
# Derives FLOPs / HBM bytes per op family from post-optimization HLO
# text — the same artifact every CompiledArtifact persists — so roofline
# attribution works offline, on CPU, and on backends whose
# ``Compiled.cost_analysis()`` is absent or partial. Conventions match
# XLA's HloCostAnalysis so the two sources agree within tolerance:
#
#   * dot          2 x out_elems x contracted_extent
#   * convolution  2 x out_elems x window_elems x in_channels / groups
#   * elementwise  out_elems (one flop per output element)
#   * transcendental (tanh/exp/log/...) counts in a SEPARATE
#     'transcendentals' bucket, NOT flops — mirroring cost_analysis(),
#     whose 'flops' key excludes them.
#   * reduce       in_elems - out_elems
#   * fusion       sum over the called computation's instructions
#   * data movement (copy/reshape/broadcast/...) 0 flops
#
# Bytes are counted for ENTRY-computation instructions only, as
# operand bytes + output bytes (fusion internals live in registers/VMEM
# and never touch HBM); parameter/tuple/get-tuple-element/bitcast are
# free. On the toy matmul+elementwise program this reproduces
# cost_analysis()'s 'bytes accessed' exactly (dot 896 + fusion 256).

_TRANSCENDENTAL_OPS = frozenset((
    'atan2', 'cbrt', 'cosine', 'erf', 'exponential',
    'exponential-minus-one', 'log', 'log-plus-one', 'logistic', 'power',
    'rsqrt', 'sine', 'sqrt', 'tan', 'tanh',
))
_ELEMENTWISE_FLOP_OPS = frozenset((
    'abs', 'add', 'add-dependency', 'and', 'ceil', 'clamp', 'compare',
    'divide', 'floor', 'maximum', 'minimum', 'multiply', 'negate', 'not',
    'or', 'remainder', 'round-nearest-afz', 'round-nearest-even',
    'select', 'shift-left', 'shift-right-arithmetic',
    'shift-right-logical', 'sign', 'subtract', 'xor',
))
_FREE_BYTES_OPS = frozenset((
    'bitcast', 'get-tuple-element', 'parameter', 'tuple',
))

_COMPUTATION_HEADER_RE = re.compile(
    r'^\s*(?P<entry>ENTRY\s+)?%?(?P<name>[\w.-]+)\s*\([^)]*\)\s*->')
_OPCODE_RE = re.compile(r'(?P<opcode>[a-z][a-z0-9-]*)\(')
_CALLS_RE = re.compile(r'(?:calls|to_apply)=%?(?P<name>[\w.-]+)')
_CONTRACTING_RE = re.compile(r'lhs_contracting_dims=\{(?P<dims>[0-9,]*)\}')
_WINDOW_SIZE_RE = re.compile(r'window=\{[^}]*size=(?P<size>[0-9x]+)')
_DIM_LABELS_RE = re.compile(r'dim_labels=(?P<lhs>[\w?]+)_[\w?]+->')
_GROUPS_RE = re.compile(r'feature_group_count=(?P<n>\d+)')
_FAMILY_SUFFIX_RE = re.compile(r'\.\d+$')


def _shape_elems(shapes_str: str) -> int:
  total = 0
  for _, dims in _SHAPE_RE.findall(shapes_str):
    n = 1
    for dim in dims.split(','):
      if dim:
        n *= int(dim)
    total += n
  return total


def _shape_dims(shape_str: str):
  m = _SHAPE_RE.search(shape_str)
  if not m:
    return []
  return [int(d) for d in m.group(2).split(',') if d]


def _split_instruction(line: str):
  """(name, opcode, out_str, operand_str, attrs_str) or None."""
  m = _INSTR_NAME_RE.match(line)
  if not m:
    return None
  rest = line.split('=', 1)[1]
  op = _OPCODE_RE.search(rest)
  if not op:
    return None
  out_str = rest[:op.start()]
  depth = 0
  start = op.end() - 1
  end = len(rest)
  for i in range(start, len(rest)):
    if rest[i] == '(':
      depth += 1
    elif rest[i] == ')':
      depth -= 1
      if depth == 0:
        end = i
        break
  return (m.group('name'), op.group('opcode'), out_str,
          rest[start + 1:end], rest[end + 1:])


_OPERAND_NAME_RE = re.compile(r'%([\w.-]+)')


def _parse_computations(hlo_text: str):
  """{computation_name: [instruction tuples]}, plus the ENTRY name.

  The installed XLA prints operands by NAME only (``dot(%a.1, %b.1)``),
  so each instruction's operand string is rewritten to the operands'
  output shapes from the computation's own definitions — HLO text is in
  def-before-use order — which is what the shape arithmetic below reads.
  """
  computations: Dict[str, list] = {}
  entry_name = None
  current = None
  shapes: Dict[str, str] = {}
  for line in hlo_text.splitlines():
    stripped = line.strip()
    if current is None:
      if stripped.endswith('{'):
        header = _COMPUTATION_HEADER_RE.match(stripped)
        if header:
          current = header.group('name')
          computations[current] = []
          shapes = {}
          if header.group('entry'):
            entry_name = current
      continue
    if stripped.startswith('}'):
      current = None
      continue
    instr = _split_instruction(line)
    if instr:
      name, opcode, out_str, operand_str, attrs = instr
      shapes[name] = out_str
      if not _SHAPE_RE.search(operand_str):
        operand_str = ', '.join(
            shapes.get(operand, '')
            for operand in _OPERAND_NAME_RE.findall(operand_str))
      computations[current].append(
          (name, opcode, out_str, operand_str, attrs))
  return computations, entry_name


def _instr_flops(instr, computations, memo):
  """(flops, transcendentals) for one parsed instruction."""
  _, opcode, out_str, operand_str, attrs = instr
  out_elems = _shape_elems(out_str)
  if opcode == 'dot':
    lhs_dims = _shape_dims(operand_str)
    contracted = 1
    m = _CONTRACTING_RE.search(attrs)
    if m and lhs_dims:
      for d in m.group('dims').split(','):
        if d and int(d) < len(lhs_dims):
          contracted *= lhs_dims[int(d)]
    return 2 * out_elems * contracted, 0
  if opcode == 'convolution':
    window = 1
    m = _WINDOW_SIZE_RE.search(attrs)
    if m:
      for s in m.group('size').split('x'):
        window *= int(s)
    in_channels = 1
    labels = _DIM_LABELS_RE.search(attrs)
    lhs_dims = _shape_dims(operand_str)
    if labels and 'f' in labels.group('lhs'):
      idx = labels.group('lhs').index('f')
      if idx < len(lhs_dims):
        in_channels = lhs_dims[idx]
    groups = 1
    m = _GROUPS_RE.search(attrs)
    if m:
      groups = max(int(m.group('n')), 1)
    return 2 * out_elems * window * in_channels // groups, 0
  if opcode == 'fusion':
    m = _CALLS_RE.search(attrs)
    if m:
      return _computation_flops(m.group('name'), computations, memo)
    return 0, 0
  if opcode in ('reduce', 'reduce-window'):
    in_elems = _shape_elems(operand_str)
    return max(in_elems - out_elems, 0), 0
  if opcode in _ELEMENTWISE_FLOP_OPS:
    return out_elems, 0
  if opcode in _TRANSCENDENTAL_OPS:
    return 0, out_elems
  return 0, 0


def _computation_flops(name, computations, memo):
  if name in memo:
    return memo[name]
  memo[name] = (0, 0)  # cycle guard
  flops = transcendentals = 0
  for instr in computations.get(name, ()):
    f, t = _instr_flops(instr, computations, memo)
    flops += f
    transcendentals += t
  memo[name] = (flops, transcendentals)
  return memo[name]


def op_cost_table(hlo_text: str) -> Dict[str, Dict[str, float]]:
  """{op family: {'flops', 'bytes', 'transcendentals', 'count'}}.

  Families carry the same naming as ``utils/xplane.op_families`` device
  events — ``'%' + instruction name with the trailing .N stripped`` — so
  a forensics capture's measured ms joins this table directly. Only the
  ENTRY computation's instructions appear (those are the ops the device
  line times); fusions fold their called computation's flops into the
  fusion family.
  """
  computations, entry = _parse_computations(hlo_text)
  if entry is None:
    return {}
  memo: Dict[str, tuple] = {}
  table: Dict[str, Dict[str, float]] = {}
  for instr in computations[entry]:
    name, opcode, out_str, operand_str, _ = instr
    flops, transcendentals = _instr_flops(instr, computations, memo)
    nbytes = 0
    if opcode not in _FREE_BYTES_OPS:
      nbytes = _shape_bytes(out_str) + _shape_bytes(operand_str)
    family = '%' + _FAMILY_SUFFIX_RE.sub('', name)
    row = table.setdefault(family, {
        'flops': 0.0, 'bytes': 0.0, 'transcendentals': 0.0, 'count': 0})
    row['flops'] += flops
    row['bytes'] += nbytes
    row['transcendentals'] += transcendentals
    row['count'] += 1
  return table


def hlo_program_cost(hlo_text: str) -> Dict[str, float]:
  """Program totals from HLO text: {'flops', 'bytes', 'transcendentals'}."""
  totals = {'flops': 0.0, 'bytes': 0.0, 'transcendentals': 0.0}
  for row in op_cost_table(hlo_text).values():
    totals['flops'] += row['flops']
    totals['bytes'] += row['bytes']
    totals['transcendentals'] += row['transcendentals']
  return totals


def program_cost(compiled_or_text) -> Dict[str, object]:
  """THE shared FLOPs/bytes accounting helper (trainer, roofline).

  Accepts a compiled executable or its ``as_text()`` string. Prefers the
  backend's own ``cost_analysis()`` (exact, fusion-aware); falls back to
  the HLO shape parse above when the method is missing, raises, or
  reports non-positive flops (some backends return properties without
  compute counts). Returns ``{'flops', 'bytes', 'transcendentals',
  'source'}`` with source in ('cost_analysis', 'hlo_parse') so callers
  can surface which model produced the number.
  """
  text = compiled_or_text if isinstance(compiled_or_text, str) else None
  if text is None:
    try:
      props = compiled_or_text.cost_analysis()
      if isinstance(props, (list, tuple)):
        props = props[0]
      flops = float(props.get('flops', -1.0))
      nbytes = float(props.get('bytes accessed', -1.0))
      if flops > 0 and nbytes > 0:
        return {
            'flops': flops,
            'bytes': nbytes,
            'transcendentals': float(props.get('transcendentals', 0.0)),
            'source': 'cost_analysis',
        }
    except Exception:  # noqa: BLE001 - fall through to the HLO parse
      pass
    text = compiled_or_text.as_text()
  totals = hlo_program_cost(text)
  totals['source'] = 'hlo_parse'
  return totals


_MODULE_HEADER_RE = re.compile(r'^HloModule\s+\S+', re.MULTILINE)


def program_fingerprint(compiled_or_text) -> str:
  """Short stable sha1 of a compiled program's post-optimization HLO.

  Accepts a compiled executable (``jit(f).lower(...).compile()``) or its
  ``as_text()`` string. Comment lines and the HloModule header (which
  carries a per-compile module id) are stripped so the digest depends
  only on the optimized program itself. The compile-config autotuner
  records this per candidate: two candidates with the SAME fingerprint
  compiled to the SAME program, so their timing delta is noise and the
  flag was a no-op for this workload — measured, not assumed.
  """
  text = compiled_or_text
  if not isinstance(text, str):
    text = compiled_or_text.as_text()
  lines = [line.strip() for line in text.splitlines()
           if line.strip() and not line.strip().startswith('//')]
  body = _MODULE_HEADER_RE.sub('HloModule <normalized>', '\n'.join(lines))
  return hashlib.sha1(body.encode('utf-8')).hexdigest()[:16]
