"""The bounded compile-config search space.

A candidate is one :class:`CompileConfig`: a set of per-compile XLA
options (shipped through ``lowered.compile(compiler_options=...)`` — no
process-global ``XLA_FLAGS`` mutation, so candidates are hermetic within
one process) plus optional model-layer overrides (conv
``dimension_numbers``/layout variants, e.g. Grasping44's
``conv_variant``/``space_to_depth`` network kwargs) and a donation
toggle for harnesses that rebuild the step per candidate.

The flag sets are CURATED, not exhaustive: the sweep is meant to run in
minutes on one chip, so each candidate must have a mechanism story
(scheduler, vmem budget, fusion aggressiveness, layout). Flags that the
local jaxlib does not recognize fail that candidate's compile with
INVALID_ARGUMENT — the autotuner records the failure and moves on, so a
curated list can safely name flags newer (or older) than the installed
toolchain.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

__all__ = ['CompileConfig', 'candidate_configs', 'BASELINE_CONFIG_ID']

BASELINE_CONFIG_ID = 'baseline'


@dataclasses.dataclass(frozen=True)
class CompileConfig:
  """One sweep candidate / one cached winner.

  Attributes:
    config_id: short stable identifier ('vmem-96m', 'latency-sched', ...).
      Forensics reports carry it verbatim.
    compiler_options: per-compile XLA options. Values keep their native
      python types (bool/int/str) — the PJRT layer rejects stringified
      bools ("'true' is not a valid bool value").
    model_overrides: model-constructor kwargs for layout variants (e.g.
      {'conv_variant': 'nchw'} or {'space_to_depth': True} for
      Grasping44's network_kwargs). Applied by harnesses that rebuild
      the model per candidate; the trainer hook applies
      compiler_options only — a layout override changes the program, so
      it must come in through the model, not the compile.
    donate: whether the candidate step donates its state argument.
    notes: one-line mechanism story, for the sweep record.
  """

  config_id: str
  compiler_options: Dict[str, Any] = dataclasses.field(default_factory=dict)
  model_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
  donate: bool = True
  notes: str = ''

  def to_dict(self) -> Dict[str, Any]:
    return dataclasses.asdict(self)

  @classmethod
  def from_dict(cls, data: Dict[str, Any]) -> 'CompileConfig':
    known = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in dict(data).items() if k in known})


def _tpu_candidates(include_layouts: bool) -> List[CompileConfig]:
  """The curated TPU set: scheduler / vmem / fusion / layout levers.

  Sources: the pjit-era tuning literature (arxiv 2204.06514 §4: compiler
  scheduling + fusion flags moved their MFU), public XLA:TPU flag surveys
  (t5x/maxtext launch configs), and this repo's own breakdown
  (PERF.md section 5): the conv fusions gate the step, so the
  plausible levers are vmem budget (deeper conv pipelining), the
  latency-hiding scheduler (dispatch/overlap), and fusion aggressiveness
  around the convs.
  """
  out = [
      CompileConfig(BASELINE_CONFIG_ID, notes='stock compile, no options'),
      CompileConfig(
          'latency-sched',
          compiler_options={'xla_tpu_enable_latency_hiding_scheduler': True},
          notes='latency-hiding scheduler: overlap copies with compute'),
      CompileConfig(
          'vmem-64m',
          compiler_options={'xla_tpu_scoped_vmem_limit_kib': 65536},
          notes='raise scoped vmem budget (deeper conv operand pipelining)'),
      CompileConfig(
          'vmem-96m',
          compiler_options={'xla_tpu_scoped_vmem_limit_kib': 98304},
          notes='vmem budget, upper point'),
      CompileConfig(
          'no-multilevel-fusion',
          compiler_options={'xla_tpu_enable_multi_level_nested_loop_fusion':
                            False},
          notes='disable nested-loop fusion: isolates the conv emitter'),
      CompileConfig(
          'async-collectives',
          compiler_options={
              'xla_tpu_enable_async_collective_fusion': True,
              'xla_tpu_enable_async_collective_fusion_fuse_all_gather': True,
          },
          notes='async collective fusion (multi-chip steps only; single-'
                'chip programs compile identically)'),
      CompileConfig(
          'flm-bounds',
          compiler_options={'xla_tpu_licm_size_inflation_ratio': 1},
          notes='pin LICM size inflation: smaller loop bodies, less vmem '
                'pressure around the crop loop'),
  ]
  if include_layouts:
    out.extend([
        CompileConfig('conv-nchw',
                      model_overrides={'conv_variant': 'nchw'},
                      notes='body convs via NCHW/OIHW dimension_numbers '
                            '(layout-assignment alternative)'),
        CompileConfig('stem-space-to-depth',
                      model_overrides={'space_to_depth': True},
                      notes='stem conv as 3x3/1 on the 2x2 packed grid '
                            '(re-tried per-flag-set: a scheduler change '
                            'can flip the round-2 verdict)'),
    ])
  return out


def _cpu_candidates(include_layouts: bool) -> List[CompileConfig]:
  """CPU set: small but real — exists so the whole sweep->cache->apply
  path runs (and is tested) without a TPU attached."""
  out = [
      CompileConfig(BASELINE_CONFIG_ID, notes='stock compile, no options'),
      CompileConfig(
          'fast-min-max',
          compiler_options={'xla_cpu_enable_fast_min_max': True},
          notes='non-strict NaN semantics in min/max lowering'),
      CompileConfig(
          'no-fast-min-max',
          compiler_options={'xla_cpu_enable_fast_min_max': False},
          notes='strict min/max lowering'),
  ]
  if include_layouts:
    out.append(CompileConfig('conv-nchw',
                             model_overrides={'conv_variant': 'nchw'},
                             notes='NCHW/OIHW body convs'))
  return out


def candidate_configs(backend: Optional[str] = None,
                      include_layouts: bool = True
                      ) -> List[CompileConfig]:
  """The curated candidate list for ``backend`` ('tpu'/'cpu'/'gpu').

  ``backend`` defaults to the live jax backend. The first entry is always
  the baseline (empty) config; ``include_layouts=False`` drops the
  model-override candidates for harnesses that cannot rebuild the model.
  """
  if backend is None:
    from tensor2robot_tpu import runtime
    backend = 'tpu' if runtime.on_tpu() else 'cpu'
  if backend.lower() == 'tpu':
    return _tpu_candidates(include_layouts)
  return _cpu_candidates(include_layouts)
