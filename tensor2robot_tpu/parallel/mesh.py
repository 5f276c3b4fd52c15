"""Device-mesh construction: ICI-major layouts, DCN-aware multi-slice meshes.

The reference delegates all distribution to TF1 (SURVEY.md §2.9): TPUEstimator
replication + CrossShardOptimizer all-reduce. Here the mesh IS the
communication backend: axes declared once, shardings annotated on arrays, and
XLA inserts psum/all-gather/reduce-scatter collectives over ICI (intra-slice)
or DCN (inter-slice) based on the mesh layout.

Axis convention (used across the framework):
  * 'data'  — batch (data parallel); gradients psum here.
  * 'fsdp'  — optional parameter sharding axis (zero-style), ICI-local.
  * 'model' — tensor parallelism for layers that opt in.
  * 'expert' — expert parallelism for MoE layers (layers/moe.py): the
    stacked expert params and the [E, ...] dispatch activations shard
    here; GSPMD lowers the dispatch/combine einsums to all-to-alls.
Sequence parallelism ('sp') reuses the 'data' axis via
parallel.ring_attention — sequence blocks ride the same ring.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from tensor2robot_tpu.reliability.logutil import log_warning

DATA_AXIS = 'data'
FSDP_AXIS = 'fsdp'
MODEL_AXIS = 'model'
EXPERT_AXIS = 'expert'
PIPE_AXIS = 'pipe'
DEFAULT_AXES = (DATA_AXIS, FSDP_AXIS, MODEL_AXIS, EXPERT_AXIS, PIPE_AXIS)


def create_mesh(axis_sizes: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence] = None,
                allow_split_physical_axes: bool = False) -> Mesh:
  """Builds a Mesh with the framework's axis names.

  ``axis_sizes`` maps axis name -> size; one axis may be -1 (filled with the
  remaining devices). Default: all devices on 'data'. Device order comes from
  ``mesh_utils.create_device_mesh`` so that the innermost axes land on
  physically adjacent chips (ICI neighbors) — keeping model/fsdp collectives
  on the fastest links.
  """
  devices = list(devices if devices is not None else jax.devices())
  n = len(devices)
  axis_sizes = dict(axis_sizes or {DATA_AXIS: -1})
  for name in DEFAULT_AXES:
    axis_sizes.setdefault(name, 1)
  unknown = [k for k, v in axis_sizes.items() if v == -1]
  if len(unknown) > 1:
    raise ValueError('At most one axis may be -1; got {}.'.format(unknown))
  known = int(np.prod([v for v in axis_sizes.values() if v != -1]))
  if unknown:
    if n % known:
      raise ValueError(
          'Cannot infer {}: {} devices not divisible by {}.'.format(
              unknown[0], n, known))
    axis_sizes[unknown[0]] = n // known
  total = int(np.prod(list(axis_sizes.values())))
  if total != n:
    raise ValueError(
        'Mesh axes {} require {} devices but {} are available.'.format(
            axis_sizes, total, n))
  # Order axes: data outermost, model innermost (fastest links).
  names = [a for a in DEFAULT_AXES if a in axis_sizes]
  names += [a for a in axis_sizes if a not in names]
  shape = [axis_sizes[a] for a in names]
  try:
    device_array = mesh_utils.create_device_mesh(
        shape, devices=devices,
        allow_split_physical_axes=allow_split_physical_axes)
  except (ValueError, AssertionError) as e:
    # Never silent: id order can put an inner axis across slow links.
    log_warning(
        'create_device_mesh could not map axes %s onto the topology (%s); '
        'using jax.devices() id order: %s', dict(zip(names, shape)), e,
        [d.id for d in devices])
    device_array = np.asarray(devices).reshape(shape)
  return Mesh(device_array, tuple(names))


def create_hybrid_mesh(ici_axis_sizes: Dict[str, int],
                       dcn_axis_sizes: Dict[str, int]) -> Mesh:
  """Multi-slice mesh: DCN axes outermost, ICI axes innermost.

  E.g. 4 v5e slices of 64 chips, data-parallel across slices, fsdp inside:
  ``create_hybrid_mesh({'fsdp': 64}, {'data': 4})`` — gradient psums then
  decompose into an ICI reduce-scatter + small DCN all-reduce, which is the
  layout that keeps the slow DCN hops to O(params/slice) bytes.
  """
  names = list(dcn_axis_sizes) + [a for a in ici_axis_sizes
                                  if a not in dcn_axis_sizes]
  ici_shape = [ici_axis_sizes.get(a, 1) for a in names]
  dcn_shape = [dcn_axis_sizes.get(a, 1) for a in names]
  device_array = mesh_utils.create_hybrid_device_mesh(
      ici_shape, dcn_shape)
  return Mesh(device_array, tuple(names))
