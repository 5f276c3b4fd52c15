"""Fused per-example crop + dtype convert as one Pallas HBM pass.

The reference crops with ``tf.image.crop_to_bounding_box`` on host CPU
(ref preprocessors/image_transformations.py:110 ``crop_image``); our
device-side equivalent (`preprocessors/image_transformations.py`
``crop_images``) vmaps ``lax.dynamic_slice`` over the batch, which XLA
lowers to a sequential while-loop over examples, followed by a separate
uint8->float convert + conv-input relayout.

This kernel does the whole thing in one pipelined pass: each grid step
pulls one uint8 frame into VMEM, rotates rows/lanes by the example's
(y, x) crop offset (``pltpu.roll`` — the only Mosaic-expressible dynamic
shift on the lane axis), keeps the leading [th, tw*C] window, converts to
float and scales. HBM traffic is the uint8 read + float write of the crop
window, with no sequential batch loop and no post-hoc convert pass.

Measured (chained on-device timing, [64, 512, 640, 3] u8 -> [64, 472,
472, 3] f32, v5e): 3.3 ms vs 24.5 ms for the XLA dynamic-slice path in
isolation — but ~3% SLOWER inside the full batch-512 QT-Opt train step
(183.6 ms f32-out / 180.3 ms bf16-out vs 178.4 ms), where XLA fuses the
convert into neighboring ops and the opaque pallas_call re-introduces a
fusion barrier + conv1-input relayout. The QT-Opt preprocessor therefore
defaults this OFF (ROADMAP D3); the kernel stays as the measured record
and for pipelines whose crop is not adjacent to a large fusible program.

Mosaic constraints that shaped the kernel (jax 0.9):

* dynamic ``pltpu.roll`` shifts must be NON-NEGATIVE — negative dynamic
  shifts are not rejected but silently wrap at 256, so left-rolls are
  expressed as right-rolls by ``size - shift``;
* there is no direct uint8->float cast; the convert routes through int32;
* the (W, C) minor dims are viewed as one W*C lane axis so C=3 frames use
  full vector lanes instead of 3/128 of them.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu import runtime


def supported(image_shape: Tuple[int, ...]) -> bool:
  """True if the fused kernel handles [B, H, W, C] efficiently.

  Conservative: full-lane rows (W*C % 128 == 0) and sublane-aligned
  heights (H % 8 == 0). Anything else falls back to the XLA path.
  """
  if len(image_shape) != 4:
    return False
  _, h, w, c = image_shape
  return (w * c) % 128 == 0 and h % 8 == 0


def _crop_kernel(offs_ref, img_ref, out_ref, *, h: int, wc: int, th: int,
                 twc: int, denom: float, out_dtype):
  b = pl.program_id(0)
  oy = offs_ref[b, 0]
  x = img_ref[0]  # [H, W*C] uint8
  x = x.astype(jnp.int32)
  # Row crop first (cheaper: rotates u32 sublanes before the lane rotate).
  x = pltpu.roll(x, shift=(h - oy) % h, axis=0)
  x = x[:th, :]
  # Column crop: left-roll by ox*C lanes, expressed non-negatively.
  x = pltpu.roll(x, shift=(wc - offs_ref[b, 1]) % wc, axis=1)
  x = x[:, :twc]
  # Divide (not multiply-by-reciprocal) for bit-parity with the XLA
  # path's ``image / 255.0``.
  out_ref[0] = (x.astype(jnp.float32) / np.float32(denom)).astype(out_dtype)


def fused_crop_convert(images: jax.Array, offsets: jax.Array,
                       target_shape: Tuple[int, int],
                       out_dtype=jnp.float32,
                       denom: float = 255.0,
                       interpret: Optional[bool] = None) -> jax.Array:
  """Crops [B, H, W, C] uint8 at per-example (y, x) and converts in one pass.

  Returns ``images[b, y:y+th, x:x+tw].astype(out_dtype) / denom`` with
  static output shape [B, th, tw, C]. Offsets are clamped to the valid
  range like ``lax.dynamic_slice`` so the contract matches the XLA path.
  """
  b, h, w, c = images.shape
  th, tw = target_shape
  if images.dtype != jnp.uint8:
    raise ValueError('fused_crop_convert expects uint8 images, got {}.'
                     .format(images.dtype))
  if not supported(images.shape):
    raise ValueError('Unsupported image shape {} (need W*C % 128 == 0 and '
                     'H % 8 == 0); use crop_images instead.'
                     .format(images.shape))
  if interpret is None:
    interpret = not runtime.on_tpu()

  offsets = jnp.asarray(offsets, jnp.int32)
  offsets = jnp.clip(offsets, 0,
                     jnp.asarray([h - th, w - tw], jnp.int32))
  # Pre-scale the x offset to lanes; the kernel sees (row, lane) offsets.
  offsets = offsets * jnp.asarray([1, c], jnp.int32)

  wc, twc = w * c, tw * c
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(b,),
      in_specs=[pl.BlockSpec((1, h, wc), lambda i, offs: (i, 0, 0))],
      out_specs=pl.BlockSpec((1, th, twc), lambda i, offs: (i, 0, 0)),
  )
  kernel = functools.partial(_crop_kernel, h=h, wc=wc, th=th, twc=twc,
                             denom=denom, out_dtype=out_dtype)
  out = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((b, th, twc), out_dtype),
      interpret=interpret,
  )(offsets, images.reshape(b, h, wc))
  return out.reshape(b, th, tw, c)
