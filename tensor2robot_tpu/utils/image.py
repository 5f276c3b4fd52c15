"""Image encode/decode helpers.

Parity target: /root/reference/utils/image.py (jpeg_string :29,
numpy_to_image_string :49) — numpy image -> encoded bytes for writing
tf.Example replay records.
"""

from __future__ import annotations

import io

import numpy as np
from PIL import Image


def jpeg_string(image: 'Image.Image', jpeg_quality: int = 90) -> bytes:
  """Encodes a PIL image as JPEG bytes (ref image.py:29)."""
  buf = io.BytesIO()
  image.save(buf, format='JPEG', quality=jpeg_quality)
  return buf.getvalue()


def numpy_to_image_string(image_array: np.ndarray,
                          image_format: str = 'jpeg',
                          data_type=np.uint8) -> bytes:
  """Encodes [H, W, C] numpy array to an image byte string (ref :49)."""
  image_array = np.asarray(image_array, dtype=data_type)
  image = Image.fromarray(image_array)
  buf = io.BytesIO()
  image.save(buf, format=image_format.upper())
  return buf.getvalue()


def image_string_to_numpy(image_bytes: bytes) -> np.ndarray:
  """Decodes encoded image bytes back to a numpy array."""
  with io.BytesIO(image_bytes) as buf:
    return np.asarray(Image.open(buf))


def camera_like_frame(rng: np.random.RandomState, height: int,
                      width: int) -> np.ndarray:
  """A synthetic uint8 [H, W, 3] frame with camera-like statistics:
  gradient background, solid blocks, mild sensor noise. Uniform noise —
  the obvious alternative — is the JPEG worst case (several times the
  bytes and decode time of a real frame) and would misstate every
  host-side figure taken on it."""
  x = np.linspace(0, 1, width)
  y = np.linspace(0, 1, height)
  frame = (np.outer(y, x)[..., None] *
           rng.randint(100, 255, 3)).astype(np.float32)
  for _ in range(12):
    r = rng.randint(0, max(1, height - 80))
    c = rng.randint(0, max(1, width - 100))
    frame[r:r + 80, c:c + 100] = rng.randint(0, 255, 3)
  frame += rng.randn(height, width, 1) * 6
  return np.clip(frame, 0, 255).astype(np.uint8)
