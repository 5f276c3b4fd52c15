"""The benchmark's own record writer: tf.Example protos in TFRecord framing.

Both formats are public and small. The benchmark writes them itself so that
the data a cell reads does not move with the program's writer: a feature is
keyed by its spec's ``name``, an encoded image is one JPEG in a bytes list, a
float vector a float list, an integer vector an int64 list.
"""

import functools
import io
import os
import struct
from concurrent.futures import ThreadPoolExecutor

import google_crc32c
import numpy as np
from PIL import Image


def _varint(value):
  out = bytearray()
  while True:
    byte = value & 0x7F
    value >>= 7
    if value:
      out.append(byte | 0x80)
    else:
      out.append(byte)
      return bytes(out)


def _field(number, payload):
  """A length-delimited protobuf field."""
  return _varint((number << 3) | 2) + _varint(len(payload)) + payload


def _feature(value):
  """tf.train.Feature: bytes_list=1, float_list=2, int64_list=3."""
  if isinstance(value, bytes):
    return _field(1, _field(1, value))
  array = np.asarray(value)
  if np.issubdtype(array.dtype, np.floating):
    packed = array.astype('<f4').ravel().tobytes()
    return _field(2, _field(1, packed))
  packed = b''.join(_varint(int(v) & 0xFFFFFFFFFFFFFFFF)
                    for v in array.ravel())
  return _field(3, _field(1, packed))


def example_bytes(features):
  """{name: bytes | float array | int array} -> serialized tf.train.Example."""
  entries = b''.join(
      _field(1, _field(1, name.encode('utf-8')) + _field(2, _feature(value)))
      for name, value in sorted(features.items()))
  return _field(1, entries)


def _masked_crc(data):
  crc = google_crc32c.value(data)
  return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def frame(record):
  """[u64 length][u32 crc(length)][record][u32 crc(record)], little-endian."""
  length = struct.pack('<Q', len(record))
  return b''.join((length, struct.pack('<I', _masked_crc(length)), record,
                   struct.pack('<I', _masked_crc(record))))


@functools.lru_cache(maxsize=4)
def _ramp(height, width):
  """The diagonal brightness ramp every frame of one size shares."""
  return np.outer(np.linspace(0, 1, height, dtype=np.float32),
                  np.linspace(0, 1, width, dtype=np.float32))[..., None]


def camera_like_pixels(rng, height, width):
  """A uint8 [H, W, 3] frame with camera-like statistics: gradient
  background, solid blocks, mild sensor noise. Uniform noise over the whole
  range, the obvious alternative, is JPEG's worst case and would misstate
  every host-side figure taken on it."""
  pixels = (_ramp(height, width) *
            rng.integers(100, 255, 3).astype(np.float32)).astype(np.int16)
  for _ in range(12):
    r = int(rng.integers(0, max(1, height - 80)))
    c = int(rng.integers(0, max(1, width - 100)))
    pixels[r:r + 80, c:c + 100] = rng.integers(0, 255, 3)
  pixels += rng.integers(-10, 11, (height, width, 1), dtype=np.int16)
  return np.clip(pixels, 0, 255).astype(np.uint8)


def camera_like_jpeg(rng, height, width, quality=75):
  buf = io.BytesIO()
  Image.fromarray(camera_like_pixels(rng, height, width)).save(
      buf, format='JPEG', quality=quality)
  return buf.getvalue()


def flat_specs(spec_structures):
  """[(example feature name, shape, dtype, is_jpeg)] of the named leaves of
  the program's spec structures (what its parser will look for)."""
  out = []
  for structure in spec_structures:
    if structure is None:
      continue
    for key in structure:
      spec = structure[key]
      if spec.name is None:
        continue
      out.append((spec.name, tuple(spec.shape), np.dtype(spec.dtype),
                  bool(spec.is_encoded_image)))
  return out


def _one_record(args):
  specs, seed, index = args
  rng = np.random.default_rng([seed % (2**32), seed // (2**32), index])
  features = {}
  for name, shape, dtype, is_jpeg in specs:
    if is_jpeg:
      features[name] = camera_like_jpeg(rng, shape[0], shape[1])
    elif np.issubdtype(dtype, np.integer):
      features[name] = rng.integers(0, 2, shape)
    else:
      features[name] = rng.random(shape, dtype=np.float32)
  return frame(example_bytes(features))


def write_records(path, specs, num_records, seed, threads=8):
  """Writes ``num_records`` records of ``specs`` to ``path``, each drawn from
  (seed, index) alone, so the file is the same whatever the thread count.
  JPEG encoding releases the GIL; threads keep the set-up short. Written to a
  temporary name and renamed, so a killed run leaves no half file."""
  os.makedirs(os.path.dirname(path), exist_ok=True)
  tmp = '{}.tmp{}'.format(path, os.getpid())
  with ThreadPoolExecutor(threads) as pool, open(tmp, 'wb') as f:
    for framed in pool.map(_one_record,
                           ((specs, seed, i) for i in range(num_records)),
                           chunksize=8):
      f.write(framed)
  os.replace(tmp, path)
  return os.path.getsize(path)
