"""Token records for traffic of kind ``train_tokens``: packed documents.

Documents of lognormal length (the median and sigma the traffic file gives,
clipped), each ending in the end-of-document id 0, are laid end to end and
cut into sequences of the model's length: no padding, no document mask, a
document may run over a sequence's end. The other ids, 1..vocab-1, are drawn
from a Zipf law over the slice of the vocabulary the configuration holds, so
that a few ids make up much of the text and routing is uneven. One sequence
is one ``tf.train.Example`` with one int64 list, in TFRecord framing
(``records.py`` beside this file has both formats).
"""

import os

import numpy as np

from benchmark.harness import records

END_OF_DOCUMENT = 0


def document_lengths(rng, total, median, sigma, shortest, longest):
  """Lengths (each counts its closing end-of-document id) that cover at
  least ``total`` tokens."""
  lengths = []
  covered = 0
  while covered < total:
    draw = np.exp(rng.normal(np.log(median), sigma,
                             size=max(64, total // median)))
    draw = np.clip(np.rint(draw), shortest, longest).astype(np.int64)
    lengths.append(draw)
    covered += int(draw.sum())
  lengths = np.concatenate(lengths)
  return lengths[:int(np.searchsorted(np.cumsum(lengths), total)) + 1]


def token_stream(seed, total, vocab, zipf_exponent, median, sigma, shortest,
                 longest):
  """(tokens [total] int32, document lengths): the same for the same seed."""
  rng = np.random.default_rng([seed % (2**32), seed // (2**32), 0x70c5])
  lengths = document_lengths(rng, total, median, sigma, shortest, longest)
  weights = 1.0 / np.arange(1, vocab, dtype=np.float64) ** zipf_exponent
  cumulative = np.cumsum(weights / weights.sum())
  tokens = 1 + np.minimum(np.searchsorted(cumulative, rng.random(total)),
                          vocab - 2)
  ends = np.cumsum(lengths) - 1
  tokens[ends[ends < total]] = END_OF_DOCUMENT
  return tokens.astype(np.int32), lengths


def _varints(values):
  """The protobuf varints of non-negative ints below 2**21, as bytes."""
  values = np.asarray(values, np.uint32)
  if values.size and int(values.max()) >= 1 << 21:
    raise ValueError('ids of 2**21 and more are not written')
  out = np.zeros((values.size, 3), np.uint8)
  out[:, 0] = values & 0x7F
  out[:, 1] = (values >> 7) & 0x7F
  out[:, 2] = (values >> 14) & 0x7F
  used = np.ones((values.size, 3), bool)
  used[:, 1] = values >= 1 << 7
  used[:, 2] = values >= 1 << 14
  out[:, 0] |= np.where(used[:, 1], 0x80, 0).astype(np.uint8)
  out[:, 1] |= np.where(used[:, 2], 0x80, 0).astype(np.uint8)
  return out[used].tobytes()


def sequence_example(name, tokens):
  """One sequence as a serialized tf.train.Example: {name: int64 list}."""
  int64_list = records._field(3, records._field(1, _varints(tokens)))
  entry = records._field(1, name.encode('utf-8')) + records._field(
      2, int64_list)
  return records._field(1, records._field(1, entry))


def write_token_records(path, name, num_records, length, seed, vocab,
                        zipf_exponent, median, sigma, shortest, longest):
  """Writes ``num_records`` sequences of ``length`` tokens; returns the
  file's size. Written to a temporary name and renamed."""
  tokens, _ = token_stream(seed, num_records * length, vocab, zipf_exponent,
                           median, sigma, shortest, longest)
  os.makedirs(os.path.dirname(path), exist_ok=True)
  tmp = '{}.tmp{}'.format(path, os.getpid())
  with open(tmp, 'wb') as f:
    for row in tokens.reshape(num_records, length):
      f.write(records.frame(sequence_example(name, row)))
  os.replace(tmp, path)
  return os.path.getsize(path)
