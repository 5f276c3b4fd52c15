"""Layer "input: read, decode, batch" and "input: transfer".

``input_wait_share``: seconds the TRAINING THREAD spent inside the program's
``data.next`` and ``data.put_batch`` spans, over the window. With
``feed_depth=1`` both run serially before each dispatch, so this is the share
of the window in which the training thread was not free to dispatch.
``transfer_busy_share``: ``pipeline/transfer/busy_seconds`` over the window.
``wire_bytes_per_example``: bytes put on the host-to-device wire per example.
"""


def _delta(obs, name):
  counters = obs.get('counters')
  if not counters or name not in counters['after']:
    return None
  return counters['after'][name] - counters['before'][name]


def input_wait_share(obs):
  waits = [_delta(obs, 'span/data.next/seconds'),
           _delta(obs, 'span/data.put_batch/seconds')]
  if None in waits or not obs.get('window_s'):
    return None
  return sum(waits) / obs['window_s']


def transfer_busy_share(obs):
  busy = _delta(obs, 'pipeline/transfer/busy_seconds')
  if busy is None or not obs.get('window_s'):
    return None
  return busy / obs['window_s']


def wire_bytes_per_example(obs):
  nbytes = _delta(obs, 'pipeline/transfer/bytes')
  examples = _delta(obs, 'pipeline/transfer/examples')
  if not nbytes or not examples:
    return None
  return nbytes / examples


METRICS = {
    'input_wait_share': input_wait_share,
    'transfer_busy_share': transfer_busy_share,
    'wire_bytes_per_example': wire_bytes_per_example,
}
