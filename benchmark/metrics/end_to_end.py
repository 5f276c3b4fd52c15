"""The end-to-end metrics: taken by the benchmark itself with the host's
clock, never read from the program."""


def _observed(name):
  return lambda obs: obs.get(name)


METRICS = {
    'setup_s': _observed('setup_s'),
    'train_examples_per_s_per_chip': _observed(
        'train_examples_per_s_per_chip'),
}
