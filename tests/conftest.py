"""Test environment: run JAX on a virtual 8-device CPU mesh.

Multi-chip sharding is validated without TPU hardware by forcing the host
platform to expose 8 devices (SURVEY.md §4: the JAX analog of the reference's
TPU-without-TPU estimator tests).
"""

import os

# The suite runs on eight forced CPU devices whatever the ambient
# platform is: sharding tests need the device count, and a test process
# must never take the chip from the one process allowed to hold it.
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ.setdefault('TF_CPP_MIN_LOG_LEVEL', '2')

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 8)


def pytest_configure(config):
  config.addinivalue_line(
      'markers', 'slow: long-running tests excluded from the tier-1 run')
  config.addinivalue_line(
      'markers',
      'fault: FaultInjector-driven fault-tolerance tests '
      "(kept inside the tier-1 'not slow' selection; filter with -m fault)")
