"""Test environment: run JAX on a virtual 8-device CPU mesh.

Multi-chip sharding is validated without TPU hardware by forcing the host
platform to expose 8 devices (SURVEY.md §4: the JAX analog of the reference's
TPU-without-TPU estimator tests).
"""

import collections
import os

import pytest

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


def _count_calls(jaxpr, calls, tags):
  for eqn in jaxpr.eqns:
    calls[eqn.primitive.name] += 1
    if eqn.primitive.name == 'pallas_call':
      calls[eqn.params['name']] += 1
    elif eqn.primitive.name == 'name':
      tags[eqn.params['name']] += 1
    else:
      for inner in jax.core.jaxprs_in_params(eqn.params):
        _count_calls(inner, calls, tags)
  return calls, tags


@pytest.fixture(scope='session')
def jaxpr_calls():
  """``calls, tags = jaxpr_calls(fn, *args)``: two Counters over the whole
  jaxpr of ``fn(*args)``, nested ones included: every primitive by its name
  and the Pallas kernels also by their ``name`` (their bodies are not
  entered); and the ``checkpoint_name`` tags. Exact, and nothing runs."""
  return lambda fn, *args: _count_calls(
      jax.make_jaxpr(fn)(*args).jaxpr, collections.Counter(),
      collections.Counter())
