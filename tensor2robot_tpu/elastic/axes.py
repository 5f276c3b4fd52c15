"""Elastic fleet orchestration + the ELASTIC bench axes (jax-free).

The elastic contract is proved the way the fleet observatory's was
(``observability/fleet_sim.py``): REAL OS processes sharing one
filesystem. ``run_elastic_fleet`` spawns N ``elastic.driver`` hosts
(each its own jax runtime on virtual CPU devices), lets them train,
SIGKILLs one mid-run (the preemption no marker ever narrates — the
lease lapse is the only evidence), waits for the coordinator's shrink +
``t2r.recovery.v1`` record, relaunches the victim, waits for the grow
back to N, and stops the run through the driver's stop-file. The same
harness backs tests/test_elastic.py's CPU acceptance run and the
MULTICHIP elastic phase (``__graft_entry__``), so the bench axes and
the test assertions are computed from identical evidence.

``collect_axes`` digests the shared base_dir's merged telemetry into
the ``ELASTIC_BENCH_KEYS`` schema the MULTICHIP artifact publishes
(host-count scaling curve + shrink/recovery axis), locked by
``bin/check_elastic_doctor``.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

from tensor2robot_tpu.elastic import membership

__all__ = ['ELASTIC_BENCH_KEYS', 'collect_axes', 'run_elastic_fleet']

# The MULTICHIP elastic axes (schema-locked in bin/check_elastic_doctor):
#   elastic_hosts              peak world size observed
#   elastic_world_curve        {world_size: aggregate examples/sec} —
#                              the host-count scaling curve
#   elastic_world_before/after the shrink's world change (t2r.recovery.v1)
#   elastic_regrow_world       world size after the last grow
#   elastic_recovery_seconds   preemption_recovery_seconds of the shrink
#   elastic_recovery_phases    its phase split (sums to the total)
#   elastic_surviving_compiles XLA compiles across every epoch>1 WARM
#                              rebuild — rebuilds by hosts already
#                              training (each incarnation's first
#                              rebuild is a process cold start and
#                              excluded); 0 when the artifact store
#                              serves every survivor
#   elastic_rebind_outcomes    per-rebuild artifact outcomes ('hit'/'miss')
#   elastic_shrinks/_grows     completed ladder counts
ELASTIC_BENCH_KEYS = (
    'elastic_hosts',
    'elastic_world_curve',
    'elastic_world_before',
    'elastic_world_after',
    'elastic_regrow_world',
    'elastic_recovery_seconds',
    'elastic_recovery_phases',
    'elastic_surviving_compiles',
    'elastic_rebind_outcomes',
    'elastic_shrinks',
    'elastic_grows',
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _merged_records(base_dir: str) -> List[Dict[str, object]]:
  from tensor2robot_tpu.observability import fleet as fleet_lib
  try:
    return fleet_lib.merged_records(fleet_lib.read_fleet(base_dir))
  except OSError:
    return []


def collect_axes(base_dir: str) -> Dict[str, object]:
  """Digests one elastic run's shared dir into ELASTIC_BENCH_KEYS."""
  records = _merged_records(base_dir)
  elastic = [r for r in records if r.get('kind') == 'elastic']
  trains = [r for r in records if r.get('kind') == 'train']
  recoveries = [r for r in records if r.get('kind') == 'recovery'
                and r.get('world_before') is not None]

  # World timeline: each grow/shrink_begin sets the world from its
  # wall-clock stamp onward (plan publish and the event share the stamp
  # to within a write).
  timeline: List[Tuple[float, int]] = []
  for record in elastic:
    event = record.get('event')
    if event in (membership.EVENT_GROW, membership.EVENT_SHRINK_BEGIN):
      timeline.append((float(record.get('time', 0.0)),
                       int(record.get('world_after') or 0)))
  timeline.sort(key=lambda entry: entry[0])

  def world_at(stamp: float) -> Optional[int]:
    current = None
    for at, world in timeline:
      if at <= stamp:
        current = world
      else:
        break
    return current

  # Scaling curve: per world size, sum over hosts of that host's mean
  # examples/sec while the world held that size — the aggregate rate
  # the fleet actually delivered at each world.
  per_world_host: Dict[int, Dict[int, List[float]]] = {}
  for record in trains:
    rate = record.get('examples_per_sec')
    world = world_at(float(record.get('time', 0.0)))
    if not rate or not world:
      continue
    host = int(record.get('process_index') or 0)
    per_world_host.setdefault(world, {}).setdefault(host, []).append(
        float(rate))
  curve = {
      str(world): round(sum(sum(rates) / len(rates)
                            for rates in hosts.values()), 2)
      for world, hosts in sorted(per_world_host.items())}

  rebuilds = [r for r in elastic
              if r.get('event') == membership.EVENT_REBUILD
              and int(r.get('epoch') or 0) > 1]
  # Surviving-host rebuilds only: each incarnation's FIRST rebuild is a
  # process cold start (a rejoiner pays device-init/transfer compiles
  # even when its train step deserializes), so per host a 'join' resets
  # the warm flag and the next rebuild is excluded. What remains is the
  # zero-compile claim that matters: a host that was already training
  # rebuilds into the new world without compiling anything.
  warm_rebuilds = []
  warm: Dict[int, bool] = {}
  for record in sorted(elastic, key=lambda r: float(r.get('time', 0.0))):
    host = int(record.get('host', record.get('process_index')) or 0)
    event = record.get('event')
    if event == membership.EVENT_JOIN:
      warm[host] = False
    elif event == membership.EVENT_REBUILD:
      if warm.get(host) and int(record.get('epoch') or 0) > 1:
        warm_rebuilds.append(record)
      warm[host] = True
  recovery = recoveries[-1] if recoveries else {}
  grows = [r for r in elastic if r.get('event') == membership.EVENT_GROW]
  return {
      'elastic_hosts': max([int(w) for _, w in timeline] or [0]),
      'elastic_world_curve': curve,
      'elastic_world_before': recovery.get('world_before'),
      'elastic_world_after': recovery.get('world_after'),
      'elastic_regrow_world': (int(grows[-1].get('world_after') or 0)
                               if grows else None),
      'elastic_recovery_seconds': recovery.get(
          'preemption_recovery_seconds'),
      'elastic_recovery_phases': recovery.get('phases'),
      'elastic_surviving_compiles': sum(
          float(r.get('compiles_delta') or 0.0) for r in warm_rebuilds),
      'elastic_rebind_outcomes': [str(r.get('artifact_outcome'))
                                  for r in rebuilds],
      'elastic_shrinks': sum(
          1 for r in elastic if r.get('event') == membership.EVENT_SHRINK),
      'elastic_grows': len(grows),
  }


def _subprocess_env() -> Dict[str, str]:
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  env.pop('XLA_FLAGS', None)  # the driver sets its own device count
  return env


def launch_host(base_dir: str, host: int, world: int,
                local_device_count: int = 2, boundary_steps: int = 2,
                per_host_batch: int = 8, lease_ttl_secs: float = 4.0,
                renew_secs: float = 0.5, max_run_seconds: float = 240.0,
                extra_args: Tuple[str, ...] = ()) -> subprocess.Popen:
  """One elastic driver subprocess; stdout -> base_dir/driver.<host>.log."""
  os.makedirs(base_dir, exist_ok=True)
  log = open(os.path.join(base_dir, 'driver.{}.log'.format(host)), 'a')
  cmd = [sys.executable, '-m', 'tensor2robot_tpu.elastic.driver',
         '--base_dir', base_dir, '--host', str(host),
         '--world', str(world),
         '--local_device_count', str(local_device_count),
         '--boundary_steps', str(boundary_steps),
         '--per_host_batch', str(per_host_batch),
         '--lease_ttl_secs', str(lease_ttl_secs),
         '--renew_secs', str(renew_secs),
         '--max_run_seconds', str(max_run_seconds),
         '--stop_file', os.path.join(base_dir, 'STOP')]
  cmd.extend(extra_args)
  proc = subprocess.Popen(cmd, cwd=_REPO_ROOT, env=_subprocess_env(),
                          stdout=log, stderr=subprocess.STDOUT)
  proc._t2r_log = log  # keep the handle alive with the process
  return proc


def _wait_for(predicate: Callable[[], bool], timeout: float,
              what: str, poll_secs: float = 0.5) -> None:
  deadline = time.monotonic() + timeout
  while time.monotonic() < deadline:
    if predicate():
      return
    time.sleep(poll_secs)
  raise TimeoutError('elastic fleet: timed out waiting for ' + what)


def _host_max_step(records, host: int) -> int:
  steps = [int(r.get('step') or 0) for r in records
           if r.get('kind') == 'train'
           and int(r.get('process_index') or 0) == int(host)]
  return max(steps) if steps else -1


def run_elastic_fleet(base_dir: str, hosts: int = 3, kill_host: int = 1,
                      local_device_count: int = 2,
                      boundary_steps: int = 2, per_host_batch: int = 8,
                      lease_ttl_secs: float = 4.0,
                      renew_secs: float = 0.5,
                      kill_after_step: int = 2,
                      settle_boundaries: int = 2,
                      phase_timeout: float = 150.0
                      ) -> Dict[str, object]:
  """The full shrink-then-grow acceptance run (see module docstring).

  Returns ``{'axes': ELASTIC_BENCH_KEYS dict, 'pre_preempt_step',
  'post_resume_steps', 'exit_codes'}``. Raises TimeoutError when any
  phase fails to materialize — with every driver log left under
  ``base_dir/driver.<i>.log`` for the post-mortem.
  """
  stop_file = os.path.join(base_dir, 'STOP')
  survivors = [h for h in range(hosts) if h != kill_host]

  def spawn(host: int) -> subprocess.Popen:
    return launch_host(
        base_dir, host, hosts, local_device_count=local_device_count,
        boundary_steps=boundary_steps, per_host_batch=per_host_batch,
        lease_ttl_secs=lease_ttl_secs, renew_secs=renew_secs)

  procs = {host: spawn(host) for host in range(hosts)}
  rejoined = None
  try:
    _wait_for(
        lambda: all(_host_max_step(_merged_records(base_dir), h)
                    >= kill_after_step for h in range(hosts)),
        phase_timeout, 'all {} hosts to pass step {}'.format(
            hosts, kill_after_step))
    records = _merged_records(base_dir)
    pre_step = max(_host_max_step(records, h) for h in range(hosts))

    # The preemption: SIGKILL writes nothing anywhere — the lease lapse
    # is the only way the fleet can learn this host is gone.
    procs[kill_host].send_signal(signal.SIGKILL)
    procs[kill_host].wait(timeout=30)

    def shrunk() -> bool:
      recs = _merged_records(base_dir)
      return any(r.get('kind') == 'recovery'
                 and r.get('world_after') == hosts - 1 for r in recs)
    _wait_for(shrunk, phase_timeout + lease_ttl_secs,
              'the shrink recovery record (world {} -> {})'.format(
                  hosts, hosts - 1))
    _wait_for(
        lambda: all(_host_max_step(_merged_records(base_dir), h)
                    > pre_step for h in survivors),
        phase_timeout, 'survivors to resume past step {}'.format(pre_step))

    # Rejoin: a fresh incarnation of the killed host.
    rejoined = spawn(kill_host)

    def regrown() -> bool:
      recs = _merged_records(base_dir)
      grow = [r for r in recs if r.get('kind') == 'elastic'
              and r.get('event') == membership.EVENT_GROW
              and int(r.get('world_after') or 0) == hosts
              and int(r.get('epoch') or 0) > 1]
      if not grow:
        return False
      # The rejoined host must have REBUILT into the grown world and
      # trained (its rebuild event names the grow's epoch or later).
      epoch = max(int(r.get('epoch') or 0) for r in grow)
      return any(r.get('kind') == 'elastic'
                 and r.get('event') == membership.EVENT_REBUILD
                 and int(r.get('process_index') or -1) == kill_host
                 and int(r.get('epoch') or 0) >= epoch for r in recs)
    _wait_for(regrown, phase_timeout,
              'the grow back to world {}'.format(hosts))
    records = _merged_records(base_dir)
    resume_floor = max(_host_max_step(records, h) for h in survivors)
    _wait_for(
        lambda: all(
            _host_max_step(_merged_records(base_dir), h)
            >= resume_floor + settle_boundaries * boundary_steps
            for h in survivors),
        phase_timeout, 'post-grow settling')

    with open(stop_file, 'w') as f:
      f.write('stop\n')
    exit_codes = {}
    for host, proc in list(procs.items()) + [(kill_host, rejoined)]:
      if host == kill_host and proc is procs.get(kill_host):
        continue  # the SIGKILLed incarnation already reaped
      try:
        exit_codes[host] = proc.wait(timeout=90)
      except subprocess.TimeoutExpired:
        proc.kill()
        exit_codes[host] = 'timeout'
    records = _merged_records(base_dir)
    return {
        'axes': collect_axes(base_dir),
        'pre_preempt_step': pre_step,
        'post_resume_steps': {h: _host_max_step(records, h)
                              for h in range(hosts)},
        'exit_codes': exit_codes,
    }
  finally:
    for proc in list(procs.values()) + ([rejoined] if rejoined else []):
      if proc.poll() is None:
        proc.kill()
