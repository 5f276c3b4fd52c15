"""Two-process multi-host proof (SURVEY §2.9 DCN row, VERDICT-r2 item 5).

Spawns two REAL processes, each owning 4 virtual CPU devices, connected
through jax.distributed: per-host input shards, a global 8-device mesh,
cross-host gradient psums, and a cooperatively-written Orbax checkpoint
that restores identically on both hosts
(tensor2robot_tpu/parallel/multihost.py:multihost_dryrun asserts each).

Under the installed jax the CPU backend runs multi-process computations
(Gloo), so this is a plain test. When one process fails, the other waits
out the coordination service's barrier: a failure here takes ~5 minutes
to report, a pass ~15 s.
"""

import os
import socket
import subprocess
import sys


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
  with socket.socket() as s:
    s.bind(('localhost', 0))
    return s.getsockname()[1]


def test_two_process_train_checkpoint_restore(tmp_path):
  workdir = str(tmp_path / 'mh')
  os.makedirs(workdir)
  port = _free_port()
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  env.pop('XLA_FLAGS', None)  # multihost.py sets the device count itself
  procs = []
  logs = []
  for pid in (0, 1):
    log = open(os.path.join(workdir, 'p{}.log'.format(pid)), 'w')
    logs.append(log)
    procs.append(subprocess.Popen(
        [sys.executable, '-m', 'tensor2robot_tpu.parallel.multihost',
         '--workdir', workdir,
         '--coordinator', 'localhost:{}'.format(port),
         '--num_processes', '2', '--process_id', str(pid),
         '--local_device_count', '4'],
        cwd=REPO_ROOT, env=env, stdout=log, stderr=subprocess.STDOUT))
  try:
    for pid, proc in enumerate(procs):
      rc = proc.wait(timeout=420)
      if rc != 0:
        logs[pid].flush()
        with open(os.path.join(workdir, 'p{}.log'.format(pid))) as f:
          raise AssertionError(
              'process {} exited {}:\n{}'.format(pid, rc, f.read()[-4000:]))
  finally:
    for proc in procs:
      if proc.poll() is None:
        proc.kill()
    for log in logs:
      log.close()
  for pid in (0, 1):
    marker = os.path.join(workdir, 'ok_{}'.format(pid))
    assert os.path.exists(marker), 'missing ' + marker
    with open(marker) as f:
      assert '2 hosts x 4 devices' in f.read()
