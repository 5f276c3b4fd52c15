"""chip_smoke.py off the chip: it must refuse to run, and its legs — the
same functions the chip runs at full size — must pass at a tiny size on
the CPU (cut depth, small batch, interpreted kernels). Plus the two
placement rules the smoke leans on: where compiled programs are cached and
how the native library is named.
"""

import os
import shutil
import subprocess
import sys

import pytest

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

from tensor2robot_tpu import parallel, runtime  # noqa: E402
from tensor2robot_tpu.data import native_loader  # noqa: E402

# Depth cut to one conv per block; every width kept.
_TINY = {'network_kwargs': {'num_convs': (1, 1, 1)}}


def _run_smoke(cwd, env_overrides):
  env = dict(os.environ)
  env.pop('PYTHONPATH', None)
  env.update(env_overrides)
  return subprocess.run(
      [sys.executable, 'chip_smoke.py'], cwd=cwd, env=env,
      capture_output=True, text=True, timeout=300)


class TestRefusals:

  def test_exits_nonzero_without_a_tpu_and_says_why(self):
    result = _run_smoke(REPO_ROOT, {'JAX_PLATFORMS': 'cpu'})
    assert result.returncode != 0
    assert 'not the TPU' in result.stderr
    assert '"ok"' not in result.stdout

  def test_exits_nonzero_without_the_rest_of_the_repo(self, tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, 'chip_smoke.py'), str(tmp_path))
    result = _run_smoke(str(tmp_path), {'JAX_PLATFORMS': 'cpu'})
    assert result.returncode != 0
    assert 'tensor2robot_tpu' in result.stderr
    assert '"ok"' not in result.stdout


class TestLegsAtTinySize:

  def test_trainer_then_server(self, tmp_path, monkeypatch):
    """Eight devices train; the server — one un-sharded AOT program —
    must still restore that checkpoint and answer from it."""
    monkeypatch.setenv('T2R_TUNING_CACHE',
                       str(tmp_path / 'store' / 'tuning_cache.json'))
    from tensor2robot_tpu.observability import install_jax_listeners

    install_jax_listeners()  # the request-time compile count reads these
    model_dir = chip_smoke.trainer_leg(
        str(tmp_path), parallel.create_mesh(), batch_size=8, steps=3,
        model_kwargs=_TINY)
    chip_smoke.server_leg(
        model_dir, device_type='cpu', cem_samples=4, cem_iters=1,
        num_elites=2, max_batch_size=2, requests=6, model_kwargs=_TINY)

  def test_flash_against_dense_interpreted(self):
    chip_smoke.flash_leg(seq_len=256, batch=1, heads=2, interpret=True)

  def test_a_failing_check_raises(self):
    with pytest.raises(chip_smoke.SmokeFailure, match='disagrees'):
      chip_smoke.flash_leg(seq_len=128, batch=1, heads=1, interpret=True,
                           tolerance=0.0)


class TestCompileCachePlacement:

  def test_env_value_is_left_alone(self, monkeypatch, tmp_path):
    monkeypatch.setenv(runtime.CACHE_DIR_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert os.environ[runtime.CACHE_DIR_ENV] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # not set in code

  def test_unset_gives_the_same_in_checkout_path_in_every_process(self):
    env = dict(os.environ)
    env.pop(runtime.CACHE_DIR_ENV, None)
    env.pop('T2R_TUNING_CACHE', None)
    code = ('from tensor2robot_tpu import runtime\n'
            'from tensor2robot_tpu.tuning import cache\n'
            'print(runtime.cache_root())\n'
            'print(cache.default_cache_path())')
    outs = [subprocess.run([sys.executable, '-c', code], cwd=REPO_ROOT,
                           env=env, capture_output=True, text=True,
                           check=True, timeout=120).stdout.split()
            for _ in range(2)]
    assert outs[0] == outs[1]
    root, tuning_cache = outs[0]
    assert root == os.path.join(REPO_ROOT, '.jax_cache')
    assert tuning_cache == os.path.join(root, 't2r', 'tuning_cache.json')

  def test_unset_configures_that_path(self, monkeypatch):
    monkeypatch.delenv(runtime.CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
      assert runtime.enable_compile_cache() == runtime.cache_root()
      assert jax.config.jax_compilation_cache_dir == runtime.cache_root()
    finally:
      jax.config.update('jax_compilation_cache_dir', before)


class TestNativeLibraryName:

  def test_name_follows_the_source_bytes(self, tmp_path, monkeypatch):
    original = native_loader._so_path()
    copy = tmp_path / 'record_loader.cc'
    shutil.copy(native_loader._SOURCE, str(copy))
    monkeypatch.setattr(native_loader, '_SOURCE', str(copy))
    assert os.path.basename(native_loader._so_path()) == \
        os.path.basename(original)
    with open(str(copy), 'a') as f:
      f.write('// one more byte\n')
    assert os.path.basename(native_loader._so_path()) != \
        os.path.basename(original)
