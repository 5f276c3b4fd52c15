"""Config system tests: the gin-syntax engine + config-driven training.

The e2e case is the reference's contract: ONE command trains a workload
from a config file (ref bin/run_t2r_trainer.py:32-39).
"""

import os
import sys

import numpy as np
import pytest

from tensor2robot_tpu.config import ginlike

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_config():
  ginlike.clear_config()
  yield
  ginlike.clear_config()


class TestEngine:

  def test_binding_injection_and_override(self):
    @ginlike.configurable('cfgtest_f1')
    def f(a=1, b=2):
      return a, b

    ginlike.parse_config('cfgtest_f1.a = 10\ncfgtest_f1.b = 20')
    assert f() == (10, 20)
    assert f(b=99) == (10, 99)     # explicit kwargs win
    assert f(5) == (5, 20)         # positional wins over binding

  def test_macros_and_literals(self):
    @ginlike.configurable('cfgtest_f2')
    def f(path=None, rate=None, flags=None, table=None):
      return path, rate, flags, table

    ginlike.parse_config("""
      DATA = '/tmp/data*'
      cfgtest_f2.path = %DATA
      cfgtest_f2.rate = 1e-4
      cfgtest_f2.flags = [True, False, None]
      cfgtest_f2.table = {'a': 1, 'b': (2, 3)}
    """)
    path, rate, flags, table = f()
    assert path == '/tmp/data*'
    assert rate == pytest.approx(1e-4)
    assert flags == [True, False, None]
    assert table == {'a': 1, 'b': (2, 3)}

  def test_configurable_reference_and_call(self):
    @ginlike.configurable('cfgtest_make')
    def make(value=3):
      return value * 2

    @ginlike.configurable('cfgtest_user')
    def user(factory=None, result=None):
      return factory, result

    ginlike.parse_config("""
      cfgtest_user.factory = @cfgtest_make
      cfgtest_user.result = @cfgtest_make()
      cfgtest_make.value = 5
    """)
    factory, result = user()
    assert result == 10        # called at injection, with its own bindings
    assert factory() == 10     # the callable itself, still configurable

  def test_scoped_bindings(self):
    @ginlike.configurable('cfgtest_gen')
    def gen(batch_size=1):
      return batch_size

    ginlike.parse_config("""
      TRAIN_GEN = @train/cfgtest_gen()
      train/cfgtest_gen.batch_size = 32
      eval/cfgtest_gen.batch_size = 4

      cfgtest_consume.train_gen = %TRAIN_GEN
      cfgtest_consume.eval_gen = @eval/cfgtest_gen()
    """)

    @ginlike.configurable('cfgtest_consume')
    def consume(train_gen=None, eval_gen=None):
      return train_gen, eval_gen

    assert consume() == (32, 4)
    assert gen() == 1  # unscoped call untouched

  def test_include_and_operative_config(self, tmp_path):
    base = tmp_path / 'base.gin'
    base.write_text('cfgtest_inc.a = 1\n')
    main = tmp_path / 'main.gin'
    main.write_text("include 'base.gin'\ncfgtest_inc.b = 2\n")

    @ginlike.configurable('cfgtest_inc')
    def f(a=0, b=0, c=0):
      return a + b + c

    ginlike.parse_config_files_and_bindings([str(main)],
                                            ['cfgtest_inc.c = 4'])
    assert f() == 7
    operative = ginlike.operative_config_str()
    assert 'cfgtest_inc.a = 1' in operative
    assert 'cfgtest_inc.c = 4' in operative

  def test_unknown_parameter_raises(self):
    @ginlike.configurable('cfgtest_strict')
    def f(a=0):
      return a

    ginlike.parse_config('cfgtest_strict.nope = 1')
    with pytest.raises(ginlike.ConfigError, match='unknown configured'):
      f()

  def test_query_parameter_and_config_str(self):
    ginlike.parse_config('some.thing = 42')
    assert ginlike.query_parameter('some.thing') == 42
    assert 'some.thing = 42' in ginlike.config_str()

  def test_suffix_name_matching(self):
    @ginlike.configurable('pkg.mod.cfgtest_suffix')
    def f(x=0):
      return x

    ginlike.parse_config('cfgtest_suffix.x = 3')
    assert f() == 3


class TestEndToEnd:

  def test_one_command_trains_pose_env(self, tmp_path):
    """The reference contract: config file + one call = a trained model."""
    sys.path.insert(0, os.path.join(REPO_ROOT, 'bin'))
    try:
      import run_t2r_trainer
    finally:
      sys.path.pop(0)
    model_dir = str(tmp_path / 'run')
    results = run_t2r_trainer.main([
        '--gin_configs',
        os.path.join(REPO_ROOT, 'tensor2robot_tpu/research/pose_env/configs/'
                     'train_pose_env.gin'),
        '--gin_bindings',
        "train_eval_model.model_dir = '{}'".format(model_dir),
    ])
    from tensor2robot_tpu.trainer import latest_checkpoint_step
    assert latest_checkpoint_step(model_dir) == 4
    assert results['eval_metrics']
    # Exporters ran: at least one committed numeric export version exists.
    from tensor2robot_tpu.export.export_generators import (
        list_exported_versions,
    )
    export_root = os.path.join(model_dir, 'export', 'latest_exporter')
    assert list_exported_versions(export_root)

  def test_qtopt_config_parses_and_builds_model(self):
    from tensor2robot_tpu import config
    config.register_framework_configurables()
    config.add_config_file_search_path(REPO_ROOT)
    config.parse_config_files_and_bindings(
        [os.path.join(REPO_ROOT, 'tensor2robot_tpu/research/qtopt/configs/'
                      'train_qtopt.gin')], [])
    model = config.query_parameter('train_eval_model.t2r_model')
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
    )
    assert isinstance(
        model, Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom)
    assert model.hparams['learning_rate'] == pytest.approx(1e-4)


class TestCollectEvalCLI:

  def test_one_command_collects_episodes(self, tmp_path):
    """bin/run_collect_eval.py drives the collect loop from a config."""
    sys.path.insert(0, os.path.join(REPO_ROOT, 'bin'))
    try:
      import run_collect_eval
    finally:
      sys.path.pop(0)
    root = str(tmp_path / 'collect')
    run_collect_eval.main([
        '--gin_configs',
        os.path.join(REPO_ROOT, 'tensor2robot_tpu/research/pose_env/configs/'
                     'run_collect_pose_env.gin'),
        '--gin_bindings',
        "collect_eval_loop.root_dir = '{}'".format(root),
    ])
    import glob
    records = glob.glob(os.path.join(root, 'policy_collect', '*'))
    assert records, 'no collected records written'
    from tensor2robot_tpu.data.tfrecord import read_all_records
    assert len(read_all_records(records[0])) >= 4  # one per episode step


class TestReferenceConfigParity:
  """Round-4 config-parity closure (VERDICT r3 item 6): every reference
  gin file has a working one-command counterpart."""

  def _write_wtl_task_files(self, tmp_path, episode_length, n_tasks=8,
                            episodes_per_task=4):
    import numpy as np
    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.wire import build_example
    rng = np.random.RandomState(0)
    paths = []
    for t in range(n_tasks):
      records = []
      for _ in range(episodes_per_task):
        records.append(build_example({
            'full_state_pose': rng.rand(
                episode_length * 32).astype(np.float32),
            'action_world': rng.rand(
                episode_length * 7).astype(np.float32),
            'success': np.ones((episode_length,), np.float32),
        }))
      path = str(tmp_path / 'task_{}.tfrecord'.format(t))
      tfrecord.write_records(path, records)
      paths.append(path)
    return str(tmp_path / 'task_*.tfrecord')

  def _run_trainer(self, gin_file, bindings):
    sys.path.insert(0, os.path.join(REPO_ROOT, 'bin'))
    try:
      import run_t2r_trainer
    finally:
      sys.path.pop(0)
    args = ['--gin_configs', os.path.join(REPO_ROOT, gin_file)]
    for binding in bindings:
      args.extend(['--gin_bindings', binding])
    return run_t2r_trainer.main(args)

  @pytest.mark.parametrize('config', [
      'run_train_wtl_statespace_trial.gin',
      'run_train_wtl_statespace_retrial.gin',
  ])
  def test_wtl_statespace_configs_train(self, tmp_path, config):
    episode_length = 12  # >= the temporal-reduce conv kernel (10)
    pattern = self._write_wtl_task_files(tmp_path, episode_length)
    model_dir = str(tmp_path / 'run')
    self._run_trainer(
        'tensor2robot_tpu/research/vrgripper/configs/' + config,
        ["TRAIN_DATA = '{}'".format(pattern),
         'VRGripperEnvSimpleTrialModel.episode_length = {}'.format(
             episode_length),
         'train_input_generator/MetaRecordInputGenerator.num_tasks = 8',
         'train_eval_model.max_train_steps = 2',
         'train_eval_model.async_checkpoints = False',
         "train_eval_model.model_dir = '{}'".format(model_dir)])
    from tensor2robot_tpu.trainer import latest_checkpoint_step
    assert latest_checkpoint_step(model_dir) == 2

  def test_pose_env_maml_config_trains(self, tmp_path):
    model_dir = str(tmp_path / 'run')
    results = self._run_trainer(
        'tensor2robot_tpu/research/pose_env/configs/run_train_reg_maml.gin',
        ['train_eval_model.max_train_steps = 2',
         "train_eval_model.model_dir = '{}'".format(model_dir)])
    from tensor2robot_tpu.trainer import latest_checkpoint_step
    assert latest_checkpoint_step(model_dir) == 2
    assert results['eval_metrics']

  def test_qtopt_sparse_config_wires_split_decode(self):
    from tensor2robot_tpu import config
    config.register_framework_configurables()
    config.add_config_file_search_path(REPO_ROOT)
    config.parse_config_files_and_bindings(
        [os.path.join(REPO_ROOT, 'tensor2robot_tpu/research/qtopt/configs/'
                      'train_qtopt_sparse.gin')], [])
    model = config.query_parameter('train_eval_model.t2r_model')
    from tensor2robot_tpu.preprocessors.device_decode import (
        DeviceDecodePreprocessor,
    )
    assert isinstance(model.preprocessor, DeviceDecodePreprocessor)
    assert model.preprocessor.sparse
