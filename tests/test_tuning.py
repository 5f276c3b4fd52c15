"""Compile-config autotuner tests (tensor2robot_tpu/tuning/).

All CPU-safe: the sweep engine, cache keying, and the trainer hook are
exercised on the 'cpu' candidate set and a stubbed timer — winner
selection must be a pure function of the scripted timings, and the cache
must hit on an identical (workload, shapes, device, jax) key and miss on
any component changing.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import tuning
from tensor2robot_tpu.trainer import Trainer
from tensor2robot_tpu.tuning import autotuner, cache as cache_lib
from tensor2robot_tpu.tuning.autotuner import StepCase
from tensor2robot_tpu.tuning.search_space import CompileConfig
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel


def _tiny_step(scale=2.0):
  """A real jitted step, cheap enough to compile per candidate.

  ``scale`` varies the PROGRAM: candidates built with different scales
  get distinct HLO fingerprints, so winner selection is decided by the
  (stubbed) timer rather than collapsed by the no-op detector.
  """

  @jax.jit
  def step(x):
    return x * scale + 1.0

  return StepCase(jitted=step, args=(jnp.ones((4,), jnp.float32),))


class TestCacheKeying:

  def test_signature_depends_on_shapes_and_dtypes_not_values(self):
    sig_a = tuning.abstract_signature((np.zeros((2, 3), np.float32),))
    sig_same = tuning.abstract_signature((np.ones((2, 3), np.float32),))
    sig_shape = tuning.abstract_signature((np.zeros((2, 4), np.float32),))
    sig_dtype = tuning.abstract_signature((np.zeros((2, 3), np.int32),))
    assert sig_a == sig_same
    assert sig_a != sig_shape
    assert sig_a != sig_dtype

  def test_key_components(self):
    sig = tuning.abstract_signature((np.zeros((2,), np.float32),))
    base = tuning.cache_key('wl', sig, 'TPU v5 lite', jax_version='1.0')
    assert tuning.cache_key('wl2', sig, 'TPU v5 lite', '1.0') != base
    assert tuning.cache_key('wl', sig, 'TPU v4', '1.0') != base
    assert tuning.cache_key('wl', sig, 'TPU v5 lite', '2.0') != base
    assert tuning.cache_key('wl', sig + 'x', 'TPU v5 lite', '1.0') != base
    assert tuning.cache_key('wl', sig, 'TPU v5 lite', '1.0') == base

  def test_store_lookup_round_trip(self, tmp_path):
    cache = tuning.ConfigCache(str(tmp_path / 'cache.json'))
    entry = {'winner': CompileConfig('w', notes='n').to_dict()}
    cache.store('key-a', entry)
    got = cache.lookup('key-a')
    assert got is not None
    assert CompileConfig.from_dict(got['winner']).config_id == 'w'
    assert cache.lookup('key-b') is None

  def test_corrupt_cache_file_reads_as_empty_and_recovers(self, tmp_path):
    path = str(tmp_path / 'cache.json')
    with open(path, 'w', encoding='utf-8') as f:
      f.write('{not json')
    cache = tuning.ConfigCache(path)
    assert cache.lookup('k') is None
    cache.store('k', {'winner': CompileConfig('w').to_dict()})
    assert cache.lookup('k') is not None
    with open(path, encoding='utf-8') as f:
      assert json.load(f)['schema'] == cache_lib.CACHE_SCHEMA

  def test_default_path_env_override(self, tmp_path, monkeypatch):
    monkeypatch.setenv(cache_lib.CACHE_PATH_ENV, str(tmp_path / 'c.json'))
    assert tuning.default_cache_path() == str(tmp_path / 'c.json')


class TestMeasureChained:

  def test_median_and_robust_spread_from_scripted_timer(self):
    # 3 reps: durations 1.0, 5.0 (the hiccup), 1.2 -> median 1.2; the
    # worst rep is dropped, so spread is 1.2 - 1.0, NOT 5.0 - 1.0.
    script = iter([0.0, 1.0, 10.0, 15.0, 20.0, 21.2])
    syncs = []
    median, spread = autotuner.measure_chained(
        step_once=lambda: 'out', sync=syncs.append, n_steps=4, reps=3,
        timer=lambda: next(script))
    assert median == pytest.approx(1.2)
    assert spread == pytest.approx(0.2)
    assert syncs == ['out'] * 3  # one sync per chain, not per step


class TestSweep:

  def _candidates(self):
    return [
        CompileConfig('baseline'),
        CompileConfig('fast-min-max',
                      compiler_options={'xla_cpu_enable_fast_min_max':
                                        True}),
    ]

  def _distinct_program_build(self, config):
    # Different program per candidate (distinct fingerprints), so the
    # no-op collapse does not govern and the timer decides alone.
    return _tiny_step(scale=2.0 if config.config_id == 'baseline' else 3.0)

  def test_deterministic_winner_on_stubbed_timer(self, tmp_path):
    # Candidate 0 chains take 10s, candidate 1 chains 1s: winner is
    # candidate 1 as a pure function of the scripted timer. Warmup is 0
    # so the script only feeds measure_chained (2 calls per rep).
    script = iter([0.0, 10.0, 20.0, 30.0,   # baseline: reps of 10s
                   0.0, 1.0, 2.0, 3.0])     # fast-min-max: reps of 1s
    result = tuning.sweep(
        'stub', self._distinct_program_build,
        candidates=self._candidates(),
        cache=tuning.ConfigCache(str(tmp_path / 'c.json')),
        n_steps=1, reps=2, warmup_steps=0,
        timer=lambda: next(script))
    assert not result.cache_hit
    assert result.winner.config_id == 'fast-min-max'
    assert result.entry['winner_ok']

  def test_tie_breaks_by_candidate_order(self, tmp_path):
    script = iter([0.0, 5.0, 10.0, 15.0,
                   0.0, 5.0, 10.0, 15.0])
    result = tuning.sweep(
        'tie', self._distinct_program_build,
        candidates=self._candidates(),
        cache=tuning.ConfigCache(str(tmp_path / 'c.json')),
        n_steps=1, reps=2, warmup_steps=0,
        timer=lambda: next(script))
    assert result.winner.config_id == 'baseline'

  def test_noop_flag_cannot_beat_baseline_on_noise(self, tmp_path):
    # fast-min-max compiles _tiny_step to the IDENTICAL program as
    # baseline (same fingerprint); even when the timer scripts it
    # faster, the winner must stay baseline — a measured no-op cannot
    # be published as a live lever.
    script = iter([0.0, 10.0, 20.0, 30.0,   # baseline: 10s
                   0.0, 1.0, 2.0, 3.0])     # no-op flag: "faster"
    result = tuning.sweep(
        'noop', lambda config: _tiny_step(),
        candidates=self._candidates(),
        cache=tuning.ConfigCache(str(tmp_path / 'c.json')),
        n_steps=1, reps=2, warmup_steps=0,
        timer=lambda: next(script))
    table = result.entry['candidates']
    assert (table['fast-min-max']['hlo_fingerprint']
            == table['baseline']['hlo_fingerprint'])
    assert result.winner.config_id == 'baseline'

  def test_end_to_end_cpu_sweep_and_cache_round_trip(self, tmp_path):
    """Real compiles + real timing over >=2 candidates, then: identical
    key -> cache HIT with zero builds; changed shapes -> re-sweep."""
    cache = tuning.ConfigCache(str(tmp_path / 'c.json'))
    builds = []

    def build(config):
      builds.append(config.config_id)
      return _tiny_step()

    example = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    first = tuning.sweep('e2e', build, candidates=self._candidates(),
                         example_args=example, cache=cache,
                         n_steps=2, reps=2, warmup_steps=1)
    assert not first.cache_hit
    assert first.winner is not None
    assert len(builds) == 2
    table = first.entry['candidates']
    assert set(table) == {'baseline', 'fast-min-max'}
    assert all(r['compile_ok'] for r in table.values())
    assert all(r['steps_per_s'] > 0 for r in table.values())
    # The winner persisted with its evidence.
    assert os.path.exists(cache.path)

    second = tuning.sweep('e2e', build, candidates=self._candidates(),
                          example_args=example, cache=cache)
    assert second.cache_hit
    assert second.winner.config_id == first.winner.config_id
    assert len(builds) == 2  # HIT performed zero builds/compiles

    changed = tuning.sweep('e2e', build, candidates=self._candidates(),
                           example_args=(jax.ShapeDtypeStruct(
                               (8,), jnp.float32),),
                           cache=cache, n_steps=1, reps=1, warmup_steps=0)
    assert not changed.cache_hit  # shape change re-tunes
    assert len(builds) == 4

  def test_force_resweeps_past_a_hit(self, tmp_path):
    cache = tuning.ConfigCache(str(tmp_path / 'c.json'))
    example = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    tuning.sweep('forced', lambda c: _tiny_step(),
                 candidates=self._candidates(), example_args=example,
                 cache=cache, n_steps=1, reps=1, warmup_steps=0)
    again = tuning.sweep('forced', lambda c: _tiny_step(),
                         candidates=self._candidates(),
                         example_args=example, cache=cache, force=True,
                         n_steps=1, reps=1, warmup_steps=0)
    assert not again.cache_hit

  def test_unknown_flag_candidate_is_recorded_not_fatal(self, tmp_path):
    candidates = [
        CompileConfig('baseline'),
        CompileConfig('bogus',
                      compiler_options={'xla_definitely_not_a_flag': True}),
    ]
    result = tuning.sweep(
        'bogus-flag', lambda c: _tiny_step(), candidates=candidates,
        cache=tuning.ConfigCache(str(tmp_path / 'c.json')),
        n_steps=1, reps=1, warmup_steps=0)
    assert result.winner.config_id == 'baseline'
    bogus = result.entry['candidates']['bogus']
    assert not bogus['compile_ok']
    assert 'xla_definitely_not_a_flag' in bogus['error']

  def test_all_failed_sweep_caches_but_reports_no_winner(self, tmp_path):
    """An all-candidates-failed sweep persists (no re-sweep every
    startup) but a later HIT must report winner=None, not the stored
    placeholder config."""
    candidates = [
        CompileConfig('bad-a',
                      compiler_options={'xla_definitely_not_a_flag': 1}),
        CompileConfig('bad-b',
                      compiler_options={'xla_also_not_a_flag': 1}),
    ]
    cache = tuning.ConfigCache(str(tmp_path / 'c.json'))
    example = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    first = tuning.sweep('doomed', lambda c: _tiny_step(),
                         candidates=candidates, example_args=example,
                         cache=cache, n_steps=1, reps=1, warmup_steps=0)
    assert first.winner is None
    assert not first.entry['winner_ok']
    hit = tuning.sweep('doomed', lambda c: _tiny_step(),
                       candidates=candidates, example_args=example,
                       cache=cache)
    assert hit.cache_hit
    assert hit.winner is None

  def test_identical_programs_share_a_fingerprint(self, tmp_path):
    """The no-op detector: a flag that does not change the optimized
    program must produce the baseline's exact HLO fingerprint."""
    result = tuning.sweep(
        'fp', lambda c: _tiny_step(), candidates=self._candidates(),
        cache=tuning.ConfigCache(str(tmp_path / 'c.json')),
        n_steps=1, reps=1, warmup_steps=0)
    prints = {cid: r['hlo_fingerprint']
              for cid, r in result.entry['candidates'].items()}
    assert all(prints.values())
    assert prints['baseline'] == prints['fast-min-max']


class TestTrainerHook:

  def _train(self, tmp_path, tuned_config, steps=2, cache_path=None):
    model = MockT2RModel(use_batch_norm=False)
    generator = MockInputGenerator(batch_size=8)
    trainer = Trainer(model, str(tmp_path / 'run'),
                      async_checkpoints=False,
                      save_checkpoints_steps=10**9,
                      log_every_n_steps=10**9,
                      tuned_config=tuned_config,
                      tuning_cache_path=cache_path)
    try:
      state = trainer.train(generator, max_train_steps=steps)
      assert int(jax.device_get(state.step)) == steps
      return trainer
    finally:
      trainer.close()

  def test_direct_config_applies_and_is_attributable(self, tmp_path):
    config = CompileConfig(
        'cpu-fmm',
        compiler_options={'xla_cpu_enable_fast_min_max': True})
    trainer = self._train(tmp_path, config)
    assert trainer.active_config_id == 'cpu-fmm'
    assert trainer._train_step_compiled is not None
    # Forensics attribution: the autoprofiler context carries the id.
    assert trainer._auto_profiler.context_fn()['tuned_config'] == 'cpu-fmm'

  def test_dict_config_applies(self, tmp_path):
    config = CompileConfig(
        'from-dict',
        compiler_options={'xla_cpu_enable_fast_min_max': False}).to_dict()
    trainer = self._train(tmp_path, config)
    assert trainer.active_config_id == 'from-dict'

  def test_workload_string_cache_miss_runs_stock_compile(self, tmp_path):
    trainer = self._train(
        tmp_path, 'never_tuned_workload',
        cache_path=str(tmp_path / 'empty_cache.json'))
    assert trainer.active_config_id is None
    assert trainer._train_step_compiled is None

  def test_workload_string_cache_hit_applies_winner(self, tmp_path,
                                                    monkeypatch):
    seen_keys = []
    winner = CompileConfig(
        'cached-winner',
        compiler_options={'xla_cpu_enable_fast_min_max': True})

    def fake_lookup(self, key):
      seen_keys.append(key)
      return {'winner': winner.to_dict()}

    monkeypatch.setattr(tuning.ConfigCache, 'lookup', fake_lookup)
    trainer = self._train(tmp_path, 'qtopt_b8',
                          cache_path=str(tmp_path / 'c.json'))
    assert trainer.active_config_id == 'cached-winner'
    assert trainer._train_step_compiled is not None
    # The key the trainer looked up is the full workload/device/jax
    # tuple, so a stale winner cannot leak across chips or versions.
    (key,) = seen_keys
    assert key.startswith('qtopt_b8|')
    assert 'jax-{}'.format(jax.__version__) in key

  def test_cached_winner_with_model_overrides_runs_stock(self, tmp_path,
                                                         monkeypatch):
    # A cache-resolved winner whose measurement included layout overrides
    # cannot be reproduced at compile time: applying just its flags would
    # run an unmeasured hybrid stamped with the winner's id. The trainer
    # must refuse — stock compile, no attribution.
    winner = CompileConfig(
        'nchw-plus-flags',
        compiler_options={'xla_cpu_enable_fast_min_max': True},
        model_overrides={'conv_variant': 'nchw'})
    monkeypatch.setattr(tuning.ConfigCache, 'lookup',
                        lambda self, key: {'winner': winner.to_dict()})
    trainer = self._train(tmp_path, 'qtopt_b8',
                          cache_path=str(tmp_path / 'c.json'))
    assert trainer.active_config_id is None
    assert trainer._train_step_compiled is None

  def test_bad_cached_flag_falls_back_to_stock_compile(self, tmp_path):
    config = CompileConfig(
        'stale', compiler_options={'xla_definitely_not_a_flag': True})
    trainer = self._train(tmp_path, config)  # must still train
    assert trainer.active_config_id is None
    assert trainer._train_step_compiled is None

  def test_model_overrides_only_config_sets_id_without_aot(self, tmp_path):
    # Layout overrides apply at model construction; the trainer hook
    # records the id (attribution: the CALLER applied them) but must not
    # AOT-compile.
    config = CompileConfig('layout-only',
                           model_overrides={'conv_variant': 'nchw'})
    trainer = self._train(tmp_path, config)
    assert trainer.active_config_id == 'layout-only'
    assert trainer._train_step_compiled is None

  def test_cached_overrides_only_winner_is_not_attributed(self, tmp_path,
                                                          monkeypatch):
    # From the CACHE path the trainer cannot apply model overrides (the
    # model is already built), so an overrides-only winner took no
    # effect — stamping its id would attribute runs to a config that
    # never applied.
    winner = CompileConfig('layout-winner',
                           model_overrides={'conv_variant': 'nchw'})
    monkeypatch.setattr(
        tuning.ConfigCache, 'lookup',
        lambda self, key: {'winner': winner.to_dict()})
    trainer = self._train(tmp_path, 'wl',
                          cache_path=str(tmp_path / 'c.json'))
    assert trainer.active_config_id is None
    assert trainer._train_step_compiled is None


class TestForensicsAttribution:

  def test_report_carries_tuned_config_id(self):
    from tensor2robot_tpu.observability import forensics

    report = forensics.build_report(step=7, tuned_config='vmem-96m')
    assert report['tuned_config'] == 'vmem-96m'
    stock = forensics.build_report(step=8)
    assert stock['tuned_config'] is None
