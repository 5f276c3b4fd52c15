"""The clock arithmetic on synthetic timings: whole-step rates, the window's
end, the process's age, seeds."""

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark.harness import common, timing


class TestWholeStepRate:

  def test_rate_is_examples_of_whole_steps_over_the_synced_time(self):
    assert timing.whole_step_rate(86, 64, 10.0, 40.1, 4) == pytest.approx(
        86 * 64 / 30.1 / 4)

  @pytest.mark.parametrize('extra_steps', [0, 1, 2])
  def test_one_step_more_or_less_does_not_move_the_rate(self, extra_steps):
    """The fault of 'steps finished in a fixed window': with steps of
    0.347 s a 30 s window holds 86 or 87 of them and the rate moved by
    1.2%. Over whole steps between two syncs it does not move at all."""
    step_s = 0.347
    steps = 86 + extra_steps
    rate = timing.whole_step_rate(steps, 64, 5.0, 5.0 + steps * step_s, 1)
    assert rate == pytest.approx(64 / step_s, rel=1e-12)

  @pytest.mark.parametrize('steps,first,last,chips', [
      (0, 0.0, 1.0, 1), (5, 1.0, 1.0, 1), (5, 2.0, 1.0, 1), (5, 0.0, 1.0, 0)])
  def test_nothing_timed_is_an_error_not_a_zero(self, steps, first, last,
                                                chips):
    with pytest.raises(ValueError):
      timing.whole_step_rate(steps, 64, first, last, chips)

  def test_window_closes_at_the_first_boundary_at_or_after_seconds(self):
    assert not timing.window_closed(29.99, 0.0, 30.0)
    assert timing.window_closed(30.0, 0.0, 30.0)
    assert timing.window_closed(130.2, 100.0, 30.0)


class TestProcessAgeAndSeeds:

  def test_process_age_is_positive_and_small(self):
    assert 0.0 < timing.process_age_s() < 3600.0

  @pytest.mark.parametrize('seed', [0, 7, 2**31 + 11, 2**33 + 5])
  def test_any_whole_number_gives_a_31_bit_seed(self, seed):
    value = common.seed31(seed)
    assert 0 <= value < 2**31
    assert value == common.seed31(seed)
    assert value != common.seed31(seed, salt=1)
