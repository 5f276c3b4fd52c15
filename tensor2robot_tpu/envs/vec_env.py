"""The jittable vectorized environment contract.

A ``VecEnv`` is a *pure function pair* over explicit state — no hidden
mutation, no host round trips — so an actor can fuse "select action"
and "step every environment" into ONE jitted XLA program (rl/loop.py)
and sweep thousands of env slots per device step:

  * ``reset(rng) -> (state, obs)`` — ``state`` is a pytree whose leaves
    carry a leading ``num_envs`` dim; ``obs`` is a flat
    ``{name: array}`` dict, also batch-leading.
  * ``step(state, action) -> VecStep(state, obs, reward, done, info)``
    — advances EVERY slot one step and **auto-resets** finished slots:
    ``obs`` is what the policy should act on next (the fresh episode's
    first observation wherever ``done``), while ``info['next_obs']`` is
    the PRE-reset successor observation — the one a replay transition
    must record, because timeout transitions bootstrap through the time
    limit (``done=0`` on the wire) and therefore consume their true
    successor.

``done`` marks "this episode ended" (terminal OR timeout);
``info['terminal']`` marks "the environment itself terminated" (for the
grasping MDP: a grasp was attempted). Only ``terminal`` is written to
replay as ``done`` — the bootstrap-through-timeout convention of
research/qtopt/grasping_sim.py, carried into the vectorized world.

Both functions must be traceable (jit/vmap-safe) and totally
deterministic given ``(state, action)`` — all randomness flows through
per-slot PRNG keys carried IN the state, which is what makes the acting
step's jit cache hold exactly one executable per signature (the
zero-request-time-compile invariant tests/test_rl_loop.py asserts).
"""

from __future__ import annotations

import abc
from typing import Any, Dict, NamedTuple, Tuple


class VecStep(NamedTuple):
  """One vectorized transition; every field is batch-leading.

  Attributes:
    state: the env state pytree AFTER auto-reset.
    obs: observation to act on next (post-auto-reset).
    reward: [B] float32 immediate rewards.
    done: [B] bool — episode ended this step (terminal or timeout).
    info: extras; the contract requires ``terminal`` ([B] bool, the
      env-terminal flag replay writes as ``done``) and ``next_obs``
      (the pre-reset successor observation dict).
  """

  state: Any
  obs: Dict[str, Any]
  reward: Any
  done: Any
  info: Dict[str, Any]


class VecEnv(abc.ABC):
  """Abstract jittable vectorized environment (module docstring)."""

  @property
  @abc.abstractmethod
  def num_envs(self) -> int:
    """B, the number of env slots advanced per step call."""

  @abc.abstractmethod
  def reset(self, rng) -> Tuple[Any, Dict[str, Any]]:
    """Fresh episodes in every slot; returns ``(state, obs)``."""

  @abc.abstractmethod
  def step(self, state, action) -> VecStep:
    """Advances every slot one step, auto-resetting finished ones."""
