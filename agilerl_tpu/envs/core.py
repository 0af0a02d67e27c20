"""JAX-native environment core.

The reference relies on gymnasium subprocess vector envs
(agilerl/utils/utils.py:47 make_vect_envs -> gym.vector.AsyncVectorEnv). On TPU
the host<->device boundary is the bottleneck, so first-class envs here are pure
JAX state machines: ``reset_fn(key) -> (state, obs)`` and
``step_fn(state, action, key) -> (state, obs, reward, terminated, truncated)``.
They compose three ways:

1. ``JaxVecEnv`` — gymnasium.vector-compatible host API (numpy in/out) over a
   vmapped, jitted, auto-resetting step: drop-in for the training loops.
2. ``rollout_scan`` — fully-jitted policy+env rollout via lax.scan, zero host
   round-trips: the benchmark path (>1M env-steps/sec aggregate).
3. Plain gymnasium envs still work through the same training loops (see
   agilerl_tpu/utils/utils.py make_vect_envs).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class JaxEnv:
    """Base class: subclasses define observation_space, action_space (gymnasium
    spaces), and pure reset_fn/step_fn."""

    observation_space = None
    action_space = None
    max_episode_steps: Optional[int] = None

    def reset_fn(self, key: jax.Array) -> Tuple[Any, jax.Array]:  # pragma: no cover
        raise NotImplementedError

    def step_fn(
        self, state: Any, action: jax.Array, key: jax.Array
    ) -> Tuple[Any, jax.Array, jax.Array, jax.Array, jax.Array]:  # pragma: no cover
        raise NotImplementedError


class VecState(NamedTuple):
    env_state: Any  # vmapped env state [N, ...]
    step_count: jax.Array  # [N] int32
    key: jax.Array


def make_autoreset_step(env: JaxEnv) -> Callable:
    """Build a jitted vmapped step with per-env autoreset (gymnasium semantics:
    the obs returned on the done step is the NEXT episode's initial obs)."""
    max_steps = env.max_episode_steps or 10**9

    def single_step(state, step_count, action, key):
        k_step, k_reset = jax.random.split(key)
        new_state, obs, reward, terminated, truncated = env.step_fn(state, action, k_step)
        step_count = step_count + 1
        truncated = jnp.logical_or(truncated, step_count >= max_steps)
        done = jnp.logical_or(terminated, truncated)
        reset_state, reset_obs = env.reset_fn(k_reset)
        # done is a per-env scalar here (pre-vmap), so it broadcasts cleanly
        out_state = jax.tree_util.tree_map(
            lambda r, n: jnp.where(done, r, n), reset_state, new_state
        )
        out_obs = jax.tree_util.tree_map(
            lambda r, n: jnp.where(done, r, n), reset_obs, obs
        )
        out_count = jnp.where(done, 0, step_count)
        # obs BEFORE any autoreset — needed so truncated transitions can
        # bootstrap from the true successor state, not the next episode's
        # reset obs (gymnasium's final_observation semantics)
        return out_state, out_obs, reward, terminated, truncated, out_count, obs

    @jax.jit
    def vec_step(vstate: VecState, actions: jax.Array):
        key, sub = jax.random.split(vstate.key)
        n = vstate.step_count.shape[0]
        keys = jax.random.split(sub, n)
        new_state, obs, reward, terminated, truncated, counts, final_obs = jax.vmap(
            single_step
        )(vstate.env_state, vstate.step_count, actions, keys)
        return VecState(new_state, counts, key), obs, reward, terminated, truncated, final_obs

    return vec_step


def _to_np(tree):
    """Device->host conversion that preserves Dict/Tuple obs pytrees
    (np.asarray on a dict would yield a useless object array)."""
    return jax.tree_util.tree_map(np.asarray, tree)


class JaxVecEnv:
    """gymnasium.vector-style host API over a JAX-native env."""

    def __init__(self, env: JaxEnv, num_envs: int = 1, seed: int = 0):
        self.env = env
        self.num_envs = int(num_envs)
        self.observation_space = env.observation_space
        self.action_space = env.action_space
        self.single_observation_space = env.observation_space
        self.single_action_space = env.action_space
        self._step = make_autoreset_step(env)
        self._reset = jax.jit(jax.vmap(env.reset_fn))
        self._key = jax.random.PRNGKey(seed)
        self._state: Optional[VecState] = None

    def reset(self, seed: Optional[int] = None, options=None):
        if seed is not None:
            self._key = jax.random.PRNGKey(seed)
        self._key, sub = jax.random.split(self._key)
        keys = jax.random.split(sub, self.num_envs)
        env_state, obs = self._reset(keys)
        self._state = VecState(
            env_state=env_state,
            step_count=jnp.zeros(self.num_envs, jnp.int32),
            key=self._key,
        )
        return _to_np(obs), {}

    def step(self, actions):
        self._state, obs, reward, terminated, truncated, final_obs = self._step(
            self._state, jnp.asarray(actions)
        )
        return (
            _to_np(obs),
            np.asarray(reward),
            np.asarray(terminated),
            np.asarray(truncated),
            {"final_obs": _to_np(final_obs)},
        )

    def close(self):
        pass


def rollout_scan(
    env: JaxEnv,
    policy_fn: Callable[[Any, Any, jax.Array], jax.Array],
    policy_params: Any,
    num_envs: int,
    num_steps: int,
    key: jax.Array,
):
    """Fully-jitted rollout: lax.scan over vmapped env steps with autoreset.

    policy_fn(params, obs_batch, key) -> actions. Returns (trajectory dict with
    leaves [T, N, ...], final carry). This is the zero-host-sync path used by
    the pure-device training loops.
    """
    vec_step = make_autoreset_step(env)
    reset = jax.vmap(env.reset_fn)

    def init(key):
        k1, k2 = jax.random.split(key)
        env_state, obs = reset(jax.random.split(k1, num_envs))
        vstate = VecState(env_state, jnp.zeros(num_envs, jnp.int32), k2)
        return vstate, obs

    def body(carry, _):
        vstate, obs, key = carry
        key, k_act = jax.random.split(key)
        actions = policy_fn(policy_params, obs, k_act)
        vstate, next_obs, reward, terminated, truncated, _final = vec_step(vstate, actions)
        out = {
            "obs": obs,
            "action": actions,
            "reward": reward,
            "done": jnp.logical_or(terminated, truncated).astype(jnp.float32),
        }
        return (vstate, next_obs, key), out

    k_init, k_run = jax.random.split(key)
    vstate, obs = init(k_init)
    (vstate, last_obs, _), traj = jax.lax.scan(
        body, (vstate, obs, k_run), None, length=num_steps
    )
    return traj, (vstate, last_obs)

