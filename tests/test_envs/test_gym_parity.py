"""Physics parity: the pure-JAX classic-control envs must match gymnasium
STEP-FOR-STEP (VERDICT r4 next #3) — same trajectory, rewards, and
termination step from the same initial state under the same action sequence.
Without this, any env-steps/sec headline would be measured on a different
workload than the reference's (gymnasium is the reference's env backend,
agilerl/utils/utils.py:47).

Method: reset the JAX env, inject its initial state into the UNWRAPPED
gymnasium env, and co-step both. The JAX side runs under x64 so the
comparison isolates dynamics errors from f32 accumulation (a separate case
pins the f32 path to loose tolerance over a short horizon).
"""

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.envs import classic


def _co_step(env_id, jax_env, to_gym_state, to_action, seed, horizon,
             rtol, x64):
    genv = gym.make(env_id).unwrapped
    genv.reset(seed=seed)  # allocates np_random; state overwritten below
    state, obs = jax_env.reset_fn(jax.random.PRNGKey(seed))
    if x64:
        state = jax.tree_util.tree_map(
            lambda l: jnp.asarray(l, jnp.float64), state)
    genv.state = to_gym_state(state)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed + 1)
    for t in range(horizon):
        a_raw = rng.integers(0, 2**31)
        action = to_action(a_raw, jax_env)
        key, sub = jax.random.split(key)
        state, obs, reward, terminated, truncated = jax_env.step_fn(
            state, jnp.asarray(action), sub)
        gobs, greward, gterm, gtrunc, _ = genv.step(action)
        # compare INTERNAL states: gymnasium keeps f64 state but rounds the
        # returned obs to f32, which would mask (or fake) ~1e-8 divergence
        np.testing.assert_allclose(
            to_gym_state(state), np.asarray(genv.state, np.float64),
            rtol=rtol, atol=rtol,
            err_msg=f"{env_id} state diverged at step {t}")
        np.testing.assert_allclose(
            float(reward), float(greward), rtol=rtol, atol=rtol,
            err_msg=f"{env_id} reward diverged at step {t}")
        assert bool(terminated) == bool(gterm), (
            f"{env_id} termination diverged at step {t}: "
            f"jax={bool(terminated)} gym={bool(gterm)}")
        if bool(terminated):
            return t
    return horizon


def _cartpole_gym_state(s):
    return np.array([s.x, s.x_dot, s.theta, s.theta_dot], np.float64)


def _pendulum_gym_state(s):
    return np.array([s.theta, s.theta_dot], np.float64)


def _mountaincar_gym_state(s):
    return np.array([s.position, s.velocity], np.float64)


CASES = {
    "CartPole-v1": (classic.CartPole, _cartpole_gym_state,
                    lambda r, e: int(r % 2)),
    "Pendulum-v1": (classic.Pendulum, _pendulum_gym_state,
                    lambda r, e: np.array(
                        [((r % 4001) - 2000) / 1000.0], np.float32)),
    "MountainCar-v0": (classic.MountainCar, _mountaincar_gym_state,
                       lambda r, e: int(r % 3)),
}


@pytest.mark.parametrize("env_id", sorted(CASES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_trajectory_parity_x64(env_id, seed):
    """Bitwise-grade parity (1e-9) over a full episode horizon under x64:
    the dynamics, reward function, and termination rule are the SAME
    computation as gymnasium's."""
    cls, to_state, to_action = CASES[env_id]
    with jax.enable_x64(True):
        steps = _co_step(env_id, cls(), to_state, to_action, seed,
                         horizon=200, rtol=1e-9, x64=True)
    assert steps > 0


@pytest.mark.parametrize("env_id", sorted(CASES))
def test_trajectory_parity_f32_short_horizon(env_id):
    """The production f32 path stays within float tolerance of gymnasium's
    f64 over a short horizon (accumulated single-precision drift only)."""
    cls, to_state, to_action = CASES[env_id]
    _co_step(env_id, cls(), to_state, to_action, seed=3, horizon=25,
             rtol=2e-4, x64=False)


def test_cartpole_termination_thresholds_match_gym():
    """Edge exactness: states just inside/outside gymnasium's x and theta
    limits terminate identically (the reward-shaping boundary)."""
    env = classic.CartPole()
    genv = gym.make("CartPole-v1").unwrapped
    genv.reset(seed=0)
    for x, theta in [(2.39, 0.0), (2.41, 0.0), (-2.41, 0.0),
                     (0.0, 0.2090), (0.0, 0.2095), (0.0, -0.2095)]:
        state = classic.CartPoleState(
            jnp.float32(x), jnp.float32(0.0),
            jnp.float32(theta), jnp.float32(0.0))
        _, _, _, term, _ = env.step_fn(state, jnp.int32(0),
                                       jax.random.PRNGKey(0))
        genv.state = np.array([x, 0.0, theta, 0.0])
        _, _, gterm, _, _ = genv.step(0)
        assert bool(term) == bool(gterm), (x, theta)
