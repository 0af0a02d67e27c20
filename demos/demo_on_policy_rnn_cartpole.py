"""Recurrent PPO on velocity-masked CartPole
(parity: demos/demo_on_policy_rnn_cartpole.py — the reference masks velocities
so the task becomes a POMDP: a flat MLP policy plateaus, an LSTM policy that
integrates positions over time solves it).

Toggle RECURRENT to compare; both run the same trainer and rollout collector
(agilerl_tpu/rollouts/on_policy.py branches on agent.recurrent)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import gymnasium as gym
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.algorithms import PPO
from agilerl_tpu.envs import CartPole, JaxVecEnv
from agilerl_tpu.rollouts.on_policy import collect_rollouts

RECURRENT = True  # False -> flat MLP PPO on the same POMDP (plateaus)


class MaskedVelocityCartPole(CartPole):
    """CartPole observing only (x, theta) — velocities hidden (POMDP)."""

    observation_space = gym.spaces.Box(
        low=np.array([-4.8, -0.418], np.float32),
        high=np.array([4.8, 0.418], np.float32),
    )

    def reset_fn(self, key):
        state, obs = super().reset_fn(key)
        return state, obs[jnp.array([0, 2])]

    def step_fn(self, state, action, key):
        state, obs, reward, terminated, truncated = super().step_fn(
            state, action, key
        )
        return state, obs[jnp.array([0, 2])], reward, terminated, truncated


if __name__ == "__main__":
    num_envs = 16
    env = JaxVecEnv(MaskedVelocityCartPole(), num_envs=num_envs, seed=0)
    net_config = {"latent_dim": 64, "recurrent": RECURRENT}
    if RECURRENT:
        net_config["encoder_config"] = {"hidden_size": 64}
    else:
        net_config["encoder_config"] = {"hidden_size": (64,)}
    agent = PPO(
        env.single_observation_space, env.single_action_space,
        num_envs=num_envs, learn_step=256, batch_size=256, update_epochs=4,
        lr=2e-3, gamma=0.99, gae_lambda=0.95, ent_coef=0.01,
        recurrent=RECURRENT, net_config=net_config, seed=0,
    )
    print(f"===== Recurrent PPO on velocity-masked CartPole "
          f"(recurrent={RECURRENT}) =====")
    for it in range(40):
        collect_rollouts(agent, env, n_steps=agent.learn_step)
        agent.learn()
        if it % 5 == 0:
            fitness = agent.test(env, max_steps=500, loop=1)
            print(f"iter {it:3d}  fitness {fitness:7.1f}  (solved ~500)")
    print("final fitness:", agent.test(env, max_steps=500, loop=3))
