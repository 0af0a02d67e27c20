"""Recurrent PPO on a MiniGrid-style partially-observable gridworld
(parity: demos/demo_on_policy_rnn_minigrid.py).

The reference drives `MiniGrid-Unlock` through gym wrappers; this demo uses a
JAX-native MiniGrid-Empty-class env — same structure (egocentric 3x3 view,
turn-left/turn-right/forward actions, minigrid's ``1 - 0.9*t/T`` success
reward), but a pure-JAX state machine so the whole rollout stays on device
(agilerl_tpu/envs/core.py design). The agent never observes its own position:
it must integrate its view history to navigate, which is what the LSTM
encoder provides. If the `minigrid` package is installed, the same agent
config also runs on the real thing via `make_vect_envs` + an obs wrapper."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from typing import NamedTuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.algorithms import PPO
from agilerl_tpu.envs import JaxVecEnv
from agilerl_tpu.envs.core import JaxEnv
from agilerl_tpu.rollouts.on_policy import collect_rollouts

SIZE = 7          # grid incl. walls; interior is 5x5
MAX_STEPS = 64
# agent directions: 0=E, 1=S, 2=W, 3=N
DIR_VEC = jnp.array([[1, 0], [0, 1], [-1, 0], [0, -1]], jnp.int32)
CORNERS = jnp.array([[1, 1], [1, 5], [5, 1], [5, 5]], jnp.int32)


class GridState(NamedTuple):
    pos: jax.Array    # [2] int32
    dir: jax.Array    # [] int32
    goal: jax.Array   # [2] int32
    t: jax.Array      # [] int32


class MiniGridEmpty(JaxEnv):
    """Egocentric 3x3 view (wall + goal channels) + direction one-hot."""

    observation_space = gym.spaces.Box(low=0.0, high=1.0, shape=(22,))
    action_space = gym.spaces.Discrete(3)  # 0=turn left, 1=turn right, 2=forward
    max_episode_steps = MAX_STEPS

    def _obs(self, state: GridState) -> jax.Array:
        dx = jnp.arange(-1, 2)
        xs = state.pos[0] + dx[None, :]          # [3, 3] grid of x coords
        ys = state.pos[1] + dx[:, None]
        wall = ((xs <= 0) | (xs >= SIZE - 1) | (ys <= 0) | (ys >= SIZE - 1))
        goal = (xs == state.goal[0]) & (ys == state.goal[1])
        view = jnp.stack([wall, goal], axis=-1).astype(jnp.float32)  # [3,3,2]
        return jnp.concatenate(
            [view.reshape(-1), jax.nn.one_hot(state.dir, 4)]
        )

    def reset_fn(self, key):
        k_goal, k_dir = jax.random.split(key)
        goal = CORNERS[jax.random.randint(k_goal, (), 0, 4)]
        state = GridState(
            pos=jnp.array([SIZE // 2, SIZE // 2], jnp.int32),
            dir=jax.random.randint(k_dir, (), 0, 4).astype(jnp.int32),
            goal=goal, t=jnp.zeros((), jnp.int32),
        )
        return state, self._obs(state)

    def step_fn(self, state, action, key):
        turn = jnp.where(action == 0, -1, jnp.where(action == 1, 1, 0))
        new_dir = (state.dir + turn) % 4
        step_vec = DIR_VEC[new_dir] * (action == 2)
        new_pos = jnp.clip(state.pos + step_vec, 1, SIZE - 2)
        t = state.t + 1
        state = GridState(new_pos, new_dir, state.goal, t)
        reached = jnp.all(new_pos == state.goal)
        reward = jnp.where(reached, 1.0 - 0.9 * t / MAX_STEPS, 0.0)
        return (state, self._obs(state), reward.astype(jnp.float32),
                reached, jnp.zeros((), bool))


if __name__ == "__main__":
    num_envs = 16
    env = JaxVecEnv(MiniGridEmpty(), num_envs=num_envs, seed=0)
    agent = PPO(
        env.single_observation_space, env.single_action_space,
        num_envs=num_envs, learn_step=256, batch_size=256, update_epochs=4,
        lr=2e-3, gamma=0.98, gae_lambda=0.95, ent_coef=0.02,
        recurrent=True, seed=0,
        net_config={"latent_dim": 64, "recurrent": True,
                    "encoder_config": {"hidden_size": 64}},
    )
    print("===== Recurrent PPO on MiniGrid-Empty (JAX-native) =====")
    for it in range(40):
        collect_rollouts(agent, env, n_steps=agent.learn_step)
        agent.learn()
        if it % 5 == 0:
            fitness = agent.test(env, max_steps=MAX_STEPS, loop=1)
            print(f"iter {it:3d}  mean episode return {fitness:6.3f} "
                  f"(reach-goal > 0.4)")
    print("final:", agent.test(env, max_steps=MAX_STEPS, loop=3))
