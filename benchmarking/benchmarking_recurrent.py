"""Recurrent PPO benchmarking (parity: benchmarking/benchmarking_recurrent.py)
on the memory probe env (POMDP)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import numpy as np

from agilerl_tpu.algorithms.ppo import PPO
from agilerl_tpu.envs import JaxVecEnv
from agilerl_tpu.envs.probe import MemoryEnv
from agilerl_tpu.rollouts.on_policy import collect_rollouts


def main():
    env = MemoryEnv()
    vec = JaxVecEnv(env, num_envs=16, seed=0)
    agent = PPO(
        observation_space=env.observation_space, action_space=env.action_space,
        num_envs=16, learn_step=48, seq_len=3, batch_size=128, update_epochs=4,
        lr=5e-3, gamma=0.9, recurrent=True, seed=0,
        net_config={"latent_dim": 16, "encoder_config": {"hidden_size": 32}},
    )
    for i in range(100):
        r = collect_rollouts(agent, vec)
        agent.learn()
        if i % 10 == 0:
            print(f"[{i}] mean step reward {r:.3f} (solved ~ 0.33)")


if __name__ == "__main__":
    main()
