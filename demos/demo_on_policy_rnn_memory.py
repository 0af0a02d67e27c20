"""Recurrent PPO on a memory task that REQUIRES memory
(parity: demos/demo_on_policy_rnn_memory.py — the cue is shown only at t=0;
a flat PPO cannot beat chance, the LSTM-encoder PPO can)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from agilerl_tpu.algorithms import PPO
from agilerl_tpu.envs import JaxVecEnv
from agilerl_tpu.envs.probe import MemoryEnv
from agilerl_tpu.rollouts.on_policy import collect_rollouts

if __name__ == "__main__":
    env = JaxVecEnv(MemoryEnv(), num_envs=16, seed=0)
    agent = PPO(
        env.single_observation_space, env.single_action_space,
        num_envs=16, learn_step=64, batch_size=128, update_epochs=4,
        lr=3e-3, gamma=0.9, ent_coef=0.01, seed=0, recurrent=True,
        net_config={"latent_dim": 32, "recurrent": True,
                    "encoder_config": {"hidden_size": 32}},
    )
    for it in range(80):
        collect_rollouts(agent, env, n_steps=agent.learn_step)
        agent.learn()
        if it % 10 == 0:
            fitness = agent.test(env, max_steps=64, loop=1)
            print(f"iter {it:3d} fitness {fitness:+.3f}  (chance 0.0, max +1.0)")
    final = agent.test(env, max_steps=64, loop=3)
    print("final fitness:", final)
