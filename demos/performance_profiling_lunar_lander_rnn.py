"""Profiling demo — recurrent PPO rollout+BPTT learn (parity:
demos/performance_flamegraph_lunar_lander_rnn.py).

Profiles the two phases of recurrent on-policy training separately: hidden-
state-carrying rollout collection and the BPTT sequence learn. The trace shows
the scan-structured LSTM forward; the printed split shows where a recurrent
workload actually spends its time."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

from agilerl_tpu.algorithms import PPO
from agilerl_tpu.rollouts.on_policy import collect_rollouts
from agilerl_tpu.utils.profiling import profile_trace
from agilerl_tpu.utils.utils import make_vect_envs

if __name__ == "__main__":
    num_envs = 8
    env = make_vect_envs("LunarLander-v3", num_envs=num_envs)
    agent = PPO(
        env.single_observation_space, env.single_action_space,
        num_envs=num_envs, learn_step=256, batch_size=256, update_epochs=2,
        lr=3e-4, recurrent=True, seed=0,
        net_config={"latent_dim": 64, "recurrent": True,
                    "encoder_config": {"hidden_size": 64}},
    )
    # warm up the jit caches outside the trace
    collect_rollouts(agent, env, n_steps=agent.learn_step)
    agent.learn()

    t_roll = t_learn = 0.0
    with profile_trace("/tmp/agilerl_tpu_trace_lander_rnn"):
        for _ in range(3):
            t0 = time.perf_counter()
            collect_rollouts(agent, env, n_steps=agent.learn_step)
            t1 = time.perf_counter()
            agent.learn()
            t2 = time.perf_counter()
            t_roll += t1 - t0
            t_learn += t2 - t1
    env.close()
    total = t_roll + t_learn
    print("trace written to /tmp/agilerl_tpu_trace_lander_rnn")
    print(f"recurrent rollout {t_roll:6.2f}s ({100 * t_roll / total:4.1f}%) | "
          f"BPTT learn {t_learn:6.2f}s ({100 * t_learn / total:4.1f}%)")
