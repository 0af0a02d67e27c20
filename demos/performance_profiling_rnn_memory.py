"""Profiling demo — recurrent PPO on the on-device memory task (parity:
demos/performance_flamegraph_rnn_memory.py).

Same workload as demo_on_policy_rnn_memory.py but instrumented: JAX-native env
(no host boundary) + LSTM PPO, traced with jax.profiler. Compare against
performance_profiling_lander_rnn.py to see how much the host env costs."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

from agilerl_tpu.algorithms import PPO
from agilerl_tpu.envs import JaxVecEnv
from agilerl_tpu.envs.probe import MemoryEnv
from agilerl_tpu.rollouts.on_policy import collect_rollouts
from agilerl_tpu.utils.profiling import StepTimer, profile_trace

if __name__ == "__main__":
    num_envs = 16
    env = JaxVecEnv(MemoryEnv(), num_envs=num_envs, seed=0)
    agent = PPO(
        env.single_observation_space, env.single_action_space,
        num_envs=num_envs, learn_step=128, batch_size=128, update_epochs=2,
        lr=3e-3, recurrent=True, seed=0,
        net_config={"latent_dim": 32, "recurrent": True,
                    "encoder_config": {"hidden_size": 32}},
    )
    collect_rollouts(agent, env, n_steps=agent.learn_step)  # warm up
    agent.learn()

    timer = StepTimer()
    timer.tick()
    t0 = time.perf_counter()
    with profile_trace("/tmp/agilerl_tpu_trace_rnn_memory"):
        for _ in range(5):
            collect_rollouts(agent, env, n_steps=agent.learn_step)
            agent.learn()
            timer.tick()
    dt = time.perf_counter() - t0
    print("trace written to /tmp/agilerl_tpu_trace_rnn_memory")
    print(f"mean iteration {timer.mean_step_time * 1e3:.1f} ms; "
          f"{5 * agent.learn_step * num_envs / dt:,.0f} env-steps/sec "
          f"(rollout+BPTT learn)")
