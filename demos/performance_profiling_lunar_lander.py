"""Profiling demo — host-env boundary workload (parity:
demos/performance_flamegraph_lunar_lander.py).

Unlike performance_profiling_cartpole.py (pure on-device EvoPPO), this
profiles the OTHER regime: a gymnasium host env (LunarLander-v3) stepping in
subprocesses while DQN's jitted get_action/learn run on device — the regime
where the host<->device boundary dominates. The jax.profiler trace shows the
device gaps; StepTimer breaks out action/env/learn wall time."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.utils.profiling import profile_trace
from agilerl_tpu.utils.utils import create_population, make_vect_envs

if __name__ == "__main__":
    num_envs = 8
    env = make_vect_envs("LunarLander-v3", num_envs=num_envs)
    agent = create_population(
        "DQN", env.single_observation_space, env.single_action_space,
        population_size=1,
        net_config={"latent_dim": 64, "encoder_config": {"hidden_size": (128,)}},
        INIT_HP={"BATCH_SIZE": 128, "LR": 1e-3, "DOUBLE": True},
        seed=0,
    )[0]
    memory = ReplayBuffer(max_size=20_000)

    obs, _ = env.reset(seed=0)
    t_act = t_env = t_learn = 0.0
    steps = 512
    with profile_trace("/tmp/agilerl_tpu_trace_lander"):
        for i in range(steps):
            t0 = time.perf_counter()
            action = agent.get_action(obs, epsilon=0.5)
            t1 = time.perf_counter()
            next_obs, reward, term, trunc, _ = env.step(action)
            t2 = time.perf_counter()
            memory.add({
                "obs": obs, "action": action,
                "reward": np.asarray(reward, np.float32),
                "next_obs": next_obs,
                "done": np.asarray(term | trunc, np.float32),
            }, batched=True)
            if len(memory) >= 256 and i % 4 == 0:
                agent.learn(memory.sample(agent.batch_size))
            t3 = time.perf_counter()
            obs = next_obs
            t_act += t1 - t0
            t_env += t2 - t1
            t_learn += t3 - t2
    env.close()
    total = t_act + t_env + t_learn
    print("trace written to /tmp/agilerl_tpu_trace_lander (open in TensorBoard)")
    print(f"wall-time split over {steps} iterations "
          f"({steps * num_envs} env-steps):")
    print(f"  get_action {t_act:6.2f}s ({100 * t_act / total:4.1f}%)")
    print(f"  env.step   {t_env:6.2f}s ({100 * t_env / total:4.1f}%)  "
          f"<- the host boundary the JAX-native envs remove")
    print(f"  learn      {t_learn:6.2f}s ({100 * t_learn / total:4.1f}%)")
