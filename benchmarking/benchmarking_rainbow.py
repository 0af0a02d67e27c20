"""Rainbow DQN benchmarking (parity: benchmarking/benchmarking_rainbow.py):
PER + n-step + C51 + noisy nets on CartPole."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

import numpy as np

from agilerl_tpu.components import MultiStepReplayBuffer, PrioritizedReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.utils.utils import create_population, make_vect_envs


def main():
    num_envs = 16
    env = make_vect_envs("CartPole-v1", num_envs=num_envs)
    pop = create_population(
        "RainbowDQN", env.single_observation_space, env.single_action_space,
        population_size=4,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        INIT_HP={"BATCH_SIZE": 64, "LR": 1e-3, "GAMMA": 0.99, "LEARN_STEP": 4,
                 "V_MIN": 0.0, "V_MAX": 500.0, "NUM_ATOMS": 51, "N_STEP": 3},
    )
    memory = PrioritizedReplayBuffer(max_size=20_000, alpha=0.6)
    n_step_memory = MultiStepReplayBuffer(max_size=20_000, n_step=3, gamma=0.99)
    tournament = TournamentSelection(2, True, 4, 1)
    mutations = Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                          activation=0.0, rl_hp=0.2)
    obs, _ = env.reset()
    start, total = time.time(), 0
    for gen in range(10):
        for agent in pop:
            for _ in range(2_000 // num_envs):
                action = agent.get_action(obs)
                next_obs, reward, term, trunc, _ = env.step(action)
                tr = {"obs": obs, "action": action,
                      "reward": np.asarray(reward, np.float32),
                      "next_obs": next_obs, "done": np.asarray(term, np.float32)}
                one_step = n_step_memory.add(tr, batched=True)
                if one_step is not None:
                    memory.add(one_step, batched=True)  # index-aligned pair
                obs = next_obs
                total += num_envs
                if len(memory) > agent.batch_size and total % (agent.learn_step * num_envs) == 0:
                    batch, idxs, weights = memory.sample(agent.batch_size)
                    n_batch = n_step_memory.sample_from_indices(idxs)
                    loss, pri = agent.learn((batch, idxs, weights, n_batch))
                    if pri is not None:
                        memory.update_priorities(idxs, pri)
            agent.test(env, max_steps=200, loop=1)
        elite, pop = tournament.select(pop)
        pop = mutations.mutation(pop)
        print(f"gen {gen}: fps {total/(time.time()-start):.0f} "
              f"elite fitness {elite.fitness[-1]:.1f}")


if __name__ == "__main__":
    main()
