"""Evolutionary DQN on CartPole (parity: demos/demo_off_policy.py in the
reference — create_population -> train_off_policy with tournament+mutations)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_off_policy import train_off_policy
from agilerl_tpu.utils.utils import create_population, make_vect_envs

if __name__ == "__main__":
    NET_CONFIG = {"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}}
    INIT_HP = {"BATCH_SIZE": 64, "LR": 1e-3, "GAMMA": 0.99, "LEARN_STEP": 4,
               "TAU": 1e-2, "DOUBLE": True, "POP_SIZE": 4}

    env = make_vect_envs("CartPole-v1", num_envs=16)
    pop = create_population(
        "DQN", env.single_observation_space, env.single_action_space,
        net_config=NET_CONFIG, INIT_HP=INIT_HP, seed=42,
    )
    memory = ReplayBuffer(max_size=20_000, seed=42)
    tournament = TournamentSelection(tournament_size=2, elitism=True,
                                     population_size=4, eval_loop=1)
    mutations = Mutations(no_mutation=0.4, architecture=0.2, new_layer_prob=0.2,
                          parameters=0.2, activation=0.0, rl_hp=0.2)

    pop, fitnesses = train_off_policy(
        env, "CartPole-v1", "DQN", pop, memory,
        max_steps=50_000, evo_steps=5_000, eval_steps=None, eval_loop=1,
        eps_start=1.0, eps_end=0.1, eps_decay=0.999,
        tournament=tournament, mutation=mutations, verbose=True,
    )
    print("best fitness:", max(max(f) for f in fitnesses))
