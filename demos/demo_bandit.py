"""Contextual-bandit demo: NeuralUCB on the iris labelled-data bandit
(parity: demos/demo_bandit.py — BanditEnv wraps a classification dataset;
reward 1 for the correct arm)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_bandits import train_bandits
from agilerl_tpu.utils.utils import create_population
from agilerl_tpu.wrappers import BanditEnv

if __name__ == "__main__":
    # synthetic 3-class separable dataset (sklearn-free iris stand-in)
    rng = np.random.default_rng(0)
    n, d, k = 300, 4, 3
    centers = rng.normal(size=(k, d)) * 2.0
    labels = rng.integers(0, k, n)
    features = centers[labels] + rng.normal(size=(n, d)) * 0.5
    env = BanditEnv(features, labels)

    pop = create_population(
        "NeuralUCB", env.observation_space, env.action_space,
        population_size=4,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        INIT_HP={"BATCH_SIZE": 64, "LR": 1e-3, "LAMBDA": 1.0, "REG": 0.000625,
                 "LEARN_STEP": 2},
        seed=42,
    )
    memory = ReplayBuffer(max_size=10_000)
    tournament = TournamentSelection(2, True, 4, 1)
    mutations = Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                          activation=0.0, rl_hp=0.2)
    pop, fitnesses = train_bandits(
        env, "iris-bandit", "NeuralUCB", pop, memory,
        max_steps=8_000, evo_steps=1_000,
        tournament=tournament, mutation=mutations,
    )
    print("best regret-free fitness:", max(max(f) for f in fitnesses))
