"""Tutorial — NeuralTS contextual bandit on a labelled dataset
(parity: tutorials/bandits/neural_ts.py — PenDigits is replaced by a
synthetic separable classification task; swap in any (features, labels))."""

# allow running directly as `python tutorials/<dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_bandits import train_bandits
from agilerl_tpu.utils.utils import create_population
from agilerl_tpu.wrappers import BanditEnv

if __name__ == "__main__":
    rng = np.random.default_rng(0)
    n, d, k = 600, 8, 4
    centers = rng.normal(size=(k, d)) * 2.0
    labels = rng.integers(0, k, n)
    features = centers[labels] + rng.normal(size=(n, d)) * 0.5
    env = BanditEnv(features, labels)

    pop = create_population(
        "NeuralTS", env.observation_space, env.action_space,
        population_size=4, seed=42,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        INIT_HP={"BATCH_SIZE": 64, "LR": 1e-3, "LAMBDA": 1.0, "REG": 0.000625,
                 "LEARN_STEP": 2},
    )
    pop, fitnesses = train_bandits(
        env, "synthetic-bandit", "NeuralTS", pop, ReplayBuffer(max_size=10_000),
        max_steps=6_000, episode_steps=100, evo_steps=1_000,
        tournament=TournamentSelection(2, True, 4, 1),
        mutation=Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                           activation=0.0, rl_hp=0.2),
    )
    print("best regret-free fitness:", max(max(f) for f in fitnesses))
