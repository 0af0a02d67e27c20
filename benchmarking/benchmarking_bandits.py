"""Contextual-bandit benchmarking (parity: benchmarking/benchmarking_bandits.py)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_bandits import train_bandits
from agilerl_tpu.utils.utils import create_population
from agilerl_tpu.wrappers import BanditEnv
from gymnasium import spaces


def main():
    rng = np.random.default_rng(0)
    features = rng.normal(size=(512, 8)).astype(np.float32)
    targets = (features[:, :4].sum(1) > 0).astype(np.int64)
    env = BanditEnv(features, targets)
    obs_space = spaces.Box(-np.inf, np.inf, (env.context_dim,))
    act_space = spaces.Discrete(env.arms)
    pop = create_population(
        "NeuralUCB", obs_space, act_space, population_size=2,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
    )
    memory = ReplayBuffer(max_size=10_000)
    pop, fitnesses = train_bandits(
        env, "Bandit", "NeuralUCB", pop, memory,
        max_steps=4_000, evo_steps=500,
        tournament=TournamentSelection(2, True, 2, 1),
        mutation=Mutations(no_mutation=0.5, architecture=0.2, parameters=0.1,
                           activation=0.0, rl_hp=0.2),
    )
    print(f"final reward rate: {max(f[-1] for f in fitnesses):.3f}")


if __name__ == "__main__":
    main()
