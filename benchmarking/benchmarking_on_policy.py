"""On-policy benchmarking (parity: benchmarking/benchmarking_on_policy.py)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import time

import numpy as np

from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.modules.configs import load_yaml_config
from agilerl_tpu.training.train_on_policy import train_on_policy
from agilerl_tpu.utils.utils import create_population, make_vect_envs


def main(config_path="configs/training/ppo.yaml"):
    cfg = load_yaml_config(config_path)
    hp, mut, net = cfg.get("INIT_HP", {}), cfg.get("MUTATION_PARAMS", {}), cfg.get("NET_CONFIG", {})
    num_envs = hp.get("NUM_ENVS", 16)
    env = make_vect_envs(hp.get("ENV_NAME", "CartPole-v1"), num_envs=num_envs)
    pop = create_population(
        "PPO", env.single_observation_space, env.single_action_space,
        net_config=net, INIT_HP=hp, num_envs=num_envs,
    )
    tournament = TournamentSelection(2, True, len(pop), 1)
    mutations = Mutations(no_mutation=mut.get("NO_MUT", 0.4),
                          architecture=mut.get("ARCH_MUT", 0.2),
                          parameters=mut.get("PARAMS_MUT", 0.2),
                          activation=0.0, rl_hp=mut.get("RL_HP_MUT", 0.2))
    start = time.time()
    pop, fitnesses = train_on_policy(
        env, hp.get("ENV_NAME", "CartPole-v1"), "PPO", pop,
        max_steps=hp.get("MAX_STEPS", 100_000), evo_steps=hp.get("EVO_STEPS", 10_240),
        tournament=tournament, mutation=mutations,
    )
    steps = sum(a.steps[-1] for a in pop)
    print(f"steps/sec: {steps / (time.time() - start):.0f}")
    print(f"best fitness: {max(max(f) for f in fitnesses):.1f}")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="configs/training/ppo.yaml")
    main(p.parse_args().config)
