"""Off-policy benchmarking harness (parity: benchmarking/benchmarking_off_policy.py
— YAML-driven evolutionary run reporting env-steps/sec)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import time

import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.modules.configs import load_yaml_config
from agilerl_tpu.training.train_off_policy import train_off_policy
from agilerl_tpu.utils.utils import create_population, make_vect_envs


def main(config_path: str = "configs/training/dqn.yaml"):
    cfg = load_yaml_config(config_path)
    hp = cfg.get("INIT_HP", {})
    mut = cfg.get("MUTATION_PARAMS", {})
    net = cfg.get("NET_CONFIG", {})

    env = make_vect_envs(hp.get("ENV_NAME", "CartPole-v1"),
                         num_envs=hp.get("NUM_ENVS", 16))
    pop = create_population(
        hp.get("ALGO", "DQN"), env.single_observation_space,
        env.single_action_space, net_config=net, INIT_HP=hp,
    )
    memory = ReplayBuffer(max_size=hp.get("MEMORY_SIZE", 100_000))
    tournament = TournamentSelection(
        hp.get("TOURN_SIZE", 2), hp.get("ELITISM", True), len(pop),
        hp.get("EVAL_LOOP", 1),
    )
    mutations = Mutations(
        no_mutation=mut.get("NO_MUT", 0.4), architecture=mut.get("ARCH_MUT", 0.2),
        new_layer_prob=mut.get("NEW_LAYER", 0.2), parameters=mut.get("PARAMS_MUT", 0.2),
        activation=mut.get("ACT_MUT", 0.0), rl_hp=mut.get("RL_HP_MUT", 0.2),
        mutation_sd=mut.get("MUT_SD", 0.1),
    )
    start = time.time()
    pop, fitnesses = train_off_policy(
        env, hp.get("ENV_NAME", "CartPole-v1"), hp.get("ALGO", "DQN"), pop, memory,
        max_steps=hp.get("MAX_STEPS", 100_000), evo_steps=hp.get("EVO_STEPS", 10_000),
        eval_loop=hp.get("EVAL_LOOP", 1), tournament=tournament, mutation=mutations,
    )
    steps = sum(a.steps[-1] for a in pop)
    print(f"steps/sec: {steps / (time.time() - start):.0f}")
    print(f"best fitness: {max(max(f) for f in fitnesses):.1f}")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="configs/training/dqn.yaml")
    main(p.parse_args().config)
