"""Evolutionary MADDPG on the JAX SimpleSpread env (parity:
demos/demo_multi_agent.py over PettingZoo simple_speaker_listener)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import numpy as np

from agilerl_tpu.components import MultiAgentReplayBuffer
from agilerl_tpu.envs.multi_agent import MultiAgentJaxVecEnv, SimpleSpreadJax
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_multi_agent_off_policy import (
    train_multi_agent_off_policy,
)
from agilerl_tpu.utils.utils import create_population

if __name__ == "__main__":
    env = MultiAgentJaxVecEnv(SimpleSpreadJax(n_agents=2), num_envs=8, seed=0)
    NET_CONFIG = {"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}}
    pop = create_population(
        "MADDPG", env.observation_spaces, env.action_spaces,
        net_config=NET_CONFIG, population_size=4, seed=0,
        agent_ids=env.agent_ids,
    )
    memory = MultiAgentReplayBuffer(max_size=100_000, agent_ids=env.agent_ids)
    tournament = TournamentSelection(2, True, 4, eval_loop=1)
    mutations = Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                          activation=0.0, rl_hp=0.2)
    pop, fitnesses = train_multi_agent_off_policy(
        env, "SimpleSpread", "MADDPG", pop, memory,
        max_steps=100_000, evo_steps=10_000,
        tournament=tournament, mutation=mutations, verbose=True,
    )
