"""Tutorial — MADDPG on a cooperative multi-agent env
(parity: tutorials/pettingzoo/maddpg.py — space_invaders/simple_speaker
become the pure-JAX SimpleSpread so rollouts run under jit; any PettingZoo
parallel env works via vector.PettingZooVecEnv)."""

# allow running directly as `python tutorials/<dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

import numpy as np

from agilerl_tpu.components import MultiAgentReplayBuffer
from agilerl_tpu.envs.multi_agent import MultiAgentJaxVecEnv, SimpleSpreadJax
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_multi_agent_off_policy import (
    train_multi_agent_off_policy,
)
from agilerl_tpu.utils.utils import create_population

if __name__ == "__main__":
    env = MultiAgentJaxVecEnv(SimpleSpreadJax(n_agents=3), num_envs=8, seed=0)
    pop = create_population(
        "MADDPG", env.observation_spaces, env.action_spaces,
        agent_ids=env.agent_ids, population_size=4, seed=42,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        INIT_HP={"BATCH_SIZE": 64, "LEARN_STEP": 8},
    )
    memory = MultiAgentReplayBuffer(max_size=100_000, agent_ids=env.agent_ids)
    pop, fitnesses = train_multi_agent_off_policy(
        env, "simple-spread", "MADDPG", pop, memory,
        max_steps=20_000, evo_steps=2_000,
        tournament=TournamentSelection(2, True, 4, 1),
        mutation=Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                           activation=0.0, rl_hp=0.2),
    )
    print("best fitness:", max(max(f) for f in fitnesses))
