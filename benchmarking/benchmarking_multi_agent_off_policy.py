"""Multi-agent off-policy benchmarking
(parity: benchmarking/benchmarking_multi_agent_off_policy.py)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

from agilerl_tpu.components import MultiAgentReplayBuffer
from agilerl_tpu.envs.multi_agent import MultiAgentJaxVecEnv, SimpleSpreadJax
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_multi_agent_off_policy import (
    train_multi_agent_off_policy,
)
from agilerl_tpu.utils.utils import create_population


def main():
    env = MultiAgentJaxVecEnv(SimpleSpreadJax(n_agents=2), num_envs=8, seed=0)
    pop = create_population(
        "MADDPG", env.observation_spaces, env.action_spaces,
        agent_ids=env.agent_ids, population_size=4,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
    )
    memory = MultiAgentReplayBuffer(max_size=100_000, agent_ids=env.agent_ids)
    start = time.time()
    pop, fitnesses = train_multi_agent_off_policy(
        env, "SimpleSpread", "MADDPG", pop, memory,
        max_steps=50_000, evo_steps=5_000,
        tournament=TournamentSelection(2, True, 4, 1),
        mutation=Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                           activation=0.0, rl_hp=0.2),
    )
    steps = sum(a.steps[-1] for a in pop)
    print(f"steps/sec: {steps / (time.time() - start):.0f}")


if __name__ == "__main__":
    main()
