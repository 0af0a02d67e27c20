"""Multi-agent on-policy (IPPO) benchmarking
(parity: benchmarking/benchmarking_multi_agent_on_policy.py)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

from agilerl_tpu.envs.multi_agent import MultiAgentJaxVecEnv, SimpleSpreadJax
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_multi_agent_on_policy import (
    train_multi_agent_on_policy,
)
from agilerl_tpu.utils.utils import create_population


def main(max_steps: int = 50_000, pop_size: int = 4):
    env = MultiAgentJaxVecEnv(SimpleSpreadJax(n_agents=2), num_envs=8, seed=0)
    pop = create_population(
        "IPPO", env.observation_spaces, env.action_spaces,
        agent_ids=env.agent_ids, population_size=pop_size,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        num_envs=8, learn_step=128, batch_size=128, update_epochs=4,
    )
    start = time.time()
    pop, fitnesses = train_multi_agent_on_policy(
        env, "SimpleSpread", "IPPO", pop,
        max_steps=max_steps, evo_steps=max_steps // 4,
        tournament=TournamentSelection(2, True, pop_size, 1),
        mutation=Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                           activation=0.0, rl_hp=0.2),
    )
    steps = sum(a.steps[-1] for a in pop)
    print(f"ippo steps/sec: {steps / (time.time() - start):.0f}; "
          f"best fitness {max(max(f) for f in fitnesses):.1f}")


if __name__ == "__main__":
    main()
