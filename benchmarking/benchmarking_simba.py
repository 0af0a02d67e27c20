"""SimBa-encoder benchmarking (parity: benchmarking/benchmarking_simba.py)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_on_policy import train_on_policy
from agilerl_tpu.utils.utils import create_population, make_vect_envs


def main():
    num_envs = 16
    env = make_vect_envs("CartPole-v1", num_envs=num_envs)
    pop = create_population(
        "PPO", env.single_observation_space, env.single_action_space,
        population_size=2, num_envs=num_envs, learn_step=128,
        net_config={"latent_dim": 64, "simba": True,
                    "encoder_config": {"hidden_size": 128, "num_blocks": 2}},
    )
    pop, fitnesses = train_on_policy(
        env, "CartPole-v1", "PPO", pop,
        max_steps=100_000, evo_steps=10_240,
        tournament=TournamentSelection(2, True, 2, 1),
        mutation=Mutations(no_mutation=0.6, architecture=0.2, parameters=0.0,
                           activation=0.0, rl_hp=0.2),
    )
    print(f"best fitness: {max(max(f) for f in fitnesses):.1f}")


if __name__ == "__main__":
    main()
