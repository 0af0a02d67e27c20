"""Evolutionary PPO on CartPole (parity: demos/demo_on_policy.py)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_on_policy import train_on_policy
from agilerl_tpu.utils.utils import create_population, make_vect_envs

if __name__ == "__main__":
    NET_CONFIG = {"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}}
    NUM_ENVS = 16

    env = make_vect_envs("CartPole-v1", num_envs=NUM_ENVS)
    pop = create_population(
        "PPO", env.single_observation_space, env.single_action_space,
        net_config=NET_CONFIG, population_size=4, num_envs=NUM_ENVS,
        learn_step=128, batch_size=256, lr=3e-4, seed=42,
    )
    tournament = TournamentSelection(2, True, 4, eval_loop=1)
    mutations = Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                          activation=0.0, rl_hp=0.2)
    pop, fitnesses = train_on_policy(
        env, "CartPole-v1", "PPO", pop,
        max_steps=100_000, evo_steps=10_240,
        tournament=tournament, mutation=mutations, verbose=True,
    )
    print("best fitness:", max(max(f) for f in fitnesses))
