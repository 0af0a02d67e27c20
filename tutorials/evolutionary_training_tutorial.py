"""Tutorial 2 — The full evolutionary loop on CartPole (pure-JAX env).

Run: python tutorials/evolutionary_training_tutorial.py
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_off_policy import train_off_policy
from agilerl_tpu.utils.utils import create_population, make_vect_envs

env = make_vect_envs("CartPole-v1", num_envs=8)   # JAX env, autoreset, vmapped
pop = create_population(
    "DQN", env.single_observation_space, env.single_action_space,
    population_size=4, INIT_HP={"BATCH_SIZE": 64, "LR": 1e-3, "LEARN_STEP": 4},
    net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
)
pop, fitnesses = train_off_policy(
    env, "CartPole-v1", "DQN", pop, ReplayBuffer(max_size=20_000),
    max_steps=20_000, evo_steps=4_000,
    tournament=TournamentSelection(2, True, 4, 1),
    mutation=Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                       activation=0.0, rl_hp=0.2),
)
print("best fitness:", max(max(f) for f in fitnesses))
