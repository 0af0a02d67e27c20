"""Offline RL demo: CQN on a random-policy CartPole dataset
(parity: demos/demo_offline.py — the bundled h5 dataset is replaced by
on-demand collection, utils/minari_utils.collect_offline_dataset)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_offline import train_offline
from agilerl_tpu.utils.minari_utils import collect_offline_dataset
from agilerl_tpu.utils.utils import create_population, make_vect_envs

if __name__ == "__main__":
    env = make_vect_envs("CartPole-v1", num_envs=8)
    dataset = collect_offline_dataset(env, steps=20_000, epsilon=1.0)
    pop = create_population(
        "CQN", env.single_observation_space, env.single_action_space,
        population_size=4,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        INIT_HP={"BATCH_SIZE": 128, "LR": 1e-3, "LEARN_STEP": 1},
        seed=42,
    )
    memory = ReplayBuffer(max_size=len(dataset["rewards"]))
    tournament = TournamentSelection(2, True, 4, 1)
    mutations = Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                          activation=0.0, rl_hp=0.2)
    pop, fitnesses = train_offline(
        env, "CartPole-v1", dataset, "CQN", pop, memory,
        max_steps=30_000, evo_steps=3_000,
        tournament=tournament, mutation=mutations,
    )
    print("best fitness:", max(max(f) for f in fitnesses))
