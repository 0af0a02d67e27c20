"""ResNet-encoder benchmarking (parity: benchmarking/benchmarking_resnet.py —
evolutionary DQN with the EvolvableResNet image encoder on the on-device
rendered VisualCartPole)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.training.train_off_policy import train_off_policy
from agilerl_tpu.utils.utils import create_population, make_vect_envs


def main(max_steps: int = 20_000, pop_size: int = 2):
    env = make_vect_envs("VisualCartPole-v0", num_envs=8)
    pop = create_population(
        "DQN", env.single_observation_space, env.single_action_space,
        population_size=pop_size,
        net_config={"latent_dim": 64, "resnet": True,
                    "encoder_config": {"channel_size": 16, "num_blocks": 1}},
        INIT_HP={"BATCH_SIZE": 32, "LR": 1e-3, "LEARN_STEP": 8},
        seed=0,
    )
    assert pop[0].actor.config.encoder_kind == "resnet"
    memory = ReplayBuffer(max_size=10_000)
    start = time.time()
    pop, fitnesses = train_off_policy(
        env, "VisualCartPole-v0", "DQN", pop, memory,
        max_steps=max_steps, evo_steps=max_steps // 4,
        tournament=TournamentSelection(2, True, pop_size, 1),
        mutation=Mutations(no_mutation=0.4, architecture=0.2, parameters=0.2,
                           activation=0.0, rl_hp=0.2),
        verbose=False,
    )
    steps = sum(a.steps[-1] for a in pop)
    print(f"resnet-dqn steps/sec: {steps / (time.time() - start):.0f}; "
          f"best fitness {max(max(f) for f in fitnesses):.1f}")


if __name__ == "__main__":
    main()
