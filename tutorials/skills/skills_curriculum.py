"""Tutorial — curriculum learning with Skill wrappers
(parity: tutorials/skills/agilerl_skills_curriculum.py — shaped-reward skills
train in sequence before the full task)."""

# allow running directly as `python tutorials/<dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.envs import CartPole, JaxVecEnv
from agilerl_tpu.training.train_off_policy import train_off_policy
from agilerl_tpu.utils.utils import create_population
from agilerl_tpu.wrappers import Skill


class StabilizeSkill(Skill):
    """Reward keeping the pole near vertical (ignore cart position)."""

    def skill_reward(self, obs, reward, terminated, truncated, info):
        angle = np.asarray(obs)[..., 2]
        return obs, 1.0 - np.abs(angle) * 10.0, terminated, truncated, info


class CenterSkill(Skill):
    """Reward keeping the cart near the centre of the track."""

    def skill_reward(self, obs, reward, terminated, truncated, info):
        x = np.asarray(obs)[..., 0]
        return obs, 1.0 - np.abs(x), terminated, truncated, info


if __name__ == "__main__":
    base = JaxVecEnv(CartPole(), num_envs=8, seed=0)
    pop = create_population(
        "DQN", base.single_observation_space, base.single_action_space,
        population_size=1, seed=42,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        INIT_HP={"BATCH_SIZE": 64, "LR": 1e-3, "LEARN_STEP": 8},
    )
    memory = ReplayBuffer(max_size=50_000)
    # curriculum: each skill shapes the reward for a phase, then the full task
    for phase, env in (("stabilize", StabilizeSkill(base)),
                       ("center", CenterSkill(base)),
                       ("full", base)):
        pop, fitnesses = train_off_policy(
            env, f"cartpole-{phase}", "DQN", pop, memory,
            max_steps=pop[0].steps[-1] + 8_000, evo_steps=2_000, verbose=False,
        )
        print(f"{phase}: fitness {fitnesses[0][-1]:.1f}")
