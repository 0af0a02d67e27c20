"""Custom networks demo (parity: demos/demo_custom_network.py).

Two ways to bring your own architecture:

1. **Native**: define a custom evolvable encoder — a frozen config dataclass +
   ``init_params``/``apply`` + ``@mutation`` methods — register it in
   ``ENCODER_TYPES``, and every algorithm, tournament, and mutation in the
   framework can drive it (the metaclass discovers the mutation methods; no
   other wiring). This replaces subclassing ``nn.Module``: modules here are
   (config, params-pytree) pairs so they stay jit/vmap-compatible.

2. **Torch import**: ``MakeEvolvable(network, input_tensor)`` introspects an
   existing ``torch.nn`` model (as the reference's deprecated wrapper does),
   rebuilds it as an evolvable JAX module, and imports the trained weights.
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.modules import layers as L
from agilerl_tpu.modules.base import EvolvableModule, config_replace, mutation
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks.base import ENCODER_TYPES, NetworkConfig
from agilerl_tpu.training.train_off_policy import train_off_policy
from agilerl_tpu.typing import MutationType
from agilerl_tpu.utils.utils import create_population, make_vect_envs


# ----------------------------------------------------------------------- #
# 1. a custom evolvable encoder: gated residual MLP
# ----------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class GatedMLPConfig:
    num_inputs: int
    num_outputs: int
    hidden_size: int = 64
    num_blocks: int = 1
    min_blocks: int = 1
    max_blocks: int = 3


class EvolvableGatedMLP(EvolvableModule):
    """x -> proj -> [h + sigmoid(gate(h)) * fc(h)] x blocks -> out."""

    Config = GatedMLPConfig

    def __init__(self, key=None, config: Optional[GatedMLPConfig] = None, **kw):
        if config is None:
            config = GatedMLPConfig(**kw)
        if key is None:
            key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
        super().__init__(config, key)

    @staticmethod
    def init_params(key: jax.Array, config: GatedMLPConfig) -> Dict:
        ks = jax.random.split(key, 2 * config.num_blocks + 2)
        params = {"proj": L.dense_init(ks[0], config.num_inputs, config.hidden_size)}
        for i in range(config.num_blocks):
            params[f"block_{i}"] = {
                "gate": L.dense_init(ks[2 * i + 1], config.hidden_size, config.hidden_size),
                "fc": L.dense_init(ks[2 * i + 2], config.hidden_size, config.hidden_size),
            }
        params["out"] = L.dense_init(ks[-1], config.hidden_size, config.num_outputs)
        return params

    @staticmethod
    def apply(config: GatedMLPConfig, params: Dict, x: jax.Array, **_) -> jax.Array:
        h = jax.nn.relu(L.dense_apply(params["proj"], x.astype(jnp.float32)))
        for i in range(config.num_blocks):
            blk = params[f"block_{i}"]
            gate = jax.nn.sigmoid(L.dense_apply(blk["gate"], h))
            h = h + gate * jax.nn.relu(L.dense_apply(blk["fc"], h))
        return L.dense_apply(params["out"], h)

    @mutation(MutationType.LAYER)
    def add_block(self, rng=None) -> Dict:
        cfg = self.config
        if cfg.num_blocks >= cfg.max_blocks:
            return {}
        self._morph(config_replace(cfg, num_blocks=cfg.num_blocks + 1))
        return {}

    @mutation(MutationType.LAYER, shrink_params=True)
    def remove_block(self, rng=None) -> Dict:
        cfg = self.config
        if cfg.num_blocks <= cfg.min_blocks:
            return {}
        self._morph(config_replace(cfg, num_blocks=cfg.num_blocks - 1))
        return {}


ENCODER_TYPES["gated_mlp"] = EvolvableGatedMLP  # <- the whole registration


def demo_native_custom_encoder():
    print("--- custom evolvable encoder inside the full RLOps loop ---")
    env = make_vect_envs("CartPole-v1", num_envs=8)
    latent = 32
    cfg = NetworkConfig(
        encoder_kind="gated_mlp",
        encoder=GatedMLPConfig(num_inputs=4, num_outputs=latent),
        head=MLPConfig(num_inputs=latent, num_outputs=2, hidden_size=(64,)),
        latent_dim=latent,
    )
    pop = create_population(
        "DQN", env.single_observation_space, env.single_action_space,
        population_size=2, net_config={"config": cfg},
        INIT_HP={"BATCH_SIZE": 64, "LR": 1e-3, "LEARN_STEP": 4, "DOUBLE": True},
        seed=7,
    )
    memory = ReplayBuffer(max_size=10_000)
    tournament = TournamentSelection(2, True, 2, 1)
    mutations = Mutations(no_mutation=0.3, architecture=0.5, parameters=0.2,
                          activation=0.0, rl_hp=0.0)
    pop, fitnesses = train_off_policy(
        env, "CartPole-v1", "DQN", pop, memory,
        max_steps=6_000, evo_steps=2_000, eval_loop=1,
        eps_start=1.0, eps_end=0.1, eps_decay=0.995,
        tournament=tournament, mutation=mutations, verbose=False,
    )
    for agent in pop:
        enc_cfg = agent.actor.config.encoder
        print(f"  agent {agent.index}: blocks={enc_cfg.num_blocks} "
              f"hidden={enc_cfg.hidden_size} fitness={agent.fitness[-1]:.1f}")
    env.close()


# ----------------------------------------------------------------------- #
# 2. import an existing torch model
# ----------------------------------------------------------------------- #


def demo_torch_import():
    try:
        import torch
        from torch import nn
    except ImportError:
        print("--- torch not installed; skipping torch-import demo ---")
        return
    from agilerl_tpu.wrappers.make_evolvable import MakeEvolvable

    print("--- MakeEvolvable: import a trained torch net ---")
    torch_net = nn.Sequential(
        nn.Linear(4, 32), nn.ReLU(), nn.Linear(32, 32), nn.ReLU(), nn.Linear(32, 2)
    )
    x = torch.randn(5, 4)
    evolvable = MakeEvolvable(torch_net, input_tensor=x, key=jax.random.PRNGKey(0))
    got = np.asarray(evolvable(x.numpy()))
    want = torch_net(x).detach().numpy()
    print(f"  imported weights match torch forward: "
          f"max abs err {np.abs(got - want).max():.2e}")
    print(f"  mutation methods discovered: "
          f"{sorted(evolvable.get_mutation_methods())}")


if __name__ == "__main__":
    print("===== agilerl_tpu custom network demo =====")
    demo_native_custom_encoder()
    demo_torch_import()
