"""Tutorial 3 — Pod-scale population parallelism: the whole evolutionary loop
as one SPMD program, one population member per device.

Run on any host (uses however many devices jax sees; on CPU set
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu).
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import jax
import numpy as np
import optax
from jax.sharding import Mesh

from agilerl_tpu.envs import CartPole
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
from agilerl_tpu.parallel.population import EvoPPO

env = CartPole()
kind, enc = default_encoder_config(env.observation_space, latent_dim=32,
                                   encoder_config={"hidden_size": (64,)})
evo = EvoPPO(
    env,
    NetworkConfig(encoder_kind=kind, encoder=enc,
                  head=MLPConfig(num_inputs=32, num_outputs=2), latent_dim=32),
    NetworkConfig(encoder_kind=kind, encoder=enc,
                  head=MLPConfig(num_inputs=32, num_outputs=1), latent_dim=32),
    D.dist_config_from_space(env.action_space),
    optax.adam(3e-4), num_envs=32, rollout_len=32,
)
n = len(jax.devices())
pop = evo.init_population(jax.random.PRNGKey(0), pop_size=n)
mesh = Mesh(np.asarray(jax.devices()), axis_names=("pop",))
gen = evo.make_pod_generation(mesh)   # shard_map: fitness all-gather over ICI
for i in range(5):
    pop, fitness = gen(pop, jax.random.PRNGKey(i))
    print(f"gen {i}: fitness {np.asarray(fitness).round(1)}")
