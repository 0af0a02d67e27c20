"""Distributed (multi-device mesh) off-policy benchmarking
(parity: benchmarking/benchmarking_off_policy_distributed.py — accelerate
launch + DDP become one shard_map program over a `pop` mesh axis: each device
trains its population shard, evolution all-gathers fitness over ICI).

On a host without multiple accelerators, run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu
for a virtual 8-device mesh.
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import time

import jax
import numpy as np
import optax
from jax.sharding import Mesh

from agilerl_tpu.envs import CartPole
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
from agilerl_tpu.parallel.off_policy import EvoDQN


def main(generations: int = 4, members_per_device: int = 2):
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), axis_names=("pop",))
    pop_size = members_per_device * len(devices)
    env = CartPole()
    kind, enc = default_encoder_config(env.observation_space, latent_dim=32,
                                       encoder_config={"hidden_size": (64,)})
    cfg = NetworkConfig(encoder_kind=kind, encoder=enc,
                        head=MLPConfig(num_inputs=32, num_outputs=2,
                                       hidden_size=(64,)), latent_dim=32)
    evo = EvoDQN(env, cfg, optax.adam(1e-3), num_envs=32, steps_per_iter=128,
                 batch_size=64)
    pop = evo.init_population(jax.random.PRNGKey(0), pop_size=pop_size)
    gen = evo.make_pod_generation(mesh)

    pop, fitness = gen(pop, jax.random.PRNGKey(1))  # compile
    jax.block_until_ready(fitness)
    start = time.time()
    for i in range(generations):
        pop, fitness = gen(pop, jax.random.PRNGKey(2 + i))
    jax.block_until_ready(fitness)
    dt = time.time() - start
    steps = pop_size * 32 * 128 * generations
    print(f"devices={len(devices)} pop={pop_size} "
          f"aggregate env-steps/sec: {steps / dt:,.0f}; "
          f"mean fitness {float(np.mean(fitness)):.1f}")


if __name__ == "__main__":
    main()
