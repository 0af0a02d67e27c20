"""Distributed evolutionary DQN on CartPole — the TPU-native equivalent of the
reference's `accelerate launch` DDP demo (parity: demos/demo_off_policy_distributed.py).

Where the reference wraps torch models in HF Accelerate and splits replay
batches across ranks, here the WHOLE evolutionary generation (rollout -> TD
updates -> fitness -> tournament -> mutation) is ONE SPMD program: the
population is sharded over a `pop` mesh axis with `shard_map`, each device
trains its shard, and evolution all-gathers fitness over ICI
(agilerl_tpu/parallel/off_policy.py make_pod_generation). There is no launcher,
no process group, no gradient hooks — one `python` invocation, any mesh size.

Run on a host with one device via a virtual 8-device CPU mesh:
    JAX_PLATFORMS=cpu python demos/demo_off_policy_distributed.py
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
if _os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    # single-host demo: fabricate an 8-device CPU mesh (SURVEY.md §4 — JAX
    # tests collectives for real where the reference fakes world-size 1)
    _flags = _os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        _os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax
import numpy as np
import optax
from jax.sharding import Mesh

from agilerl_tpu.envs import CartPole
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
from agilerl_tpu.parallel.off_policy import EvoDQN

if __name__ == "__main__":
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), axis_names=("pop",))
    members_per_device = 2
    pop_size = members_per_device * len(devices)
    print(f"===== agilerl_tpu distributed off-policy demo =====\n"
          f"devices: {len(devices)} ({devices[0].platform}), "
          f"population {pop_size} ({members_per_device}/device)")

    env = CartPole()
    kind, enc = default_encoder_config(env.observation_space, latent_dim=32,
                                       encoder_config={"hidden_size": (64,)})
    net = NetworkConfig(encoder_kind=kind, encoder=enc,
                        head=MLPConfig(num_inputs=32, num_outputs=2,
                                       hidden_size=(64,)), latent_dim=32)
    evo = EvoDQN(env, net, optax.adam(1e-3), num_envs=16, steps_per_iter=128,
                 buffer_size=10_000, batch_size=64)

    pop = evo.init_population(jax.random.PRNGKey(42), pop_size=pop_size)
    generation = evo.make_pod_generation(mesh)  # shard_map over the pop axis

    for gen_idx in range(8):
        pop, fitness = generation(pop, jax.random.PRNGKey(gen_idx))
        print(f"generation {gen_idx}: fitness "
              f"mean {float(np.mean(fitness)):6.1f} "
              f"max {float(np.max(fitness)):6.1f}")
    print("done — best member fitness:", float(np.max(fitness)))
