"""Distributed offline RL (CQN) — data-parallel learning over a device mesh
(parity: demos/demo_offline_distributed.py, where the reference shards replay
batches across Accelerate DDP ranks).

The TPU-native shape: params stay replicated, each sampled batch is placed
with a `NamedSharding` that splits the batch axis over the `dp` mesh axis, and
GSPMD compiles the SAME jitted train step into a data-parallel program — the
gradient all-reduce the reference gets from DDP hooks is inserted by XLA as an
ICI psum. No launcher, no process groups, identical numerics to 1 device.

Run on a host with one device via a virtual 8-device CPU mesh:
    JAX_PLATFORMS=cpu python demos/demo_offline_distributed.py
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
if _os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    _flags = _os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        _os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from agilerl_tpu.components import ReplayBuffer
from agilerl_tpu.utils.minari_utils import collect_offline_dataset
from agilerl_tpu.utils.utils import create_population, make_vect_envs


def shard_batch(batch, sharding):
    """Split the batch axis of every leaf across the dp mesh axis."""
    return jax.tree.map(
        lambda x: jax.device_put(jnp.asarray(x), sharding), dict(batch)
    )


if __name__ == "__main__":
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), axis_names=("dp",))
    data_sharding = NamedSharding(mesh, P("dp"))
    print(f"===== agilerl_tpu distributed offline demo =====\n"
          f"devices: {len(devices)} ({devices[0].platform}) — dp axis")

    env = make_vect_envs("CartPole-v1", num_envs=8)
    dataset = collect_offline_dataset(env, steps=10_000, epsilon=1.0)
    memory = ReplayBuffer(max_size=len(dataset["rewards"]))
    memory.add({
        "obs": np.asarray(dataset["observations"]),
        "action": np.asarray(dataset["actions"]).squeeze(),
        "reward": np.asarray(dataset["rewards"], np.float32).squeeze(),
        "next_obs": np.asarray(dataset["next_observations"]),
        "done": np.asarray(dataset["terminals"], np.float32).squeeze(),
    }, batched=True)

    # batch size must divide evenly across the dp axis
    batch_size = 128 * len(devices) if len(devices) > 1 else 128
    agent = create_population(
        "CQN", env.single_observation_space, env.single_action_space,
        population_size=1,
        net_config={"latent_dim": 32, "encoder_config": {"hidden_size": (64,)}},
        INIT_HP={"BATCH_SIZE": batch_size, "LR": 1e-3},
        seed=42,
    )[0]

    for step in range(200):
        batch = memory.sample(batch_size)
        loss = agent.learn(shard_batch(batch, data_sharding))
        if step % 50 == 0:
            print(f"step {step:4d}  cql loss {float(loss):8.4f}")

    fitness = agent.test(env, max_steps=500, loop=3)
    env.close()
    print(f"done — offline-trained fitness over 3 eval episodes: {fitness:.1f}")
