"""Profiling demo (parity: demos/performance_flamegraph_cartpole.py — cProfile/
torch.profiler flamegraphs become jax.profiler traces + on-device step timing).

Writes an XLA trace viewable in TensorBoard/Perfetto and prints StepTimer
percentiles for the jitted EvoPPO generation step.
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import jax
import optax

from agilerl_tpu.envs import CartPole
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
from agilerl_tpu.parallel.population import EvoPPO
from agilerl_tpu.utils.profiling import StepTimer, profile_trace

if __name__ == "__main__":
    env = CartPole()
    kind, enc = default_encoder_config(
        env.observation_space, latent_dim=64, encoder_config={"hidden_size": (64,)}
    )
    actor_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc,
        head=MLPConfig(num_inputs=64, num_outputs=2, hidden_size=(64,)),
        latent_dim=64,
    )
    critic_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc,
        head=MLPConfig(num_inputs=64, num_outputs=1, hidden_size=(64,)),
        latent_dim=64,
    )
    evo = EvoPPO(env, actor_cfg, critic_cfg,
                 D.dist_config_from_space(env.action_space), optax.adam(3e-4),
                 num_envs=32, rollout_len=32, update_epochs=1, num_minibatches=4)
    pop = evo.init_population(jax.random.PRNGKey(0), 8)
    gen = evo.make_vmap_generation()
    pop, fit = gen(pop, jax.random.PRNGKey(1))  # compile
    jax.block_until_ready(fit)

    timer = StepTimer()
    timer.tick()
    with profile_trace("/tmp/agilerl_tpu_trace"):
        for i in range(5):
            pop, fit = gen(pop, jax.random.PRNGKey(2 + i))
            jax.block_until_ready(fit)
            timer.tick()
    steps_per_gen = 8 * 32 * 32  # pop x envs x rollout
    print("trace written to /tmp/agilerl_tpu_trace (open in TensorBoard)")
    print(f"mean generation time {timer.mean_step_time * 1e3:.1f} ms "
          f"({timer.throughput(steps_per_gen):,.0f} env-steps/sec)")
