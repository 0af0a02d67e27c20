"""Back-to-back generations of an ``EvoPPO`` population on one chip. A step
is one ``make_vmap_generation`` call (rollout -> GAE -> PPO epochs ->
tournament -> mutation, one program) ended by fetching the fitness."""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
import optax

from agilerl_tpu.envs import CartPole
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
from agilerl_tpu.parallel.population import EvoPPO
from perfbench import harness
from perfbench.reference import cartpole_numpy as ref

ENVS = {"CartPole-v1": CartPole}
CHECK_PAIRS = 1000  # seeded (state, action) pairs against the reference


def make_evo(config: Dict[str, Any]) -> EvoPPO:
    """``EvoPPO`` with the configuration file's hyper-parameters."""
    env = ENVS[config["ENV_NAME"]]()
    net = config["NET_CONFIG"]
    latent = int(net["latent_dim"])
    head_hidden = tuple(config["assumed"]["head_hidden_size"])
    kind, enc = default_encoder_config(
        env.observation_space, latent_dim=latent,
        encoder_config={"hidden_size": tuple(net["encoder_config"]["hidden_size"])})

    def network(outputs: int) -> NetworkConfig:
        return NetworkConfig(
            encoder_kind=kind, encoder=enc, latent_dim=latent,
            head=MLPConfig(num_inputs=latent, num_outputs=outputs,
                           hidden_size=head_hidden))

    tx = optax.chain(optax.clip_by_global_norm(float(config["MAX_GRAD_NORM"])),
                     optax.adam(float(config["LR"])))
    return EvoPPO(
        env, network(env.action_space.n), network(1),
        D.dist_config_from_space(env.action_space), tx,
        num_envs=int(config["NUM_ENVS"]), rollout_len=int(config["LEARN_STEP"]),
        update_epochs=int(config["UPDATE_EPOCHS"]),
        num_minibatches=int(config["assumed"]["num_minibatches"]),
        gamma=float(config["GAMMA"]), gae_lambda=float(config["GAE_LAMBDA"]),
        clip_coef=float(config["CLIP_COEF"]), ent_coef=float(config["ENT_COEF"]),
        vf_coef=float(config["VF_COEF"]))


def check_env(env, seed: int) -> List[str]:
    """The program's CartPole step against the plain reference on seeded
    (state, action) pairs, some past the termination limits."""
    rng = np.random.default_rng([seed, 11])
    state = rng.uniform(-1, 1, size=(CHECK_PAIRS, 4)) * np.array(
        [2.6, 3.0, 0.23, 3.0])
    action = rng.integers(0, 2, size=CHECK_PAIRS)
    s32 = state.astype(np.float32)
    want, want_term = ref.step(s32, action)
    state_cls = type(env.reset_fn(jax.random.PRNGKey(0))[0])
    step = jax.jit(jax.vmap(lambda s, a: env.step_fn(
        state_cls(*s), a, jax.random.PRNGKey(0))))
    _, got, _, got_term, _ = step(jnp.asarray(s32), jnp.asarray(action))
    got, got_term = np.asarray(got), np.asarray(got_term)
    worst = float(np.abs(got - want).max())
    problems = []
    if not worst <= ref.STATE_TOL:
        problems.append(f"CartPole step differs from the reference by {worst}")
    # a state within rounding of a limit may terminate on one side only
    near = (np.abs(np.abs(want[:, 0]) - ref.X_LIMIT) < 1e-4) | \
        (np.abs(np.abs(want[:, 2]) - ref.THETA_LIMIT) < 1e-4)
    if (got_term != want_term)[~near].any():
        problems.append("CartPole termination differs from the reference")
    return problems


class Session:
    trace_steps = 3

    def __init__(self, cell, seed, devices):
        config = cell.config
        self.device = devices[0]
        self.pop_size = int(config["POP_SIZE"])
        self.per_step = int(cell.traffic["generations_per_step"])
        self.env_steps = (self.pop_size * int(config["NUM_ENVS"])
                          * int(config["LEARN_STEP"]))
        evo = make_evo(config)
        self.problems = check_env(evo.env, seed)
        self.key = jax.random.PRNGKey(seed + 1)
        self.n_steps = 0
        self.pop = jax.jit(evo.init_population, static_argnums=1)(
            jax.random.PRNGKey(seed), self.pop_size)
        self.gen = evo.make_vmap_generation()
        record = self.step()  # warm-up: compiles the generation program
        self.fitness_after_warmup = record["fitness_mean"]
        harness.note(perfbench="warm-up generation", **record)

    def step(self) -> Dict[str, Any]:
        with harness.span("generation"):
            t0 = time.perf_counter()
            for _ in range(self.per_step):
                key = jax.random.fold_in(self.key, self.n_steps)
                self.n_steps += 1
                self.pop, fitness = self.gen(self.pop, key)
            fitness = np.asarray(fitness)  # waits for the programs
            gen_s = time.perf_counter() - t0
        ok = fitness.shape == (self.pop_size,) and np.isfinite(fitness).all()
        if not ok:
            self.problems.append(f"bad fitness {fitness.shape}: {fitness}")
        return {"attempted": self.per_step, "failed": 0 if ok else self.per_step,
                "gen_s": gen_s, "env_steps": self.env_steps * self.per_step,
                "fitness_mean": float(fitness.mean())}

    def end_to_end(self, records) -> Dict[str, float]:
        return {"env_steps_s": sum(r["env_steps"] for r in records)
                / sum(r["gen_s"] for r in records)}

    def finish(self, records) -> List[str]:
        problems = []
        on = {d for x in jax.tree_util.tree_leaves(self.pop)
              for d in x.devices()}
        if on != {self.device}:
            problems.append(f"the population is on {on}, not {self.device}")
        last = records[-1]["fitness_mean"]
        if not last >= self.fitness_after_warmup:
            problems.append(
                f"mean fitness fell from {self.fitness_after_warmup} after "
                f"warm-up to {last} after the window")
        return problems
