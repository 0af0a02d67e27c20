"""Population parallelism: the whole evolutionary loop (rollout -> PPO update ->
fitness -> tournament -> mutation) as ONE jitted SPMD program.

This is the north-star redesign of the reference's population handling
(SURVEY.md §2.8 "Population parallelism"): the reference keeps the full
population on every rank and trains members sequentially with rank-0 deciding
evolution + broadcast_object_list (agilerl/hpo/tournament.py:161). Here the
population is a stacked pytree sharded one-member-per-device over a "pop" mesh
axis (shard_map); fitnesses all-gather over ICI; every device computes the SAME
tournament from a shared PRNG key (deterministic => no object broadcast); winner
params move with one all-gather; parameter mutations apply locally.

Works identically vmapped on one chip (the bench path) and shard_mapped over a
pod — same member_iteration function.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from agilerl_tpu.components.rollout_buffer import shuffled_minibatches
from agilerl_tpu.envs.core import JaxEnv, VecState, make_autoreset_step
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.base import EvolvableNetwork
from agilerl_tpu.observability.timeline import device_scope
from agilerl_tpu.parallel.generation import (
    evolve_actor_critic,
    make_pod_generation,
    make_vmap_generation,
)

#: the three parts of a generation that ``member_iteration`` names
#: (docs/observability.md, "Device scopes"); tournament and mutation stay in
#: the remainder
ROLLOUT_SCOPE = "evo/rollout"
SHUFFLE_SCOPE = "evo/shuffle"
UPDATE_SCOPE = "evo/update"


class MemberState(NamedTuple):
    actor: Any
    critic: Any
    opt_state: Any
    env_state: Any  # VecState
    obs: jax.Array
    ep_ret: jax.Array  # [num_envs] running episode return (spans iterations)
    key: jax.Array


class EvoPPO:
    """Fully-on-device evolutionary PPO over a JAX-native env."""

    def __init__(
        self,
        env: JaxEnv,
        actor_config,
        critic_config,
        dist_config,
        tx,
        num_envs: int = 64,
        rollout_len: int = 32,
        update_epochs: int = 2,
        num_minibatches: int = 4,
        gamma: float = 0.99,
        gae_lambda: float = 0.95,
        clip_coef: float = 0.2,
        ent_coef: float = 0.01,
        vf_coef: float = 0.5,
        elitism: bool = True,
        tournament_size: int = 2,
        mutation_sd: float = 0.02,
        mutation_prob: float = 0.5,
    ):
        self.env = env
        self.actor_config = actor_config
        self.critic_config = critic_config
        self.dist_config = dist_config
        self.tx = tx
        self.num_envs = num_envs
        self.rollout_len = rollout_len
        self.update_epochs = update_epochs
        self.num_minibatches = num_minibatches
        self.gamma = gamma
        self.gae_lambda = gae_lambda
        self.clip_coef = clip_coef
        self.ent_coef = ent_coef
        self.vf_coef = vf_coef
        self.elitism = elitism
        self.tournament_size = tournament_size
        self.mutation_sd = mutation_sd
        self.mutation_prob = mutation_prob
        self._vec_step = make_autoreset_step(env)
        self._reset = jax.vmap(env.reset_fn)

    # ------------------------------------------------------------------ #
    def init_member(self, key: jax.Array) -> MemberState:
        k1, k2, k3, k4 = jax.random.split(key, 4)
        actor = EvolvableNetwork.init_params(k1, self.actor_config)
        extra = D.extra_params(self.dist_config)
        if extra:
            actor["dist"] = extra
        critic = EvolvableNetwork.init_params(k2, self.critic_config)
        opt_state = self.tx.init({"actor": actor, "critic": critic})
        env_state, obs = self._reset(jax.random.split(k3, self.num_envs))
        vstate = VecState(env_state, jnp.zeros(self.num_envs, jnp.int32), k4)
        return MemberState(actor, critic, opt_state, vstate, obs,
                           jnp.zeros(self.num_envs), key)

    def init_population(self, key: jax.Array, pop_size: int) -> MemberState:
        return jax.vmap(self.init_member)(jax.random.split(key, pop_size))

    # ------------------------------------------------------------------ #
    def _rollout(self, state: MemberState):
        """lax.scan rollout; returns trajectory + episode-return fitness."""

        def body(carry, _):
            vstate, obs, ep_ret, fitness_sum, fitness_n, key = carry
            key, k_act = jax.random.split(key)
            logits = EvolvableNetwork.apply(self.actor_config, state.actor, obs)
            action = D.sample(self.dist_config, logits, k_act, state.actor.get("dist"))
            logp = D.log_prob(self.dist_config, logits, action, state.actor.get("dist"))
            value = EvolvableNetwork.apply(self.critic_config, state.critic, obs)[..., 0]
            vstate, next_obs, reward, term, trunc, final_obs = self._vec_step(vstate, action)
            done = jnp.logical_or(term, trunc).astype(jnp.float32)
            # time-limit bootstrapping at truncations (fold gamma*V(s_final))
            v_final = EvolvableNetwork.apply(
                self.critic_config, state.critic, final_obs
            )[..., 0]
            reward_adj = reward + self.gamma * v_final * trunc.astype(jnp.float32)
            ep_ret = ep_ret + reward
            fitness_sum = fitness_sum + jnp.sum(ep_ret * done)
            fitness_n = fitness_n + jnp.sum(done)
            ep_ret = ep_ret * (1.0 - done)
            out = dict(obs=obs, action=action, logp=logp, value=value,
                       reward=reward_adj, done=done)
            return (vstate, next_obs, ep_ret, fitness_sum, fitness_n, key), out

        key, sub = jax.random.split(state.key)
        # derive zero accumulators from state.obs so they carry the same
        # varying-axis type as loop outputs under shard_map (new vma checks)
        zero = 0.0 * jnp.sum(state.obs.astype(jnp.float32))
        # ep_ret carries across iterations so episodes spanning the boundary
        # report their FULL return (review finding)
        init = (state.env_state, state.obs,
                state.ep_ret + zero, zero, zero, sub)
        (vstate, obs, ep_ret, fsum, fn, _), traj = jax.lax.scan(
            body, init, None, length=self.rollout_len
        )
        fitness = jnp.where(fn > 0, fsum / jnp.maximum(fn, 1.0),
                            jnp.mean(traj["reward"]) * self.env.max_episode_steps
                            if self.env.max_episode_steps else jnp.mean(traj["reward"]))
        return traj, vstate, obs, ep_ret, fitness, key

    def _gae(self, traj, last_value):
        # dones are per-step terminal flags: step t's own done masks both its
        # bootstrap and the carried advantage (see components/rollout_buffer.py)
        def step(carry, xs):
            gae, next_v = carry
            r, v, d = xs
            nonterm = 1.0 - d
            delta = r + self.gamma * next_v * nonterm - v
            gae = delta + self.gamma * self.gae_lambda * nonterm * gae
            return (gae, v), gae

        init = (jnp.zeros_like(last_value), last_value)
        _, adv = jax.lax.scan(
            step, init,
            (traj["reward"][::-1], traj["value"][::-1], traj["done"][::-1]),
        )
        adv = adv[::-1]
        return adv, adv + traj["value"]

    def _ppo_update(self, actor, critic, opt_state, traj, adv, ret, key):
        """PPO epochs over the flattened rollout. An epoch's rows ride the
        sorts that draw its permutation (``shuffled_minibatches``): on a TPU
        v5e a gather pays ~15 ns an index, the sort moves a payload column
        for a tenth of that (PERF.md section 6, PR 26)."""
        T, N = traj["reward"].shape
        total = T * N
        mb = total // self.num_minibatches
        flat = {
            "obs": traj["obs"].reshape((total,) + traj["obs"].shape[2:]),
            "action": traj["action"].reshape((total,) + traj["action"].shape[2:]),
            "logp": traj["logp"].reshape(total),
            "adv": adv.reshape(total),
            "ret": ret.reshape(total),
        }

        def epoch(carry, k):
            params, opt_state = carry
            with device_scope(SHUFFLE_SCOPE):
                batches = shuffled_minibatches(k, flat, self.num_minibatches, mb)

            def minibatch(carry, b):
                params, opt_state = carry

                def loss_fn(p):
                    logits = EvolvableNetwork.apply(self.actor_config, p["actor"], b["obs"])
                    extra = p["actor"].get("dist")
                    new_logp = D.log_prob(self.dist_config, logits, b["action"], extra)
                    ent = D.entropy(self.dist_config, logits, extra).mean()
                    value = EvolvableNetwork.apply(
                        self.critic_config, p["critic"], b["obs"]
                    )[..., 0]
                    a = (b["adv"] - b["adv"].mean()) / (b["adv"].std() + 1e-8)
                    ratio = jnp.exp(new_logp - b["logp"])
                    pg = jnp.maximum(
                        -a * ratio,
                        -a * jnp.clip(ratio, 1 - self.clip_coef, 1 + self.clip_coef),
                    ).mean()
                    v_loss = 0.5 * jnp.square(value - b["ret"]).mean()
                    return pg - self.ent_coef * ent + self.vf_coef * v_loss

                loss, grads = jax.value_and_grad(loss_fn)(params)
                updates, opt_state = self.tx.update(grads, opt_state, params)
                params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
                return (params, opt_state), loss

            with device_scope(UPDATE_SCOPE):
                (params, opt_state), losses = jax.lax.scan(
                    minibatch, (params, opt_state), batches)
            return (params, opt_state), losses.mean()

        params = {"actor": actor, "critic": critic}
        keys = jax.random.split(key, self.update_epochs)
        (params, opt_state), losses = jax.lax.scan(epoch, (params, opt_state), keys)
        return params["actor"], params["critic"], opt_state, losses.mean()

    # ------------------------------------------------------------------ #
    def member_iteration(self, state: MemberState) -> Tuple[MemberState, jax.Array]:
        """One generation for one member: rollout -> GAE -> PPO epochs."""
        with device_scope(ROLLOUT_SCOPE):
            traj, vstate, obs, ep_ret, fitness, key = self._rollout(state)
            last_value = EvolvableNetwork.apply(
                self.critic_config, state.critic, obs)[..., 0]
            adv, ret = self._gae(traj, last_value)
        key, k_up = jax.random.split(key)
        actor, critic, opt_state, _loss = self._ppo_update(
            state.actor, state.critic, state.opt_state, traj, adv, ret, k_up
        )
        return MemberState(actor, critic, opt_state, vstate, obs, ep_ret, key), fitness

    # ------------------------------------------------------------------ #
    def _evolve_extracted(self, extracted, fitness: jax.Array, key: jax.Array):
        """Tournament + mutation over exactly the subtrees evolution needs
        (actor, critic, optimizer state) — the shared generation-engine
        step, same key-split order as before the refactor."""
        return evolve_actor_critic(
            extracted, fitness, key,
            tournament_size=self.tournament_size, elitism=self.elitism,
            mutation_prob=self.mutation_prob, mutation_sd=self.mutation_sd,
        )

    def evolve(self, pop: MemberState, fitness: jax.Array, key: jax.Array) -> MemberState:
        """Deterministic tournament + parameter mutation as pure array ops.
        pop leaves have leading pop axis; fitness [P]. Same key on every host
        => same winners everywhere (replaces rank-0 + broadcast).

        NOTE: unlike the off-policy scan tier, EvoPPO carries ``ep_ret``
        across the boundary — its fitness window (one rollout) is far
        shorter than an episode, so segmenting would cap measurable returns
        at ``rollout_len``. The scan-resident off-policy/multi-agent
        programs segment instead (see generation.ScanOffPolicy.evolve)."""
        actor, critic, opt_state = self._evolve_extracted(
            (pop.actor, pop.critic, pop.opt_state), fitness, key
        )
        return MemberState(
            actor, critic, opt_state, pop.env_state, pop.obs,
            pop.ep_ret, pop.key
        )

    # ------------------------------------------------------------------ #
    def make_vmap_generation(self) -> Callable:
        """Single-device: vmapped members + on-device evolution, one jit.
        The population pytree is donated — callers follow the
        ``pop, fitness = gen(pop, key)`` pattern, and the dead input copy
        would otherwise cost a full parameter+optimizer+buffer memcpy per
        generation (measurable on the HBM/memory-bound hot loop)."""
        return make_vmap_generation(self.member_iteration, self.evolve)

    def make_pod_generation(self, mesh: Mesh = None, plan=None,
                            donate: bool = True) -> Callable:
        """Pod-sharded: members shard over the 'pop' axis (any number per
        device); fitness and ONLY the evolution subtrees (actor, critic,
        optimizer) all-gather over ICI inside shard_map — env states stay
        device-local (the pre-refactor path gathered the whole member).
        ``plan`` (ShardingPlan or registered name) supplies the mesh and the
        member layout rules declaratively."""
        return make_pod_generation(
            mesh,
            self.member_iteration,
            extract=lambda pop: (pop.actor, pop.critic, pop.opt_state),
            evolve_extracted=self._evolve_extracted,
            insert=lambda pop, mine: pop._replace(
                actor=mine[0], critic=mine[1], opt_state=mine[2]
            ),
            plan=plan,
            donate=donate,
        )
