"""Scan-resident multi-agent IPPO over the JAX-native multi-agent envs.

:class:`EvoIPPO` runs independent PPO — one actor/critic per agent, stacked
on a leading agent axis and vmapped — with the whole rollout → GAE → PPO
update → tournament → mutation loop inside one jitted SPMD program, exactly
the ``make_vmap_generation`` / ``make_pod_generation`` contract the
single-agent programs satisfy. Environments follow the
:func:`~agilerl_tpu.envs.multi_agent.make_ma_autoreset_step` stacked layout
(homogeneous agents, shared reward — ``SimpleSpreadJax``).

Fitness = censored mean of the shared episode return; running returns are
segmented at generation boundaries (``evolve`` zeroes ``ep_ret``) like the
rest of the scan tier.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from agilerl_tpu.components.rollout_buffer import shuffled_minibatches
from agilerl_tpu.envs.core import VecState
from agilerl_tpu.envs.multi_agent import SimpleSpreadJax, make_ma_autoreset_step
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.base import EvolvableNetwork
from agilerl_tpu.parallel.generation import (
    evolve_actor_critic,
    make_pod_generation,
    make_vmap_generation,
)


class IPPOMemberState(NamedTuple):
    actor: Any  # per-agent stacked params, leaves [A, ...]
    critic: Any
    opt_state: Any  # [A, ...]
    env_state: Any  # VecState
    obs: jax.Array  # [A, N, obs_dim]
    ep_ret: jax.Array  # [N] shared-reward episode return
    key: jax.Array


class EvoIPPO:
    """Fully-on-device evolutionary independent PPO (multi-agent)."""

    def __init__(
        self,
        env: SimpleSpreadJax,
        actor_config,
        critic_config,
        dist_config,
        tx,
        num_envs: int = 32,
        rollout_len: int = 32,
        update_epochs: int = 2,
        num_minibatches: int = 2,
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
        self.n_agents = len(env.agent_ids)
        self.actor_config = actor_config
        self.critic_config = critic_config
        self.dist_config = dist_config
        self.tx = tx
        self.num_envs = int(num_envs)
        self.rollout_len = int(rollout_len)
        self.update_epochs = int(update_epochs)
        self.num_minibatches = int(num_minibatches)
        self.gamma = float(gamma)
        self.gae_lambda = float(gae_lambda)
        self.clip_coef = float(clip_coef)
        self.ent_coef = float(ent_coef)
        self.vf_coef = float(vf_coef)
        self.elitism = bool(elitism)
        self.tournament_size = int(tournament_size)
        self.mutation_sd = float(mutation_sd)
        self.mutation_prob = float(mutation_prob)
        self._vec_step = make_ma_autoreset_step(env)
        self._reset = jax.vmap(env.reset_fn)

    @property
    def env_steps_per_generation(self) -> int:
        return self.num_envs * self.rollout_len

    # ------------------------------------------------------------------ #
    def init_member(self, key: jax.Array) -> IPPOMemberState:
        A = self.n_agents
        k1, k2, k3, k4 = jax.random.split(key, 4)

        def init_actor(k):
            params = EvolvableNetwork.init_params(k, self.actor_config)
            extra = D.extra_params(self.dist_config)
            if extra:
                params["dist"] = extra
            return params

        actor = jax.vmap(init_actor)(jax.random.split(k1, A))
        critic = jax.vmap(
            lambda k: EvolvableNetwork.init_params(k, self.critic_config)
        )(jax.random.split(k2, A))
        opt_state = jax.vmap(
            lambda a, c: self.tx.init({"actor": a, "critic": c})
        )(actor, critic)
        env_state, obs_dict = self._reset(jax.random.split(k3, self.num_envs))
        obs = jnp.stack(
            [obs_dict[a] for a in self.env.agent_ids], axis=0
        )  # [A, N, D]
        vstate = VecState(env_state, jnp.zeros(self.num_envs, jnp.int32), k4)
        return IPPOMemberState(actor, critic, opt_state, vstate, obs,
                               jnp.zeros(self.num_envs), key)

    def init_population(self, key: jax.Array, pop_size: int) -> IPPOMemberState:
        return jax.vmap(self.init_member)(jax.random.split(key, pop_size))

    # ------------------------------------------------------------------ #
    def _apply_actor(self, actor, obs):
        """Per-agent stacked apply: params leaves [A, ...], obs [A, N, D]."""
        return jax.vmap(
            lambda p, o: EvolvableNetwork.apply(self.actor_config, p, o)
        )(actor, obs)

    def _apply_critic(self, critic, obs):
        return jax.vmap(
            lambda p, o: EvolvableNetwork.apply(self.critic_config, p, o)[..., 0]
        )(critic, obs)

    def _dist_extra(self, actor):
        return actor.get("dist") if isinstance(actor, dict) else None

    def _rollout(self, state: IPPOMemberState):
        A = self.n_agents
        extra = self._dist_extra(state.actor)

        def body(carry, _):
            vstate, obs, ep_ret, fsum, fn, key = carry
            key, k_act = jax.random.split(key)
            logits = self._apply_actor(state.actor, obs)  # [A, N, out]
            k_agents = jax.random.split(k_act, A)
            if extra is not None:
                action = jax.vmap(
                    lambda lg, k, ex: D.sample(self.dist_config, lg, k, ex)
                )(logits, k_agents, extra)
                logp = jax.vmap(
                    lambda lg, a, ex: D.log_prob(self.dist_config, lg, a, ex)
                )(logits, action, extra)
            else:
                action = jax.vmap(
                    lambda lg, k: D.sample(self.dist_config, lg, k, None)
                )(logits, k_agents)
                logp = jax.vmap(
                    lambda lg, a: D.log_prob(self.dist_config, lg, a, None)
                )(logits, action)
            value = self._apply_critic(state.critic, obs)  # [A, N]
            vstate, next_obs, reward, term, trunc, final_obs = self._vec_step(
                vstate, action
            )
            done = jnp.logical_or(term, trunc).astype(jnp.float32)  # [N]
            # time-limit bootstrapping at truncations, per agent's own critic
            v_final = self._apply_critic(state.critic, final_obs)  # [A, N]
            reward_adj = (
                reward[None, :]
                + self.gamma * v_final * trunc.astype(jnp.float32)[None, :]
            )
            ep_ret = ep_ret + reward
            fsum = fsum + jnp.sum(ep_ret * done)
            fn = fn + jnp.sum(done)
            ep_ret = ep_ret * (1.0 - done)
            out = dict(obs=obs, action=action, logp=logp, value=value,
                       reward=reward_adj, done=done)
            return (vstate, next_obs, ep_ret, fsum, fn, key), out

        key, sub = jax.random.split(state.key)
        zero = 0.0 * jnp.sum(state.obs.astype(jnp.float32))
        init = (state.env_state, state.obs, state.ep_ret + zero,
                zero, zero, sub)
        (vstate, obs, ep_ret, fsum, fn, _), traj = jax.lax.scan(
            body, init, None, length=self.rollout_len
        )
        # censored-return fitness (see generation.ScanOffPolicy._run_iteration)
        fitness = (fsum + jnp.sum(ep_ret)) / (fn + self.num_envs)
        return traj, vstate, obs, ep_ret, fitness, key

    def _gae(self, reward, value, done, last_value):
        """Single-agent GAE over [T, N] arrays (vmapped over agents)."""

        def step(carry, xs):
            gae, next_v = carry
            r, v, d = xs
            nonterm = 1.0 - d
            delta = r + self.gamma * next_v * nonterm - v
            gae = delta + self.gamma * self.gae_lambda * nonterm * gae
            return (gae, v), gae

        init = (jnp.zeros_like(last_value), last_value)
        _, adv = jax.lax.scan(step, init, (reward[::-1], value[::-1], done[::-1]))
        adv = adv[::-1]
        return adv, adv + value

    def _agent_update(self, params, opt_state, flat, key):
        """One agent's PPO epochs over its flattened rollout (vmapped)."""
        total = flat["logp"].shape[0]
        mb = total // self.num_minibatches

        def epoch(carry, k):
            params, opt_state = carry
            batches = shuffled_minibatches(k, flat, self.num_minibatches, mb)

            def minibatch(carry, b):
                params, opt_state = carry

                def loss_fn(p):
                    logits = EvolvableNetwork.apply(
                        self.actor_config, p["actor"], b["obs"]
                    )
                    ex = p["actor"].get("dist")
                    new_logp = D.log_prob(self.dist_config, logits, b["action"], ex)
                    ent = D.entropy(self.dist_config, logits, ex).mean()
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

            (params, opt_state), losses = jax.lax.scan(
                minibatch, (params, opt_state), batches
            )
            return (params, opt_state), losses.mean()

        keys = jax.random.split(key, self.update_epochs)
        (params, opt_state), losses = jax.lax.scan(
            epoch, (params, opt_state), keys
        )
        return params, opt_state, losses.mean()

    # ------------------------------------------------------------------ #
    def member_iteration(
        self, state: IPPOMemberState
    ) -> Tuple[IPPOMemberState, jax.Array]:
        """One generation for one member: rollout → per-agent GAE → per-agent
        PPO epochs (everything past the rollout vmapped over the agent axis)."""
        A = self.n_agents
        T, N = self.rollout_len, self.num_envs
        traj, vstate, obs, ep_ret, fitness, key = self._rollout(state)
        last_value = self._apply_critic(state.critic, obs)  # [A, N]
        done_b = jnp.broadcast_to(
            traj["done"][:, None, :], traj["value"].shape
        )  # [T, A, N]
        adv, ret = jax.vmap(self._gae, in_axes=(1, 1, 1, 0), out_axes=(1, 1))(
            traj["reward"], traj["value"], done_b, last_value
        )

        def flatten(x):  # [T, A, N, ...] -> [A, T*N, ...]
            x = jnp.moveaxis(x, 1, 0)
            return x.reshape((A, T * N) + x.shape[3:])

        flat = {
            "obs": flatten(traj["obs"]),
            "action": flatten(traj["action"]),
            "logp": flatten(traj["logp"]),
            "adv": flatten(adv),
            "ret": flatten(ret),
        }
        key, k_up = jax.random.split(key)
        params = {"actor": state.actor, "critic": state.critic}
        new_params, opt_state, _loss = jax.vmap(self._agent_update)(
            params, state.opt_state, flat, jax.random.split(k_up, A)
        )
        return (
            IPPOMemberState(new_params["actor"], new_params["critic"], opt_state,
                            vstate, obs, ep_ret, key),
            fitness,
        )

    # ------------------------------------------------------------------ #
    def _evolve_extracted(self, extracted, fitness: jax.Array, key: jax.Array):
        return evolve_actor_critic(
            extracted, fitness, key,
            tournament_size=self.tournament_size, elitism=self.elitism,
            mutation_prob=self.mutation_prob, mutation_sd=self.mutation_sd,
        )

    def evolve(
        self, pop: IPPOMemberState, fitness: jax.Array, key: jax.Array
    ) -> IPPOMemberState:
        actor, critic, opt_state = self._evolve_extracted(
            (pop.actor, pop.critic, pop.opt_state), fitness, key
        )
        return pop._replace(
            actor=actor, critic=critic, opt_state=opt_state,
            ep_ret=jnp.zeros_like(pop.ep_ret),
        )

    # ------------------------------------------------------------------ #
    def make_vmap_generation(self) -> Callable:
        return make_vmap_generation(self.member_iteration, self.evolve)

    def make_pod_generation(self, mesh=None, plan=None,
                            donate: bool = True) -> Callable:
        return make_pod_generation(
            mesh,
            self.member_iteration,
            extract=lambda pop: (pop.actor, pop.critic, pop.opt_state),
            evolve_extracted=self._evolve_extracted,
            insert=lambda pop, mine: pop._replace(
                actor=mine[0], critic=mine[1], opt_state=mine[2],
                ep_ret=jnp.zeros_like(pop.ep_ret),
            ),
            plan=plan,
            donate=donate,
        )

    # -- snapshots ------------------------------------------------------ #
    def state_dict(self, pop: IPPOMemberState):
        from agilerl_tpu.parallel.generation import population_state_dict

        return population_state_dict(pop)

    def load_state_dict(self, pop: IPPOMemberState, blob):
        from agilerl_tpu.parallel.generation import population_load_state_dict

        return population_load_state_dict(pop, blob)
