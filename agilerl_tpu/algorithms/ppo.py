"""PPO (parity: agilerl/algorithms/ppo.py — PPO:?, rollout-buffer learn path
learn:635, flat minibatch epochs _learn_from_rollout_buffer_flat:814, recurrent
BPTT path _learn_from_rollout_buffer_bptt:923, GAE in the buffer, target-KL
early stop, entropy/value-coef HPs, recurrent hidden-state plumbing
get_initial_hidden_state:504).

TPU-first: the minibatch update (policy + value loss, grads, optax step) is one
jitted function; epochs iterate over device-resident permutations. Observation
preprocessing (one-hot etc.) happens inside the jitted update so raw env obs
stay zero-copy. Recurrent learning replays sequences through lax.scan-backed
LSTM encoders (truncated BPTT over fixed-length chunks).
"""

from __future__ import annotations

import functools

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from agilerl_tpu.algorithms.core.base import RLAlgorithm
from agilerl_tpu.algorithms.core.optimizer import OptimizerWrapper
from agilerl_tpu.algorithms.core.registry import (
    HyperparameterConfig,
    NetworkGroup,
    OptimizerConfig,
    RLParameter,
)
from agilerl_tpu.components.rollout_buffer import (
    RolloutBuffer,
    shuffled_minibatches,
)
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.actors import StochasticActor
from agilerl_tpu.networks.base import EvolvableNetwork
from agilerl_tpu.networks.value_networks import ValueNetwork
from agilerl_tpu.utils.spaces import preprocess_observation


def default_hp_config() -> HyperparameterConfig:
    return HyperparameterConfig(
        lr=RLParameter(min=1e-5, max=1e-2, dtype=float),
        batch_size=RLParameter(min=32, max=1024, dtype=int),
        learn_step=RLParameter(min=64, max=4096, dtype=int),
        ent_coef=RLParameter(min=1e-4, max=0.1, dtype=float),
    )


class PPO(RLAlgorithm):
    # activation mutation is blocked for policy-gradient algos (parity: hpo/mutation.py:473)
    supports_activation_mutation = False

    def __init__(
        self,
        observation_space,
        action_space,
        index: int = 0,
        hp_config: Optional[HyperparameterConfig] = None,
        net_config: Optional[Dict[str, Any]] = None,
        batch_size: int = 64,
        lr: float = 3e-4,
        learn_step: int = 128,
        gamma: float = 0.99,
        gae_lambda: float = 0.95,
        clip_coef: float = 0.2,
        ent_coef: float = 0.01,
        vf_coef: float = 0.5,
        max_grad_norm: float = 0.5,
        update_epochs: int = 4,
        target_kl: Optional[float] = None,
        normalize_advantage: bool = True,
        num_envs: int = 1,
        recurrent: bool = False,
        seq_len: int = 16,
        use_rollout_buffer: bool = True,
        **kwargs,
    ):
        super().__init__(
            observation_space,
            action_space,
            index=index,
            hp_config=hp_config or default_hp_config(),
            **kwargs,
        )
        self.batch_size = int(batch_size)
        self.lr = float(lr)
        self.learn_step = int(learn_step)
        self.gamma = float(gamma)
        self.gae_lambda = float(gae_lambda)
        self.clip_coef = float(clip_coef)
        self.ent_coef = float(ent_coef)
        self.vf_coef = float(vf_coef)
        self.max_grad_norm = float(max_grad_norm)
        self.update_epochs = int(update_epochs)
        self.target_kl = target_kl
        self.normalize_advantage = bool(normalize_advantage)
        self.num_envs = int(num_envs)
        self.recurrent = bool(recurrent)
        self.seq_len = int(seq_len)
        self.use_rollout_buffer = bool(use_rollout_buffer)
        self.net_config = dict(net_config or {})

        net_kwargs = dict(self.net_config)
        if recurrent:
            net_kwargs["recurrent"] = True
        self.actor = StochasticActor(
            observation_space, action_space, key=self.next_key(), **net_kwargs
        )
        self.critic = ValueNetwork(observation_space, key=self.next_key(), **net_kwargs)

        self.optimizer = OptimizerWrapper(
            optimizer="adam", lr=self.lr, max_grad_norm=self.max_grad_norm
        )
        self.register_network_group(NetworkGroup(eval="actor", policy=True))
        self.register_network_group(NetworkGroup(eval="critic"))
        self.register_optimizer(
            OptimizerConfig(name="optimizer", networks=["actor", "critic"], lr="lr")
        )
        self.finalize_registry()

        self.rollout_buffer = RolloutBuffer(
            capacity=self.learn_step,
            num_envs=self.num_envs,
            gamma=self.gamma,
            gae_lambda=self.gae_lambda,
            recurrent=self.recurrent,
        )
        self._last_obs = None
        self._last_done = None
        self._hidden = None

    # ------------------------------------------------------------------ #
    @property
    def init_dict(self) -> Dict[str, Any]:
        return {
            "observation_space": self.observation_space,
            "action_space": self.action_space,
            "index": self.index,
            "net_config": self.net_config,
            "batch_size": self.batch_size,
            "lr": self.lr,
            "learn_step": self.learn_step,
            "gamma": self.gamma,
            "gae_lambda": self.gae_lambda,
            "clip_coef": self.clip_coef,
            "ent_coef": self.ent_coef,
            "vf_coef": self.vf_coef,
            "max_grad_norm": self.max_grad_norm,
            "update_epochs": self.update_epochs,
            "target_kl": self.target_kl,
            "num_envs": self.num_envs,
            "recurrent": self.recurrent,
            "seq_len": self.seq_len,
        }

    def value_of(self, obs: Any) -> np.ndarray:
        """Critic value of a (batched) observation — used for time-limit
        bootstrapping at truncation boundaries."""
        obs_p = self.preprocess_observation(obs)
        if self.recurrent:
            hidden = self._hidden or self.get_initial_hidden_state(
                jax.tree_util.tree_leaves(obs_p)[0].shape[0]
            )
            latent, _ = _lstm_encode(
                self.critic.config, self.critic.params, obs_p, hidden["critic"]
            )
            from agilerl_tpu.modules.mlp import EvolvableMLP

            return np.asarray(
                EvolvableMLP.apply(self.critic.config.head, self.critic.params["head"], latent)[..., 0]
            )
        return np.asarray(
            EvolvableNetwork.apply(self.critic.config, self.critic.params, obs_p)[..., 0]
        )

    def get_initial_hidden_state(self, num_envs: Optional[int] = None) -> Dict:
        """Zero hidden states for actor+critic LSTM encoders
        (parity: ppo.py:504)."""
        from agilerl_tpu.modules.lstm import EvolvableLSTM

        n = num_envs or self.num_envs
        return {
            "actor": EvolvableLSTM.initial_hidden(self.actor.config.encoder, n),
            "critic": EvolvableLSTM.initial_hidden(self.critic.config.encoder, n),
        }

    # ------------------------------------------------------------------ #
    def _act_fn(self):
        actor_cfg = self.actor.config
        critic_cfg = self.critic.config
        dist_cfg = self.actor.dist_config
        space = self.observation_space
        recurrent = self.recurrent

        @jax.jit
        def act(actor_params, critic_params, obs, key, hidden, mask=None):
            obs = preprocess_observation(space, obs)
            if recurrent:
                latent, new_ha = _lstm_encode(actor_cfg, actor_params, obs, hidden["actor"])
                from agilerl_tpu.modules.mlp import EvolvableMLP

                logits = EvolvableMLP.apply(actor_cfg.head, actor_params["head"], latent)
                latent_c, new_hc = _lstm_encode(critic_cfg, critic_params, obs, hidden["critic"])
                value = EvolvableMLP.apply(critic_cfg.head, critic_params["head"], latent_c)[..., 0]
                new_hidden = {"actor": new_ha, "critic": new_hc}
            else:
                logits = EvolvableNetwork.apply(actor_cfg, actor_params, obs)
                value = EvolvableNetwork.apply(critic_cfg, critic_params, obs)[..., 0]
                new_hidden = hidden
            dist_extra = actor_params.get("dist")
            action = D.sample(dist_cfg, logits, key, dist_extra, mask)
            logp = D.log_prob(dist_cfg, logits, action, dist_extra, mask=mask)
            return action, logp, value, new_hidden

        return act

    def get_action(
        self,
        obs: Any,
        action_mask: Optional[np.ndarray] = None,
        training: bool = True,
        hidden: Optional[Dict] = None,
    ):
        """Host API: returns numpy action (plus logp/value via get_action_and_value)."""
        a, _, _, _ = self.get_action_and_value(
            obs, hidden=hidden, deterministic=not training,
            action_mask=action_mask,
        )
        return a

    def get_action_and_value(
        self,
        obs: Any,
        hidden: Optional[Dict] = None,
        deterministic: bool = False,
        action_mask: Optional[np.ndarray] = None,
    ):
        single = not _batched(obs, self.observation_space)
        if single:
            obs = jax.tree_util.tree_map(lambda x: np.asarray(x)[None], obs)
            if action_mask is not None:
                action_mask = np.asarray(action_mask)[None]
        mask = None if action_mask is None else jnp.asarray(action_mask)
        if self.recurrent and hidden is None:
            batch = jax.tree_util.tree_leaves(obs)[0].shape[0]
            if self._hidden is None or (
                jax.tree_util.tree_leaves(self._hidden)[0].shape[1] != batch
            ):
                self._hidden = self.get_initial_hidden_state(batch)
            hidden = self._hidden
        act = self.jit_fn(
            "act", self._act_fn,
            static_key=(self.actor.config, self.critic.config, self.recurrent,
                        str(self.observation_space), str(self.action_space)),
        )
        if deterministic:
            obs_p = self.preprocess_observation(obs)
            if self.recurrent:
                latent, new_ha = _lstm_encode(
                    self.actor.config, self.actor.params, obs_p,
                    hidden["actor"] if hidden else self.get_initial_hidden_state()["actor"],
                )
                # advance hidden during greedy eval too — without this, test()
                # on a recurrent policy would re-zero memory every step
                if hidden is not None:
                    self._hidden = {**hidden, "actor": new_ha}
                from agilerl_tpu.modules.mlp import EvolvableMLP

                logits = EvolvableMLP.apply(self.actor.config.head, self.actor.params["head"], latent)
            else:
                logits = EvolvableNetwork.apply(self.actor.config, self.actor.params, obs_p)
            action = D.mode(self.actor.dist_config, logits, mask)
            out = (np.asarray(action), None, None, hidden)
        else:
            action, logp, value, new_hidden = act(
                self.actor.params, self.critic.params, obs, self.next_key(),
                hidden if hidden is not None else {}, mask,
            )
            if self.recurrent:
                self._hidden = new_hidden
            out = (np.asarray(action), np.asarray(logp), np.asarray(value), new_hidden)
        if single:
            out = (out[0][0],) + out[1:]
        return out

    # ------------------------------------------------------------------ #
    def _update_fn(self):
        actor_cfg = self.actor.config
        critic_cfg = self.critic.config
        dist_cfg = self.actor.dist_config
        space = self.observation_space
        tx = self.optimizer.tx
        normalize_advantage = self.normalize_advantage

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt_state, batch, clip, ent_coef, vf_coef):
            def loss_fn(p):
                obs = preprocess_observation(space, batch["obs"])
                logits = EvolvableNetwork.apply(actor_cfg, p["actor"], obs)
                dist_extra = p["actor"].get("dist")
                mask = batch.get("action_mask")
                new_logp = D.log_prob(dist_cfg, logits, batch["action"], dist_extra,
                                      mask=mask)
                entropy = D.entropy(dist_cfg, logits, dist_extra, mask=mask).mean()
                value = EvolvableNetwork.apply(critic_cfg, p["critic"], obs)[..., 0]

                adv = batch["advantages"]
                if normalize_advantage:
                    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
                logratio = new_logp - batch["log_prob"]
                ratio = jnp.exp(logratio)
                pg1 = -adv * ratio
                pg2 = -adv * jnp.clip(ratio, 1 - clip, 1 + clip)
                pg_loss = jnp.maximum(pg1, pg2).mean()
                v_loss = 0.5 * jnp.square(value - batch["returns"]).mean()
                loss = pg_loss - ent_coef * entropy + vf_coef * v_loss
                approx_kl = ((ratio - 1) - logratio).mean()
                return loss, (pg_loss, v_loss, entropy, approx_kl)

            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, aux

        return update

    def _update_bptt_fn(self):
        actor_cfg = self.actor.config
        critic_cfg = self.critic.config
        dist_cfg = self.actor.dist_config
        space = self.observation_space
        tx = self.optimizer.tx
        normalize_advantage = self.normalize_advantage

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt_state, batch, clip, ent_coef, vf_coef):
            # batch leaves: [B, S, ...]; hidden_state: per-net {h,c} [B, L, H]
            def loss_fn(p):
                obs = preprocess_observation(space, batch["obs"])
                logits = _lstm_encode_seq(actor_cfg, p["actor"], obs, batch["hidden_state"]["actor"])
                from agilerl_tpu.modules.mlp import EvolvableMLP

                logits = EvolvableMLP.apply(actor_cfg.head, p["actor"]["head"], logits)
                values = _lstm_encode_seq(
                    critic_cfg, p["critic"], obs, batch["hidden_state"]["critic"]
                )
                values = EvolvableMLP.apply(critic_cfg.head, p["critic"]["head"], values)[..., 0]
                dist_extra = p["actor"].get("dist")
                mask = batch.get("action_mask")
                new_logp = D.log_prob(dist_cfg, logits, batch["action"], dist_extra,
                                      mask=mask)
                entropy = D.entropy(dist_cfg, logits, dist_extra, mask=mask).mean()
                adv = batch["advantages"]
                if normalize_advantage:
                    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
                logratio = new_logp - batch["log_prob"]
                ratio = jnp.exp(logratio)
                pg1 = -adv * ratio
                pg2 = -adv * jnp.clip(ratio, 1 - clip, 1 + clip)
                pg_loss = jnp.maximum(pg1, pg2).mean()
                v_loss = 0.5 * jnp.square(values - batch["returns"]).mean()
                loss = pg_loss - ent_coef * entropy + vf_coef * v_loss
                approx_kl = ((ratio - 1) - logratio).mean()
                return loss, (pg_loss, v_loss, entropy, approx_kl)

            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, aux

        return update

    def _scan_learn_fn(self, total: int):
        """Whole PPO update (epochs x minibatches) as ONE jitted program —
        no host dispatch per minibatch (the TPU-side answer to the reference's
        per-minibatch torch steps)."""
        actor_cfg = self.actor.config
        critic_cfg = self.critic.config
        dist_cfg = self.actor.dist_config
        space = self.observation_space
        tx = self.optimizer.tx
        normalize_advantage = self.normalize_advantage
        mb = min(self.batch_size, total)
        n_mb = max(total // mb, 1)
        epochs = self.update_epochs

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def scan_learn(params, opt_state, data, key, clip, ent_coef, vf_coef):
            def minibatch(carry, b):
                params, opt_state = carry

                def loss_fn(p):
                    obs = preprocess_observation(space, b["obs"])
                    logits = EvolvableNetwork.apply(actor_cfg, p["actor"], obs)
                    extra = p["actor"].get("dist")
                    mask = b.get("action_mask")
                    new_logp = D.log_prob(dist_cfg, logits, b["action"], extra,
                                          mask=mask)
                    entropy = D.entropy(dist_cfg, logits, extra, mask=mask).mean()
                    value = EvolvableNetwork.apply(critic_cfg, p["critic"], obs)[..., 0]
                    adv = b["advantages"]
                    if normalize_advantage:
                        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
                    ratio = jnp.exp(new_logp - b["log_prob"])
                    pg = jnp.maximum(
                        -adv * ratio, -adv * jnp.clip(ratio, 1 - clip, 1 + clip)
                    ).mean()
                    v_loss = 0.5 * jnp.square(value - b["returns"]).mean()
                    return pg - ent_coef * entropy + vf_coef * v_loss

                loss, grads = jax.value_and_grad(loss_fn)(params)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), loss

            def epoch(carry, k):
                params, opt_state = carry
                batches = shuffled_minibatches(k, data, n_mb, mb)
                (params, opt_state), losses = jax.lax.scan(
                    minibatch, (params, opt_state), batches
                )
                return (params, opt_state), losses.mean()

            keys = jax.random.split(key, epochs)
            (params, opt_state), losses = jax.lax.scan(
                epoch, (params, opt_state), keys
            )
            return params, opt_state, losses.mean()

        return scan_learn

    def learn(self, experiences: Optional[Tuple] = None) -> float:
        """Update from the rollout buffer (parity: ppo.py:635)."""
        buf = self.rollout_buffer
        assert buf.state is not None, "collect rollouts before learn()"
        # bootstrap value for the final obs
        last_obs = self.preprocess_observation(self._last_obs)
        if self.recurrent:
            latent, _ = _lstm_encode(
                self.critic.config, self.critic.params, last_obs,
                (self._hidden or self.get_initial_hidden_state())["critic"],
            )
            from agilerl_tpu.modules.mlp import EvolvableMLP

            last_value = EvolvableMLP.apply(
                self.critic.config.head, self.critic.params["head"], latent
            )[..., 0]
        else:
            last_value = EvolvableNetwork.apply(
                self.critic.config, self.critic.params, last_obs
            )[..., 0]
        buf.compute_returns_and_advantages(last_value, jnp.asarray(self._last_done))

        params = {"actor": self.actor.params, "critic": self.critic.params}
        opt_state = self.optimizer.opt_state
        mean_loss, n_updates = 0.0, 0

        if self.recurrent:
            update = self.jit_fn("update_bptt", self._update_bptt_fn)
            seqs = buf.get_sequences(self.seq_len)
            n_seqs = jax.tree_util.tree_leaves(seqs["action"])[0].shape[0]
            mb = max(self.batch_size // self.seq_len, 1)
            for _ in range(self.update_epochs):
                perm = np.asarray(jax.random.permutation(self.next_key(), n_seqs))
                for s in range(0, n_seqs, mb):
                    idx = perm[s : s + mb]
                    batch = jax.tree_util.tree_map(lambda x: x[idx], seqs)
                    params, opt_state, loss, aux = update(
                        params, opt_state, batch,
                        jnp.float32(self.clip_coef), jnp.float32(self.ent_coef),
                        jnp.float32(self.vf_coef),
                    )
                    mean_loss += float(loss)
                    n_updates += 1
                if self.target_kl is not None and float(aux[3]) > 1.5 * self.target_kl:
                    break
        elif self.target_kl is None:
            # fully device-side path: the whole update is one XLA program
            data = buf.get_all_flat()
            total = jax.tree_util.tree_leaves(data["action"])[0].shape[0]
            scan_learn = self.jit_fn(
                f"scan_learn_{total}", lambda: self._scan_learn_fn(total),
                static_key=(self.actor.config, self.critic.config,
                            self.normalize_advantage, total, self.batch_size,
                            self.update_epochs, str(self.observation_space),
                            str(self.action_space), self.optimizer.optimizer_name,
                            self.optimizer.max_grad_norm),
            )
            params, opt_state, loss = scan_learn(
                params, opt_state, data, self.next_key(),
                jnp.float32(self.clip_coef), jnp.float32(self.ent_coef),
                jnp.float32(self.vf_coef),
            )
            mean_loss += float(loss)
            n_updates += 1
        else:
            update = self.jit_fn(
                "update", self._update_fn,
                static_key=(self.actor.config, self.critic.config,
                            self.normalize_advantage, str(self.observation_space),
                            str(self.action_space), self.optimizer.optimizer_name,
                            self.optimizer.max_grad_norm),
            )
            for _ in range(self.update_epochs):
                idxs = buf.minibatch_indices(self.batch_size, key=self.next_key())
                for idx in idxs:
                    batch = buf.get_batch(idx)
                    params, opt_state, loss, aux = update(
                        params, opt_state, batch,
                        jnp.float32(self.clip_coef), jnp.float32(self.ent_coef),
                        jnp.float32(self.vf_coef),
                    )
                    mean_loss += float(loss)
                    n_updates += 1
                if self.target_kl is not None and float(aux[3]) > 1.5 * self.target_kl:
                    break

        self.actor.params = params["actor"]
        self.critic.params = params["critic"]
        self.optimizer.opt_state = opt_state
        buf.reset()
        return mean_loss / max(n_updates, 1)

    def test(self, env, swap_channels=False, max_steps=None, loop=3, sum_scores=True):
        if self.recurrent:
            self._hidden = None
        return super().test(env, swap_channels, max_steps, loop, sum_scores)


# --------------------------------------------------------------------------- #
# LSTM-encoder helpers (single step + sequence) for recurrent PPO
# --------------------------------------------------------------------------- #


def _batched(obs, space) -> bool:
    from agilerl_tpu.algorithms.dqn import _is_single

    pre = preprocess_observation(space, obs)
    return not _is_single(pre, space)


def _lstm_encode(net_cfg, params, obs, hidden):
    """One-step LSTM encode: obs [B, D] -> latent [B, latent], new hidden."""
    from agilerl_tpu.modules.lstm import EvolvableLSTM

    return EvolvableLSTM.apply(
        net_cfg.encoder, params["encoder"], obs, hidden=hidden, return_hidden=True
    )


def _lstm_encode_seq(net_cfg, params, obs_seq, hidden0):
    """Sequence encode: obs [B, S, D], hidden0 leaves [B, L, H] -> latent [B, S, latent]."""
    from agilerl_tpu.modules.lstm import EvolvableLSTM

    def one(obs, h0):
        # obs [S, D] -> time-major [S, 1, D]
        hidden = {"h": h0["h"][:, None, :], "c": h0["c"][:, None, :]}
        cfg = net_cfg.encoder
        seq = obs[:, None, :]
        outs = []
        import jax.numpy as jnp

        from agilerl_tpu.modules import layers as L

        x = seq.astype(jnp.float32)
        hs, cs = hidden["h"], hidden["c"]
        for i in range(cfg.num_layers):
            x, _ = L.lstm_scan(params["encoder"][f"lstm_{i}"], x, hs[i], cs[i])
        out = L.dense_apply(params["encoder"]["output"], x[:, 0, :])
        return out  # [S, latent]

    return jax.vmap(one)(obs_seq, hidden0)
