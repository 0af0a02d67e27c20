"""Action distributions over network heads, with masking
(parity: agilerl/networks/distributions.py — EvolvableDistribution:110,
apply_mask:239, TorchDistribution:31).

Pure-functional: a frozen DistConfig describes the distribution family; all ops
(sample / log_prob / entropy) are jittable functions of (config, dist_params,
key). dist_params come straight off the actor head; Normal heads carry a
state-independent learnable log_std vector alongside.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from gymnasium import spaces

NEG_INF = -1e8


@dataclasses.dataclass(frozen=True)
class DistConfig:
    kind: str  # "categorical" | "normal" | "multidiscrete" | "bernoulli"
    action_dim: int
    nvec: Tuple[int, ...] = ()  # for multidiscrete
    log_std_init: float = 0.0
    squash: bool = False


def dist_config_from_space(space) -> DistConfig:
    if isinstance(space, spaces.Discrete):
        return DistConfig(kind="categorical", action_dim=int(space.n))
    if isinstance(space, spaces.MultiDiscrete):
        nvec = tuple(int(n) for n in space.nvec)
        return DistConfig(kind="multidiscrete", action_dim=int(sum(nvec)), nvec=nvec)
    if isinstance(space, spaces.MultiBinary):
        import numpy as np

        return DistConfig(kind="bernoulli", action_dim=int(np.prod(space.shape)))
    if isinstance(space, spaces.Box):
        import numpy as np

        return DistConfig(kind="normal", action_dim=int(np.prod(space.shape)))
    raise TypeError(f"Unsupported action space {type(space)}")


def head_output_dim(config: DistConfig) -> int:
    """Number of raw head outputs the distribution consumes."""
    return config.action_dim


def extra_params(config: DistConfig) -> dict:
    """Learnable distribution params outside the head (Normal log_std)."""
    if config.kind == "normal":
        return {"log_std": jnp.full((config.action_dim,), config.log_std_init)}
    return {}


def apply_mask(config: DistConfig, logits: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    """Set masked-out action logits to -inf (parity: distributions.py:239)."""
    if mask is None or config.kind == "normal":
        return logits
    return jnp.where(mask.astype(bool), logits, NEG_INF)


def sample(
    config: DistConfig,
    logits: jax.Array,
    key: jax.Array,
    dist_extra: Optional[dict] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    logits = apply_mask(config, logits, mask)
    if config.kind == "categorical":
        return jax.random.categorical(key, logits, axis=-1)
    if config.kind == "multidiscrete":
        outs = []
        for i, (start, n) in enumerate(_md_slices(config)):
            sub = logits[..., start : start + n]
            outs.append(jax.random.categorical(jax.random.fold_in(key, i), sub, axis=-1))
        return jnp.stack(outs, axis=-1)
    if config.kind == "bernoulli":
        p = jax.nn.sigmoid(logits)
        return (jax.random.uniform(key, logits.shape) < p).astype(jnp.int32)
    # normal
    std = jnp.exp(dist_extra["log_std"])
    eps = jax.random.normal(key, logits.shape)
    action = logits + std * eps
    return jnp.tanh(action) if config.squash else action


def mode(config: DistConfig, logits: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    logits = apply_mask(config, logits, mask)
    if config.kind == "categorical":
        return jnp.argmax(logits, axis=-1)
    if config.kind == "multidiscrete":
        return jnp.stack(
            [
                jnp.argmax(logits[..., s : s + n], axis=-1)
                for s, n in _md_slices(config)
            ],
            axis=-1,
        )
    if config.kind == "bernoulli":
        return (logits > 0).astype(jnp.int32)
    return jnp.tanh(logits) if config.squash else logits


def log_prob(
    config: DistConfig,
    logits: jax.Array,
    action: jax.Array,
    dist_extra: Optional[dict] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Log-probability of ``action`` under the head's distribution.

    Discrete heads expect each action index in ``[0, n)`` of its head, as
    ``sample`` draws them. An index outside that range (negative ones too:
    nothing wraps) selects no entry and contributes 0 to the result, with a
    zero gradient. A chosen action that the mask rules out scores
    ``NEG_INF``-low, not NaN.
    """
    logits = apply_mask(config, logits, mask)
    if config.kind == "categorical":
        return _chosen(jax.nn.log_softmax(logits, axis=-1), action)
    if config.kind == "multidiscrete":
        total = 0.0
        for i, (s, n) in enumerate(_md_slices(config)):
            logp = jax.nn.log_softmax(logits[..., s : s + n], axis=-1)
            total = total + _chosen(logp, action[..., i])
        return total
    if config.kind == "bernoulli":
        logp = -jax.nn.softplus(-logits) * action - jax.nn.softplus(logits) * (1 - action)
        return jnp.sum(logp, axis=-1)
    # normal (diagonal); squash=True scores a=tanh(u) with the change of
    # variables log p(a) = log N(atanh(a)) - sum log(1 - a^2)
    log_std = dist_extra["log_std"]
    var = jnp.exp(2 * log_std)
    if config.squash:
        a = jnp.clip(action, -1.0 + 1e-6, 1.0 - 1e-6)
        u = jnp.arctanh(a)
        logp = -0.5 * ((u - logits) ** 2 / var + 2 * log_std + jnp.log(2 * jnp.pi))
        logp = logp - jnp.log(1.0 - jnp.square(a) + 1e-6)
        return jnp.sum(logp, axis=-1)
    logp = -0.5 * ((action - logits) ** 2 / var + 2 * log_std + jnp.log(2 * jnp.pi))
    return jnp.sum(logp, axis=-1)


def entropy(
    config: DistConfig,
    logits: jax.Array,
    dist_extra: Optional[dict] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    logits = apply_mask(config, logits, mask)
    if config.kind == "categorical":
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    if config.kind == "multidiscrete":
        total = 0.0
        for s, n in _md_slices(config):
            logp = jax.nn.log_softmax(logits[..., s : s + n], axis=-1)
            total = total - jnp.sum(jnp.exp(logp) * logp, axis=-1)
        return total
    if config.kind == "bernoulli":
        p = jax.nn.sigmoid(logits)
        h = jax.nn.softplus(-logits) + logits * (1 - p)
        return jnp.sum(h, axis=-1)
    log_std = dist_extra["log_std"]
    base = jnp.sum(log_std + 0.5 * jnp.log(2 * jnp.pi * jnp.e), axis=-1) * jnp.ones(
        logits.shape[:-1]
    )
    if config.squash:
        # H[tanh(u)] = H[u] + E[log(1 - tanh(u)^2)]; the expectation is
        # approximated at the mean (documented approximation — exact value has
        # no closed form)
        base = base + jnp.sum(jnp.log(1.0 - jnp.square(jnp.tanh(logits)) + 1e-6), axis=-1)
    return base


def _chosen(logp: jax.Array, index: jax.Array) -> jax.Array:
    """``logp[..., index]`` as compare-select-reduce over the last axis. XLA
    fuses it into the log-softmax that made the row; a gather (and in the
    backward a scatter) pays ~14 ns an index on a TPU v5e whatever it
    fetches (PERF.md section 6, PR 30). ``where`` and not a one-hot product:
    a masked entry may be -inf, and -inf * 0 is NaN. Adding exact zeros
    changes no bit, so this equals the gather for every in-range index."""
    hit = jnp.arange(logp.shape[-1]) == index[..., None].astype(jnp.int32)
    return jnp.sum(jnp.where(hit, logp, 0), axis=-1)


def _md_slices(config: DistConfig):
    out = []
    start = 0
    for n in config.nvec:
        out.append((start, n))
        start += n
    return out
