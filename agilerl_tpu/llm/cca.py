"""Compressed convolutional attention (CCA): attention that runs in a latent
``H x hd`` / ``K x hd`` wide (narrower than ``d_model``), whose queries and
keys are mixed along the sequence before they meet. On the block's normed
input ``x`` [T, d], with ``G = H / K`` query heads a key head:

    qt, kt   = x Wq, x Wk                         -> H x hd | K x hd
    mq[i]    = (qt[i] + kt[i // G]) / 2           the mean a query head
    mk[j]    = mean of mq[i] over the group j     the mean a key head
    p        = [qt ; kt]                          (H + K) heads of hd
    c0[t]    = b0 + sum_u w0[u] * p[t-(k0-1)+u]   depthwise, causal
    c1[t, n] = b1[n] + sum_u c0[t-(k1-1)+u, n] W1[u, n]   a head, causal
    q', k'   = c1[:H] + mq, c1[H:] + mk
    q, k     = sqrt(hd) q'/|q'|, tau[j] sqrt(hd) k'/|k'|   (L2 a head)
    q, k     = rope on the first rotary_share * hd dimensions of a head
    v        = [x[t] Wv1 ; x[t-1] Wv2]            half the key heads each
    out      = softmax(q k^T / sqrt(hd)) v        H heads on K, causal

Positions before a sequence's first are zero, in ``p``, in ``c0`` and in
the shifted values alike. What a cache keeps of a token is ``k`` and ``v``
in the ordinary layout (``K`` heads of ``hd``), so the paged pool, flash
attention and ``ops/decode_attention`` are untouched. What CCA adds is a
ROLLING STATE a sequence, a layer: the last ``k0 - 1`` rows of ``p``, the
last ``k1 - 1`` rows of ``c0`` and the last token's ``x Wv2`` — what the
next position's convolutions and value shift read.

``qkv`` is the one home of this mathematics, in the three shapes
``ssm.mixer`` has:

- no state (training / log-probabilities): the whole sequence from zeros;
- a state and ``T > 1`` (prefill): the sequence continues from the state,
  and the state BEFORE the last token comes back too — the serving tier
  snapshots it, because a prefix-cache hit re-enters the last prompt token;
- a state and ``T == 1`` (decode): the one-token step.

The last two are one code path: the state is a window of inputs, not a
recurrence, so a step is a forward of one position. A position whose mask
is 0 adds zeros to the windows (left padding is exact), and a call whose
LAST position is masked (a finished or free slot's step) leaves the state
as it was.

The taps are added in float32 in one fixed order, ``c0`` is kept in the
compute dtype (the state holds it so), the norms, ``tau`` and the rotation
are float32.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from agilerl_tpu.llm.model import GPTConfig, _maybe_lora, _normal, _rope

PROJECT_SCOPE = "cca/project"
MIX_SCOPE = "cca/mix"
STATE_SCOPE = "cca/state"


def widths(config: GPTConfig) -> Tuple[int, int]:
    """(query latent, key latent): ``H hd`` and ``K hd``."""
    return (config.n_head * config.head_dim,
            config.kv_heads * config.head_dim)


def cca_dims(config: GPTConfig) -> Dict[str, Tuple[int, int]]:
    """(in, out) of the mixer's projections, which are also those that take
    a LoRA adapter."""
    d = config.d_model
    dq, dk = widths(config)
    return {"wq": (d, dq), "wk": (d, dk), "wv1": (d, dk // 2),
            "wv2": (d, dk // 2), "wo": (dq, d)}


def init_cca_mixer(key: jax.Array, config: GPTConfig, out_std: float) -> Dict:
    """Projections normal(0, 0.02) like the rest of the model; both
    convolutions as a Conv1d is drawn by default (uniform +-1/sqrt(fan in),
    biases too), so that no tap is an identity and the conv path is of the
    size of the mean path; ``tau`` log-normal around 1 — not AT 1, where it
    would check nothing."""
    dims = cca_dims(config)
    dq, dk = widths(config)
    hd, k0, k1 = config.head_dim, config.cca_time0, config.cca_time1
    ks = jax.random.split(key, 10)
    b0, b1 = 1.0 / math.sqrt(k0), 1.0 / math.sqrt(k1 * hd)
    uniform = lambda k, shape, b: jax.random.uniform(  # noqa: E731
        k, shape, jnp.float32, -b, b)
    return {
        "wq": _normal(ks[0], dims["wq"], 0.02),
        "wk": _normal(ks[1], dims["wk"], 0.02),
        "wv1": _normal(ks[2], dims["wv1"], 0.02),
        "wv2": _normal(ks[3], dims["wv2"], 0.02),
        "wo": _normal(ks[4], dims["wo"], out_std),
        "conv0_w": uniform(ks[5], (k0, dq + dk), b0),
        "conv0_b": uniform(ks[6], (dq + dk,), b0),
        # [tap, head, in, out]: a head's hd channels mix among themselves
        "conv1_w": uniform(ks[7], (k1, (dq + dk) // hd, hd, hd), b1),
        "conv1_b": uniform(ks[8], (dq + dk,), b1),
        "tau": jnp.exp(0.2 * jax.random.normal(ks[9], (config.kv_heads,),
                                               jnp.float32)),
    }


def init_state(config: GPTConfig, n_layers: int, batch: int):
    """(p window [n, B, k0-1, C], c0 window [n, B, k1-1, C], previous
    token's ``x Wv2`` [n, B, K hd / 2]), compute dtype, ``C = (H + K) hd``."""
    dq, dk = widths(config)
    zeros = lambda *shape: jnp.zeros((n_layers, batch, *shape), config.dtype)  # noqa: E731
    return (zeros(config.cca_time0 - 1, dq + dk),
            zeros(config.cca_time1 - 1, dq + dk), zeros(dk // 2))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-24)


def qkv(config: GPTConfig, blk: Dict, x: jax.Array, positions: jax.Array,
        mask: jax.Array, state=None, lora_layer=None, lora_scale: float = 2.0):
    """x [B, T, d] (normed), positions and mask [B, T] -> (q [B, T, H, hd],
    k, v [B, T, K, hd], new_state, prev_state): ``new_state`` after the last
    position, ``prev_state`` before it (``T > 1`` with a state only); both
    None without a state."""
    B, T, _ = x.shape
    dtype, f32 = x.dtype, jnp.float32
    H, K, hd = config.n_head, config.kv_heads, config.head_dim
    k0, k1 = config.cca_time0, config.cca_time1
    dq, dk = widths(config)
    m = mask.astype(dtype)[..., None]
    proj = lambda name: _maybe_lora(  # noqa: E731
        x, blk[name], lora_layer, name, lora_scale, dtype)
    with jax.named_scope(PROJECT_SCOPE):
        qt, kt, v1 = proj("wq"), proj("wk"), proj("wv1")
        v2 = proj("wv2") * m
        p = jnp.concatenate([qt, kt], axis=-1) * m
    if state is None:
        left = tuple(jnp.zeros((B, *s.shape[2:]), dtype)
                     for s in init_state(config, 1, B))
    else:
        left = state
    with jax.named_scope(STATE_SCOPE):
        ppad = jnp.concatenate([left[0], p], axis=1)  # [B, k0-1+T, C]
        v2pad = jnp.concatenate([left[2][:, None], v2], axis=1)  # [B, 1+T, .]
    with jax.named_scope(MIX_SCOPE):
        c0 = blk["conv0_b"].astype(f32)
        for u in range(k0):
            c0 = c0 + blk["conv0_w"][u].astype(f32) * ppad[:, u:u + T].astype(f32)
        c0 = c0.astype(dtype) * m
    with jax.named_scope(STATE_SCOPE):
        c0pad = jnp.concatenate([left[1], c0], axis=1)  # [B, k1-1+T, C]
    with jax.named_scope(MIX_SCOPE):
        c1 = blk["conv1_b"].astype(f32).reshape(H + K, hd)
        for u in range(k1):
            # operands widened, not the product: both hold compute-dtype
            # values, which the MXU's default single pass multiplies exactly
            # and adds in float32
            c1 = c1 + jnp.einsum(
                "btni,nio->btno",
                c0pad[:, u:u + T].reshape(B, T, H + K, hd).astype(f32),
                blk["conv1_w"][u].astype(dtype).astype(f32))
        qh = qt.astype(f32).reshape(B, T, K, H // K, hd)
        mq = (qh + kt.astype(f32).reshape(B, T, K, 1, hd)) / 2
        mk = mq.mean(axis=3)
        q = math.sqrt(hd) * _l2(c1[:, :, :H] + mq.reshape(B, T, H, hd))
        k = (blk["tau"].astype(f32)[:, None] * math.sqrt(hd)
             * _l2(c1[:, :, H:] + mk))
        rd = int(hd * config.rotary_share)
        rot = lambda a: jnp.concatenate(  # noqa: E731
            [_rope(a[..., :rd], positions, config.rope_theta), a[..., rd:]],
            axis=-1).astype(dtype)
        q, k = rot(q), rot(k)
        v = jnp.concatenate([v1.reshape(B, T, K // 2, hd),
                             v2pad[:, :T].reshape(B, T, K // 2, hd)], axis=2)
    if state is None:
        return q, k, v, None, None
    with jax.named_scope(STATE_SCOPE):
        keep = mask[:, -1].astype(bool)
        new_state = tuple(
            jnp.where(keep.reshape((B,) + (1,) * (old.ndim - 1)), new, old)
            for new, old in zip((ppad[:, T:], c0pad[:, T:], v2pad[:, T]),
                                state))
        prev_state = None
        if T > 1:
            prev_state = (ppad[:, T - 1:T + k0 - 2],
                          c0pad[:, T - 1:T + k1 - 2], v2pad[:, T - 1])
    return q, k, v, new_state, prev_state
