"""Latent attention (MLA) as DeepSeek-V3-class models run it, without a
query latent (``q_lora_rank`` null):

    q          = x Wq                       -> H x (nope + rope)
    ckv, k_r   = split(x Wkv_a)             -> kv_lora_rank | rope (ONE head)
    c          = rms(ckv)                   (kv_norm)
    q_r, k_r   = rope(q_r), rope(k_r)       pairs (2i, 2i+1), as model._rope
    k_n, v     = split(c Wkv_b)             -> H x (nope | v)
    out        = softmax([q_n|q_r] [k_n|k_r]^T / sqrt(nope + rope)) v

What a cache holds of a token is ``[c | k_r]`` (``latent_width`` values, the
rotation already applied) and nothing else: one array without a head axis,
no V array. Two forms of one mathematics:

- ``attend_expanded`` (no cache: training and log-probabilities): ``Wkv_b``
  up-projects every position to per-head keys and values and plain causal
  attention runs on them — through the flash kernel where asked, with keys
  ``nope + rope`` wide and values ``v_head_dim`` wide;
- ``attend_absorbed`` (any cached forward: prefill, decode, the paged
  tier): with ``Wkv_b`` parted a head into ``W_uk`` and ``W_uv``,
  ``q_lat = q_n W_uk^T`` scores straight against the cached ``c``
  (``q_lat . c + q_r . k_r``), the probabilities average ``c`` itself, and
  ``W_uv`` projects that average: ``ops.decode_attention``'s loop (its
  contiguous or its paged entry, as the caller's cache is laid out) with H
  query heads on one latent head whose value is the first ``kv_lora_rank``
  columns of its key. An adapter on ``wkv_b`` adds its low-rank part to both
  products; no weight is merged.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from agilerl_tpu.llm.model import GPTConfig, _maybe_lora, _normal, _rms, _rope

EXPAND_SCOPE = "mla/expand"
ABSORB_SCOPE = "mla/absorb"
ATTEND_SCOPE = "mla/attend"


def latent_width(config: GPTConfig) -> int:
    return config.kv_lora_rank + config.qk_rope_dim


def mla_dims(config: GPTConfig) -> Dict[str, Tuple[int, int]]:
    """(in, out) of the mixer's projections, which are also those that take
    a LoRA adapter."""
    d, nh = config.d_model, config.n_head
    return {
        "wq": (d, nh * (config.qk_nope_dim + config.qk_rope_dim)),
        "wkv_a": (d, latent_width(config)),
        "wkv_b": (config.kv_lora_rank,
                  nh * (config.qk_nope_dim + config.v_head_dim)),
        "wo": (nh * config.v_head_dim, d),
    }


def init_mla_mixer(key: jax.Array, config: GPTConfig, out_std: float) -> Dict:
    dims = mla_dims(config)
    ks = jax.random.split(key, 4)
    return {
        "wq": _normal(ks[0], dims["wq"], 0.02),
        "wkv_a": _normal(ks[1], dims["wkv_a"], 0.02),
        "kv_norm": jnp.ones((config.kv_lora_rank,), jnp.float32),
        "wkv_b": _normal(ks[2], dims["wkv_b"], 0.02),
        "wo": _normal(ks[3], dims["wo"], out_std),
    }


def project(config: GPTConfig, blk, x, positions, lora_layer, lora_scale):
    """x [B, T, d] (normed) -> (q_nope [B, T, H, nope], q_rope [B, T, H,
    rope] rotated, latent [B, T, rank + rope] = [rms(ckv) | rotated k_rope]:
    what the cache keeps of these positions)."""
    B, T = x.shape[:2]
    dtype = x.dtype
    r = config.kv_lora_rank
    q = _maybe_lora(x, blk["wq"], lora_layer, "wq", lora_scale, dtype)
    q = q.reshape(B, T, config.n_head, config.qk_nope_dim + config.qk_rope_dim)
    q_nope, q_rope = q[..., :config.qk_nope_dim], q[..., config.qk_nope_dim:]
    kv = _maybe_lora(x, blk["wkv_a"], lora_layer, "wkv_a", lora_scale, dtype)
    c = _rms(kv[..., :r], blk["kv_norm"], config.rms_eps)
    k_rope = kv[..., None, r:]  # one head, shared by every query head
    q_rope = _rope(q_rope, positions, config.rope_theta)
    k_rope = _rope(k_rope, positions, config.rope_theta)
    return q_nope, q_rope, jnp.concatenate([c, k_rope[:, :, 0]], -1)


def attend_expanded(config: GPTConfig, blk, q_nope, q_rope, latent,
                    attention_mask, lora_layer, lora_scale, use_flash):
    """Causal attention over the positions of this call alone. Returns
    [B, T, H * v_head_dim]."""
    B, T, H = q_nope.shape[:3]
    dtype = q_nope.dtype
    nope, dv = config.qk_nope_dim, config.v_head_dim
    r = config.kv_lora_rank
    with jax.named_scope(EXPAND_SCOPE):
        kv = _maybe_lora(latent[..., :r], blk["wkv_b"], lora_layer,
                         "wkv_b", lora_scale, dtype).reshape(B, T, H, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(latent[:, :, None, r:],
                              (B, T, H, config.qk_rope_dim))],
            axis=-1)
        v = kv[..., nope:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    qh, kh, vh = (jnp.moveaxis(a, 2, 1) for a in (q, k, v))  # [B, H, T, .]
    if use_flash:
        from agilerl_tpu.ops.flash_attention_vjp import flash_attention_diff

        # spmd=False: the kernel's partitioning rule names one head size
        # for q, k and v, and a mesh refuses this stack before it gets here.
        # The tiles are flash_plan's, from (T, 192, 128, dtype).
        attn = flash_attention_diff(qh, kh, vh, attention_mask, True,
                                    spmd=False)
    else:
        t_ids = jnp.arange(T)
        mask = t_ids[None, None, :] <= t_ids[None, :, None]
        mask = jnp.logical_and(mask, attention_mask[:, None, :].astype(bool))
        scores = jnp.einsum("bhtd,bhsd->bhts", qh, kh).astype(jnp.float32)
        scores = scores / math.sqrt(q.shape[-1])
        scores = jnp.where(mask[:, None, :, :], scores, -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        attn = jnp.einsum("bhts,bhsd->bhtd", probs, vh)
    return jnp.moveaxis(attn, 1, 2).reshape(B, T, H * dv)


def _lora_ab(lora_layer, dtype, config: GPTConfig):
    """The adapter on ``wkv_b`` as (A [kv_lora_rank, r], B [r, H, nope +
    v]) or None."""
    if lora_layer is None or "wkv_b" not in lora_layer:
        return None
    a = lora_layer["wkv_b"]["A"].astype(dtype)
    b = lora_layer["wkv_b"]["B"].astype(dtype)
    return a, b.reshape(b.shape[0], config.n_head,
                        config.qk_nope_dim + config.v_head_dim)


def attend_absorbed(config: GPTConfig, blk, q_nope, q_rope, cache, valid,
                    start, lora_layer, lora_scale):
    """Attention of T queries over a latent cache (query t sees slot j iff
    j <= start[b] + t and valid[b, j]). ``cache`` is a slab [B, S, rank +
    rope] that already holds this call's positions (``model.forward``), or
    — a paged cache, ``model.forward_paged`` — the tuple (one layer's pool
    [nb, bs, rank + rope] as it was before this call, block tables [B,
    max_blocks], this call's latent [B, T, rank + rope], its logical slots
    ``write_pos`` [B] or [B, T]): the pool is then read through the table a
    live chunk at a time and no slab is built. Returns [B, T, H *
    v_head_dim]."""
    from agilerl_tpu.ops.decode_attention import (
        chunked_cached_attention,
        chunked_paged_attention,
    )

    B, T, H = q_nope.shape[:3]
    dtype = q_nope.dtype
    nope, dv, r = config.qk_nope_dim, config.v_head_dim, config.kv_lora_rank
    w = blk["wkv_b"].astype(dtype).reshape(r, H, nope + dv)
    ab = _lora_ab(lora_layer, dtype, config)
    with jax.named_scope(ABSORB_SCOPE):
        q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w[..., :nope])
        if ab is not None:
            q_lat = q_lat + jnp.einsum(
                "bthr,cr->bthc",
                jnp.einsum("bthn,rhn->bthr", q_nope, ab[1][..., :nope]),
                ab[0]) * lora_scale
        q = jnp.concatenate([q_lat, q_rope], axis=-1)
    with jax.named_scope(ATTEND_SCOPE):
        kw = dict(scale=1.0 / math.sqrt(nope + config.qk_rope_dim), v_width=r)
        if isinstance(cache, tuple):
            pool, block_tables, lat, write_pos = cache
            o_lat = chunked_paged_attention(
                q, pool, None, block_tables, lat, None, write_pos, valid,
                start, **kw)
        else:
            o_lat = chunked_cached_attention(q, cache, None, valid, start,
                                             **kw)
    with jax.named_scope(ABSORB_SCOPE):
        out = jnp.einsum("bthc,chv->bthv", o_lat, w[..., nope:])
        if ab is not None:
            out = out + jnp.einsum(
                "bthr,rhv->bthv", jnp.einsum("bthc,cr->bthr", o_lat, ab[0]),
                ab[1][..., nope:]) * lora_scale
    return out.reshape(B, T, H * dv)
