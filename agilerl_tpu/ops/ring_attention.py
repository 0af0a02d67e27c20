"""Ring attention — sequence/context parallelism over a mesh axis.

The reference has NO sequence parallelism (SURVEY.md §5.7: absent; long context
is handled only by chunking + vLLM paged attention). This module goes beyond
parity: sequences shard over a "sp" mesh axis, K/V blocks rotate around the ring
via ppermute over ICI, and softmax is accumulated online (flash-style running
max/denominator), so attention memory per chip is O(T/P * T/P) and sequence
length scales linearly with ring size. (Liu et al., Ring Attention, 2023.)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _block_attn(q, k, v, mask, scale):
    """One q-block x kv-block partial attention.

    q [B, Tq, H, d]; k/v [B, Tk, H, d]; mask [Tq, Tk] or None.
    Returns (numerator [B, Tq, H, d], row max m [B, Tq, H], denom l [B, Tq, H])."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, Tq, Tk]
    if mask is not None:
        if mask.ndim == 2:  # [Tq, Tk]
            mask = mask[None, None]
        elif mask.ndim == 3:  # [B, Tq, Tk]
            mask = mask[:, None]
        scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, axis=-1)  # [B, H, Tq]
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)  # [B, H, Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, jnp.moveaxis(m, 1, 2), jnp.moveaxis(l, 1, 2)  # m,l -> [B, Tq, H]


def _ring_flash(q, k, v, axis_name, causal, kv_mask, block_q, block_k):
    """Ring attention with the Pallas flash kernel as the per-block engine:
    the [T_local, T_local] score matrix never materialises in HBM (online
    softmax in VMEM), so per-chip attention memory is O(block^2) instead of
    O(T_local^2). Each ring offset picks the right kernel via lax.switch
    (earlier block: full attention; diagonal: causal; future: skip), and
    partial results merge by logsumexp — flash_attention_with_lse's lse
    output is differentiable, so this path serves training too."""
    from agilerl_tpu.ops.flash_attention_vjp import flash_attention_with_lse

    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    qh = jnp.moveaxis(q, 2, 1)  # [B, H, T, d]

    def step(carry, i):
        k_blk, v_blk, m_blk, o_acc, lse_acc = carry
        src_idx = (my_idx - i) % p_size
        kh = jnp.moveaxis(k_blk, 2, 1)
        vh = jnp.moveaxis(v_blk, 2, 1)

        def past(_):
            return flash_attention_with_lse(
                qh, kh, vh, m_blk, False, block_q, block_k)

        def diag(_):
            return flash_attention_with_lse(
                qh, kh, vh, m_blk, True, block_q, block_k)

        def future(_):
            return (jnp.zeros_like(qh),
                    jnp.zeros(qh.shape[:3], jnp.float32) - 1e30)

        if causal:
            idx = (jnp.where(src_idx == my_idx, 1, 0)
                   + jnp.where(src_idx > my_idx, 2, 0))
            o_b, lse_b = lax.switch(idx, [past, diag, future], None)
        else:
            o_b, lse_b = past(None)

        # merge normalized partials by logsumexp weight
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        w_acc = jnp.exp(lse_acc - lse_new)[..., None]
        w_b = jnp.exp(lse_b - lse_new)[..., None]
        o_new = o_acc * w_acc + o_b.astype(o_acc.dtype) * w_b

        perm = [(j, (j + 1) % p_size) for j in range(p_size)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        m_next = (
            lax.ppermute(m_blk, axis_name, perm) if m_blk is not None else None
        )
        return (k_next, v_next, m_next, o_new, lse_new), None

    o0 = qh.astype(jnp.float32) * 0.0
    lse0 = jnp.sum(o0, axis=-1) - 1e30
    (_, _, _, o, _), _ = lax.scan(
        step, (k, v, kv_mask, o0, lse0), jnp.arange(p_size))
    return jnp.moveaxis(o, 1, 2).astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [B, T_local, H, d] — local sequence shard
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
    kv_mask: Optional[jax.Array] = None,  # [B, T_local] 1 = real token; the
    # mask ROTATES around the ring with its k/v block (ragged/right-padded seqs)
    use_flash: bool = False,
    block_q: Optional[int] = None,  # None: flash_plan chooses from T_local
    block_k: Optional[int] = None,
) -> jax.Array:
    """Call INSIDE shard_map with q/k/v sharded on the sequence axis.
    ``use_flash=True`` swaps the per-block engine for the Pallas flash
    kernel (O(block^2) VMEM instead of O(T_local^2) HBM scores)."""
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal, kv_mask,
                           block_q, block_k)
    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, T, H, d = q.shape
    scale = 1.0 / (d ** 0.5)

    t_ids = jnp.arange(T)
    intra_causal = t_ids[:, None] >= t_ids[None, :]  # causal within a block

    def step(carry, i):
        k_blk, v_blk, m_blk, o_acc, m_acc, l_acc = carry
        src_idx = (my_idx - i) % p_size  # which block this k/v shard came from

        pad_mask = (
            jnp.broadcast_to(m_blk[:, None, :].astype(bool), (B, T, T))
            if m_blk is not None else None
        )
        if causal:
            # select the MASK per ring offset (diagonal block: causal-within;
            # earlier block: full) instead of computing the block attention
            # twice and selecting outputs — halves every causal ring step
            same = src_idx == my_idx
            after = src_idx > my_idx
            eff_mask = jnp.where(same, intra_causal[None, :, :], True)
            if pad_mask is not None:
                eff_mask = jnp.logical_and(eff_mask, pad_mask)
        else:
            eff_mask = pad_mask
        o_b, m_b, l_b = _block_attn(q, k_blk, v_blk, eff_mask, scale)
        if causal:
            # future blocks contribute nothing — explicit overrides (an
            # all-masked score block would otherwise yield p=1 rows)
            m_b = jnp.where(after, -1e30, m_b)
            l_b = jnp.where(after, 0.0, l_b)
            o_b = jnp.where(after, 0.0, o_b)

        # online softmax merge
        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_b - m_new)
        l_new = l_acc * alpha + l_b * beta
        o_new = o_acc * alpha[..., None] + o_b * beta[..., None]

        # rotate k/v (and their mask) around the ring
        perm = [(j, (j + 1) % p_size) for j in range(p_size)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        m_next = (
            lax.ppermute(m_blk, axis_name, perm) if m_blk is not None else None
        )
        return (k_next, v_next, m_next, o_new, m_new, l_new), None

    # derive accumulators from q so they carry the same varying-axis ("vma")
    # type as the per-device loop outputs (new shard_map type system)
    o0 = q * 0.0
    m0 = jnp.sum(o0, axis=-1) - 1e30
    l0 = jnp.sum(o0, axis=-1)
    (k_f, v_f, _mf, o, m, l), _ = lax.scan(
        step, (k, v, kv_mask, o0, m0, l0), jnp.arange(p_size)
    )
    return o / jnp.maximum(l[..., None], 1e-30)


def make_ring_attention(
    mesh: Mesh, axis_name: str = "sp", causal: bool = True,
    with_mask: bool = False, use_flash: bool = False,
):
    """Wrap ring_attention in shard_map: takes [B, T, H, d] arrays sharded on T
    (+ an optional [B, T] kv padding mask when with_mask=True)."""

    spec = P(None, axis_name, None, None)
    mspec = P(None, axis_name)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                           use_flash=use_flash)
    if with_mask:
        def wrapped(q, k, v, m):
            return fn(q, k, v, kv_mask=m)

        return jax.jit(
            shard_map(wrapped, mesh=mesh, in_specs=(spec, spec, spec, mspec),
                      out_specs=spec, check_vma=False)
        )
    # check_vma=False: pallas_call out_shapes carry no vma annotations (the
    # flash per-block engine); collective correctness is covered by the
    # dense-reference parity tests
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                  check_vma=False)
    )


def reference_attention(q, k, v, causal: bool = True):
    """Dense attention for correctness checks."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
