"""Chunked cached attention — the flash-decode path for the in-tree generate
loop (parity goal: replace vLLM's paged decode attention,
agilerl/algorithms/core/base.py:3101; SURVEY.md §2.9).

Decode attention is HBM-bandwidth-bound, not MXU-bound: each step reads the
whole live KV prefix once. The dense XLA path previously scored every q
against the FULL cache allocation [B, S, Hkv, d] (S = prompt + max_new_tokens)
and materialized a GQA-repeated copy of K/V. This op fixes both:

- online-softmax accumulation over KV chunks inside a ``lax.fori_loop`` whose
  trip count is the *dynamic* live length ``ceil((start+T)/block)`` — slots
  beyond the live prefix are never read (a dynamic trip count is a value, not
  a shape, so XLA compiles it once as a while loop);
- GQA folded into the einsum (q reshaped [B,T,Hkv,rep,d]) so K/V are never
  repeated in HBM.

Two fetches, one loop. The loop (``_online_softmax``) is handed a function
that fetches chunk ``i``'s keys and values, and that is all its two entries
differ in:

- ``chunked_cached_attention`` — a contiguous cache [B, S, ...] that already
  holds this call's K/V (the dense and bucketed tiers, ``model.forward``):
  chunk ``i`` is a ``dynamic_slice`` of it. Reverse-differentiable (custom
  VJP through the dense formulation).
- ``chunked_paged_attention`` — a block pool [nb, bs, ...] and a block table
  [B, max_blocks] (the continuous tier, ``model.forward_paged``): chunk
  ``i`` is ``block // bs`` pool blocks taken through the table, with this
  call's new K/V put into the chunk by a select. No array of a slot's full
  extent ``max_blocks * bs`` is ever built: a gather in front of the op
  would undo the dynamic bound (it is as large as the layer's whole pool
  at the benchmark's sizes). Forward-only. Its loop runs under the scope
  ``paged/attend``.

A sliding window (``window=W`` > 0: query t sees slots ``t - W < j <= t``,
a window layer of a stack that mixes window and full attention) is the
loop's too: it STARTS at the chunk that holds the shallowest live row's slot
``start - W + 1`` — what lies wholly behind every row's window is neither
fetched nor read — and masks inside the chunks it runs. The paged entry's
loop of a window layer runs under ``paged/attend_win`` inside
``paged/attend``.

Both layouts of a cache go through both entries: K and V with a head axis
(``[.., Hkv, d]``), or one latent array without one whose value is the first
``v_width`` columns of its key (llm/mla.py).

Two callers share this op with different window shapes, both covered by the
same visibility rule (slot j visible to query t iff j <= start[b] + t and
valid[j]):

- plain decode: T = 1, ``start`` = per-row cache depth before the step;
- speculative verify (llm/speculate.py): T = K + 1 — the committed last token
  plus K draft tokens are scored in ONE forward, with ``start`` = per-row
  depth of the committed prefix and the window's K/V at slots
  start[b]..start[b]+T-1. Query t attends to the committed prefix plus
  the first t window tokens, exactly as if the drafts had been decoded one
  step at a time — which is what makes accept/reject token-exact.

Numerics match the dense masked-softmax path bit-for-bit at f32 accumulation
(tests/test_ops/test_decode_attention.py, incl. the per-row-start T>1
verify-window case), and the paged entry equals gather + insert + the
contiguous entry bit for bit (same file). A Pallas kernel is deliberately
NOT used here: with BlockSpec pipelining the operand fetch for a grid step
happens whether or not ``pl.when`` skips the compute, so a static-grid
Pallas kernel cannot skip the dead cache tail — the dynamic-bound XLA loop
can, and the per-chunk math (two matmuls + exp) is already fused by XLA. A
paged kernel would have to take the block table as a scalar-prefetch
operand and the live length as its grid bound; what it could still win is
the chunk written out once between the pool and the matmuls
(``paged_attend_share`` says what that is worth before anyone writes it).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

PAGED_SCOPE = "paged/attend"
PAGED_WINDOW_SCOPE = "paged/attend_win"  # a window layer's loop, inside it


def _dense_reference(q, k_cache, v_cache, valid, start, scale=None,
                     v_width=None, window=0):
    """Differentiable dense formulation of the same visibility rule — used
    only as the backward path (custom VJP): the chunked forward's
    dynamic-trip-count while_loop is not reverse-differentiable, but its
    output is bit-equal to this dense one, so the VJP of this function AT
    THE SAME INPUTS is the correct gradient."""
    B, T, Hq, d = q.shape
    if v_width is not None:  # a latent cache [B, S, d]: one head, no axis
        k_cache = k_cache[:, :, None, :]
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    qr = q.reshape(B, T, Hkv, rep, d)
    scores = jnp.einsum(
        "bthrd,bshd->bhrts", qr, k_cache, preferred_element_type=jnp.float32
    )
    scores = scores / math.sqrt(d) if scale is None else scores * scale
    if v_width is not None:
        v_cache = k_cache[..., :v_width]
    slot = jnp.arange(S)
    # start may be [] (all rows aligned) or [B] (paged slots at
    # heterogeneous depths) — broadcast to per-row either way
    start_b = jnp.broadcast_to(jnp.asarray(start), (B,))
    causal = (slot[None, None, :]
              <= (start_b[:, None] + jnp.arange(T)[None, :])[:, :, None])  # [B, T, S]
    if window:
        behind = (start_b[:, None] + jnp.arange(T)[None, :] - window)[:, :, None]
        causal = jnp.logical_and(causal, slot[None, None, :] > behind)
    mask = jnp.logical_and(
        causal[:, None, None], valid.astype(bool)[:, None, None, None, :]
    )
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    out = jnp.einsum(
        "bhrts,bshd->bhrtd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return jnp.moveaxis(out, 3, 1).reshape(
        B, T, Hq, v_cache.shape[-1]).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _make_chunked(block: int, scale=None, v_width=None, window=0):
    if v_width is not None:
        if window:
            raise NotImplementedError(
                "a sliding window over a latent cache: not implemented")
        return _make_chunked_latent(block, scale, v_width)

    @jax.custom_vjp
    def f(q, k_cache, v_cache, valid, start):
        return _chunked_impl(q, k_cache, v_cache, valid, start, block,
                             window=window)

    def fwd(q, k_cache, v_cache, valid, start):
        return f(q, k_cache, v_cache, valid, start), (
            q, k_cache, v_cache, valid, start,
        )

    def bwd(res, g):
        q, k_cache, v_cache, valid, start = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _dense_reference(q_, k_, v_, valid, start,
                                                window=window),
            q, k_cache, v_cache,
        )
        dq, dk, dv = vjp(g)
        f0 = jax.dtypes.float0
        return (dq, dk, dv,
                np.zeros(np.shape(valid), f0), np.zeros(np.shape(start), f0))

    f.defvjp(fwd, bwd)
    return f


def _make_chunked_latent(block: int, scale: float, v_width: int):
    """The same op for a cache whose value is the first ``v_width`` columns
    of its key (latent attention): one array, read once a chunk."""

    @jax.custom_vjp
    def f(q, k_cache, valid, start):
        return _chunked_impl(q, k_cache, None, valid, start, block, scale,
                             v_width)

    def fwd(q, k_cache, valid, start):
        return f(q, k_cache, valid, start), (q, k_cache, valid, start)

    def bwd(res, g):
        q, k_cache, valid, start = res
        _, vjp = jax.vjp(
            lambda q_, k_: _dense_reference(q_, k_, None, valid, start, scale,
                                            v_width), q, k_cache)
        f0 = jax.dtypes.float0
        return (*vjp(g), np.zeros(np.shape(valid), f0),
                np.zeros(np.shape(start), f0))

    f.defvjp(fwd, bwd)
    return lambda q, k_cache, v_cache, valid, start: f(q, k_cache, valid, start)


@functools.partial(jax.jit, static_argnames=("block", "scale", "v_width",
                                             "window"))
def chunked_cached_attention(
    q: jax.Array,        # [B, T, Hq, d] RoPE'd queries (absolute pos start..start+T)
    k_cache: jax.Array,  # [B, S, Hkv, d] cache AFTER inserting this step's K
    v_cache: jax.Array,  # [B, S, Hkv, d]
    valid: jax.Array,    # [B, S] 1 = slot holds a real token
    start,               # [] or [B]: cache length before this step — per-row
    #                      for the paged/continuous decode path, whose slots
    #                      sit at heterogeneous depths
    *,
    block: int = 512,
    scale: Optional[float] = None,
    v_width: Optional[int] = None,
    window: int = 0,
) -> jax.Array:
    """Returns attention output [B, T, Hq, d] (same visibility rule as the
    dense path: slot j visible to query t iff j <= start[b] + t and valid[j]
    — and, with a ``window``, j > start[b] + t - window).
    Reverse-differentiable: grads route through a dense backward (custom
    VJP) since the dynamic-bound forward loop cannot be transposed.

    ``scale`` (default ``1 / sqrt(d)``) multiplies the scores. With
    ``v_width`` the cache is ``[B, S, d]`` — one head and no head axis —,
    the value of a slot is the first ``v_width`` columns of its key,
    ``v_cache`` is None and the output is ``[B, T, Hq, v_width]``: the latent
    cache of llm/mla.py, where ``d`` is not the published head size."""
    return _make_chunked(min(block, k_cache.shape[1]), scale, v_width,
                         window)(
        q, k_cache, v_cache, valid, jnp.asarray(start)
    )


def _chunked_impl(q, k_cache, v_cache, valid, start, block, scale=None,
                  v_width=None, window=0):
    """The contiguous fetch: chunk i is a slice of a cache that already
    holds this call's K/V."""
    S = k_cache.shape[1]
    Hkv = 1 if v_width is not None else k_cache.shape[2]

    def fetch(off_c):
        ks = jax.lax.dynamic_slice_in_dim(k_cache, off_c, block, axis=1)
        if v_cache is None:
            return ks, None
        return ks, jax.lax.dynamic_slice_in_dim(v_cache, off_c, block, axis=1)

    return _online_softmax(q, fetch, S, Hkv, valid, start, None, block,
                           scale, v_width, window)


@functools.partial(jax.jit, static_argnames=("block", "scale", "v_width",
                                             "window"))
def chunked_paged_attention(
    q: jax.Array,             # [B, T, Hq, d] RoPE'd queries
    pool_k: jax.Array,        # [nb, bs, Hkv, d] ONE layer's pool, BEFORE this
    pool_v: jax.Array,        # call's write; latent: [nb, bs, d] and None
    block_tables: jax.Array,  # [B, max_blocks] logical block -> pool block
    new_k: jax.Array,         # [B, T, Hkv, d] this call's K (latent [B, T, d])
    new_v: jax.Array,         # [B, T, Hkv, d], or None (latent)
    write_pos: jax.Array,     # [B] or [B, T] logical slot of each new token
    valid: jax.Array,         # [B, S] 1 = slot holds a real token, S =
    #                           max_blocks * bs; the new tokens' slots set
    start,                    # [B] (or []) depth before this call
    *,
    block: int = 512,
    scale: Optional[float] = None,
    v_width: Optional[int] = None,
    window: int = 0,
) -> jax.Array:
    """``chunked_cached_attention`` over a block pool: the same loop, chunk
    boundaries, masks and order of accumulation, with chunk ``i`` taken
    from the pool through ``block_tables`` (``block // bs`` entries at the
    chunk's clamped offset) and this call's new K/V put into it where
    ``write_pos`` falls inside — a position in no chunk (a released slot's
    runaway length, a verify window past the extent) is dropped. Bit-equal
    to gathering every slot's whole extent, inserting the new K/V and
    calling the contiguous entry with the same ``block``, on every row that
    has a valid slot; no array of the whole extent is built, and a chunk
    past the deepest such row is neither gathered nor read.

    The loop's bound is the deepest LIVE row: a released slot's length keeps
    advancing (``generate.paged_decode_step``) and its mask row is all zero,
    so it is left out of the bound — its own output is then an average over
    the sink block for as many chunks as the live rows need, which nobody
    reads. ``block`` is cut to a whole number of pool blocks (at least one,
    at most the table). Forward-only: no caller differentiates a paged
    forward (the learn step runs ``model.forward`` without a cache), and the
    dynamic-bound loop has no transpose."""
    bs = pool_k.shape[1]
    B, mb = block_tables.shape
    T = q.shape[1]
    per = max(1, min(block // bs, mb))  # pool blocks a chunk
    slot = jnp.arange(per * bs)
    if write_pos.ndim == 1:
        write_pos = write_pos[:, None]

    def fetch(off_c):
        # off_c is a whole number of pool blocks: block is, and so is S
        rows = jax.lax.dynamic_slice_in_dim(
            block_tables, off_c // bs, per, axis=1).reshape(-1)
        here = slot[None, :, None] == (write_pos - off_c)[:, None, :]

        def chunk(pool, new):
            # clip, not jnp.take's default fill: a table holds pool blocks,
            # and the fill's select is a pass of its own over the chunk
            got = jnp.take(pool, rows, axis=0, mode="clip").reshape(
                B, per * bs, *pool.shape[2:])
            for t in range(T):  # T is 1, or a verify window of a few tokens
                at = jnp.expand_dims(here[:, :, t], range(2, got.ndim))
                got = jnp.where(at, new[:, t][:, None], got)
            return got

        return chunk(pool_k, new_k), (None if pool_v is None
                                      else chunk(pool_v, new_v))

    if window and v_width is not None:
        raise NotImplementedError(
            "a sliding window over a latent cache: not implemented")
    with contextlib.ExitStack() as scopes:
        scopes.enter_context(jax.named_scope(PAGED_SCOPE))
        if window:
            scopes.enter_context(jax.named_scope(PAGED_WINDOW_SCOPE))
        return _online_softmax(
            q, fetch, mb * bs, 1 if v_width is not None else pool_k.shape[2],
            valid, jnp.asarray(start), jnp.any(valid.astype(bool), axis=1),
            per * bs, scale, v_width, window)


def _online_softmax(q, fetch, S, Hkv, valid, start, live_rows, block, scale,
                    v_width, window=0):
    """The one loop both entries run. ``fetch(off_c)`` returns the keys and
    values of slots ``off_c .. off_c + block`` ([B, block, Hkv, d] each; a
    latent cache's [B, block, d] and None). ``live_rows`` ([B] bool, or
    None for all) are the rows whose depth bounds the loop. With a
    ``window`` the loop starts at the chunk that holds slot ``start - window
    + 1`` of the shallowest such row (the first query's window; later
    queries' begin later) and slots at or behind ``start + t - window`` are
    masked."""
    B, T, Hq, d = q.shape
    rep = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    dv = d if v_width is None else v_width

    qr = q.reshape(B, T, Hkv, rep, d)
    t_ids = jnp.arange(T)

    # start: [] or [B] (paged decode slots sit at heterogeneous depths);
    # the loop bound must cover the DEEPEST row — shallower rows' extra
    # chunks are fully masked and contribute exact zeros
    start_b = jnp.broadcast_to(jnp.asarray(start), (B,))
    deepest = jnp.max(start_b if live_rows is None
                      else jnp.where(live_rows, start_b, 0))
    live = deepest + T  # number of potentially-visible slots
    n_chunks = jnp.minimum(
        (live + block - 1) // block, -(-S // block)
    ).astype(jnp.int32)

    first_chunk = 0
    if window:
        shallowest = jnp.min(start_b if live_rows is None
                             else jnp.where(live_rows, start_b, S))
        first_chunk = jnp.minimum(
            jnp.maximum(shallowest - window + 1, 0) // block, n_chunks
        ).astype(jnp.int32)

    m0 = jnp.full((B, Hkv, rep, T), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, T), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, rep, T, dv), jnp.float32)

    def chunk_step(i, carry):
        m, l, acc = carry
        off = i * block
        # when S % block != 0 the last chunk's slice is clamped to S - block
        # (no padding — a pad would COPY the whole cache every call); the
        # re-read slots below `off` are masked out so nothing double-counts
        off_c = jnp.minimum(off, S - block)
        ks, vs = fetch(off_c)
        if v_width is not None:  # [B, BK, d] -> one head; v a slice of k
            ks = ks[:, :, None, :]
            vs = ks[..., :v_width]
        vm = jax.lax.dynamic_slice_in_dim(valid, off_c, block, axis=1)

        scores = jnp.einsum(
            "bthrd,bshd->bhrts", qr, ks, preferred_element_type=jnp.float32
        ) * scale  # [B, Hkv, rep, T, BK]

        slot = off_c + jnp.arange(block)
        causal = (slot[None, None, :]
                  <= (start_b[:, None] + t_ids[None, :])[:, :, None])  # [B, T, BK]
        if window:
            behind = (start_b[:, None] + t_ids[None, :] - window)[:, :, None]
            causal = jnp.logical_and(causal, slot[None, None, :] > behind)
        fresh = slot >= off                                            # [BK]
        mask = jnp.logical_and(
            jnp.logical_and(causal, fresh[None, None, :])[:, None, None],
            vm.astype(bool)[:, None, None, None, :],
        )
        scores = jnp.where(mask, scores, -1e30)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhrts,bshd->bhrtd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(first_chunk, n_chunks, chunk_step,
                                  (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]   # [B, Hkv, rep, T, d]
    out = jnp.moveaxis(out, 3, 1)                  # [B, T, Hkv, rep, d]
    return out.reshape(B, T, Hq, dv).astype(q.dtype)
