"""Paged KV cache primitives (ISSUE 7 tentpole): block-pool gather/scatter
round-trips, forward_paged vs the dense cached forward, and the host block
allocator's refcounted prefix-cache lifecycle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.serving import BlockAllocator
from tests.test_ops.test_decode_attention import paged_gather

pytestmark = pytest.mark.serving

CFG = M.GPTConfig(vocab_size=64, n_layer=2, n_head=4, n_kv_head=2,
                  d_model=32, max_seq_len=128, dtype=jnp.float32)


def test_scatter_gather_roundtrip():
    """Prompt blocks scattered into the pool gather back bit-identical, in
    table order, regardless of physical placement."""
    bs, nb = 4, 8
    pool = M.init_paged_cache(CFG, nb, bs)
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(CFG.n_layer, 8, CFG.kv_heads,
                                      CFG.head_dim)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=kp.shape).astype(np.float32))
    # two blocks placed out of order in the pool
    pool = M.paged_scatter_prompt(pool, jnp.asarray([5, 2], np.int32), kp, vp)
    tables = jnp.asarray([[5, 2, 0]], np.int32)
    k_slab, v_slab = paged_gather(pool.k[:, :][0], pool.v[0], tables)
    np.testing.assert_array_equal(np.asarray(k_slab[0, :8]),
                                  np.asarray(kp[0]))
    np.testing.assert_array_equal(np.asarray(v_slab[0, :8]),
                                  np.asarray(vp[0]))


def test_scatter_tokens_lands_per_slot_and_clamps():
    """Per-slot token writes land at (table[pos//bs], pos%bs); a released
    slot (all-zero table, runaway length) clamps into the garbage block 0
    without touching live blocks."""
    bs, nb = 4, 6
    pool = M.init_paged_cache(CFG, nb, bs)
    tables = jnp.asarray([[3, 4], [0, 0]], np.int32)
    write_pos = jnp.asarray([5, 10_000], np.int32)  # slot1 = released junk
    new_k = jnp.ones((CFG.n_layer, 2, CFG.kv_heads, CFG.head_dim),
                     CFG.dtype) * jnp.asarray([1.0, 9.0])[None, :, None, None]
    pool2 = M.paged_scatter_tokens(pool, tables, write_pos, new_k, new_k)
    got = np.asarray(pool2.k)
    # slot 0: logical pos 5 -> block table[1]=4, offset 1
    np.testing.assert_array_equal(got[:, 4, 1], np.ones_like(got[:, 4, 1]))
    # the junk write went to block 0 only; blocks 1-3,5 stay zero
    for b in (1, 2, 3, 5):
        assert (got[:, b] == 0).all(), f"block {b} dirtied"
    assert (got[:, 0] != 0).any()  # garbage block took the clamped write


def test_forward_paged_matches_dense_cached_forward():
    """One decode step through forward_paged over a paged layout must equal
    the dense KVCache forward for rows at the SAME depth — and stay correct
    for rows at different depths (the continuous-batching case the dense
    path cannot express)."""
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(1)
    B, P, bs = 2, 8, 4
    ext = 16  # P + decode extent
    prompt = rng.integers(3, 60, size=(B, P)).astype(np.int32)
    pmask = np.ones((B, P), np.int32)
    # dense reference: prefill then one cached decode forward
    caches = M.init_caches(CFG, B, ext)
    _, caches = M.forward(CFG, params, jnp.asarray(prompt),
                          attention_mask=jnp.asarray(pmask), cache=caches)
    tok = rng.integers(3, 60, size=(B, 1)).astype(np.int32)
    pos = jnp.asarray([[P], [P]], np.int32)
    hidden_d, _ = M.forward(CFG, params, jnp.asarray(tok),
                            attention_mask=jnp.ones((B, 1), np.int32),
                            positions=pos, cache=caches)
    # paged: same logical layout in per-slot blocks
    mb = ext // bs
    pool = M.init_paged_cache(CFG, 1 + B * mb, bs)
    tables = np.zeros((B, mb), np.int32)
    nxt = 1
    for i in range(B):
        ids = list(range(nxt, nxt + mb))
        nxt += mb
        tables[i] = ids
        c1 = M.init_caches(CFG, 1, ext)
        _, c1 = M.forward(CFG, params, jnp.asarray(prompt[i:i + 1]),
                          attention_mask=jnp.asarray(pmask[i:i + 1]),
                          cache=c1)
        pool = M.paged_scatter_prompt(
            pool, jnp.asarray(ids[:P // bs], np.int32),
            c1.k[:, 0, :P], c1.v[:, 0, :P])
    slot_mask = np.zeros((B, mb * bs), np.int32)
    slot_mask[:, :P + 1] = 1  # prompt + the incoming token
    hidden_p, (nk, nv) = M.forward_paged(
        CFG, params, jnp.asarray(tok), jnp.asarray([P, P], np.int32),
        jnp.asarray([P, P], np.int32), pool, jnp.asarray(tables),
        jnp.asarray(slot_mask))
    np.testing.assert_array_equal(np.asarray(hidden_d), np.asarray(hidden_p))
    assert nk.shape == (CFG.n_layer, B, CFG.kv_heads, CFG.head_dim)


def test_allocator_lifecycle():
    """alloc/free/refcount/evict: cached blocks survive release (evictable),
    eviction reclaims LRU-first, and an unsatisfiable request mutates
    nothing."""
    a = BlockAllocator(6)  # blocks 1..5 usable
    got = a.alloc(5)
    assert sorted(got) == [1, 2, 3, 4, 5]
    assert a.alloc(1) is None
    # register 1,2 as prompt blocks; free 3,4,5 as private
    assert a.register(b"h1", got[0])
    assert a.register(b"h2", got[1])
    a.free(got[2:])
    assert a.free_blocks == 3 and a.evictable_blocks == 0
    # release -> evictable but still hit-able
    a.release_shared(got[:2])
    assert a.evictable_blocks == 2
    assert a.lookup_chain([b"h1", b"h2"]) == got[:2]
    assert a.evictable_blocks == 0  # the hit re-referenced them
    a.release_shared(got[:2])
    # allocating 5 blocks forces eviction of both cached blocks
    got2 = a.alloc(5)
    assert len(got2) == 5
    assert a.lookup_chain([b"h1"]) is None  # evicted
    # 0 is never handed out (reserved garbage block)
    assert 0 not in got2


def test_allocator_first_writer_wins_on_duplicate_hash():
    """Two different blocks can carry the same chain hash (identical all-pad
    leading blocks of different prompts that both missed): registration is
    first-writer-wins, the refused block stays private, and evicting either
    never orphans the mapping."""
    a = BlockAllocator(4)
    b1, b2, b3 = a.alloc(3)
    assert a.register(b"same", b1)
    assert not a.register(b"same", b2)  # refused: caller keeps it private
    a.free([b2])
    a.release_shared([b1])
    # b3 is still privately held: only b2 (free) + b1 (evictable) remain —
    # allocating both forces the eviction of b1
    got = a.alloc(2)
    assert b1 in got and a.lookup_chain([b"same"]) is None
    assert a.register(b"same", b3)  # the hash is free again
