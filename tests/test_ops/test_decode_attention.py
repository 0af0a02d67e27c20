"""Chunked cached attention (flash-decode) vs the dense masked-softmax path.

The chunked op must reproduce the dense cached-attention numerics exactly
(same visible set, f32 accumulation) for prefill (T=P, start=0), decode
(T=1, start>0), GQA (rep>1), and ragged left-padded masks.

The paged entry (a block pool read through a block table, one live chunk at
a time) must equal gathering every slot's whole extent, inserting the
call's new K/V and running the contiguous entry — bit for bit — and the
paged decode step must build no array of a slot's whole extent."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from agilerl_tpu.ops.decode_attention import (
    chunked_cached_attention,
    chunked_paged_attention,
)


def dense_reference(q, ck, cv, cm, start):
    """The model's dense cached path (llm/model.py cached branch) verbatim."""
    B, T, Hq, d = q.shape
    S, Hkv = ck.shape[1], ck.shape[2]
    rep = Hq // Hkv
    k_all = jnp.repeat(ck, rep, axis=2) if rep > 1 else ck
    v_all = jnp.repeat(cv, rep, axis=2) if rep > 1 else cv
    kv_slot = jnp.arange(S)
    causal = kv_slot[None, None, :] <= (start + jnp.arange(T))[None, :, None]
    mask = jnp.logical_and(causal, cm[:, None, :].astype(bool))
    qh = jnp.moveaxis(q, 2, 1)
    kh = jnp.moveaxis(k_all, 2, 1)
    vh = jnp.moveaxis(v_all, 2, 1)
    scores = jnp.einsum("bhtd,bhsd->bhts", qh, kh).astype(jnp.float32)
    scores = scores / np.sqrt(d)
    scores = jnp.where(mask[:, None, :, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhts,bhsd->bhtd", probs, vh)
    return jnp.moveaxis(attn, 1, 2)


def make_case(rng, B, T, S, Hq, Hkv, d, start, ragged=True):
    q = jnp.asarray(rng.normal(size=(B, T, Hq, d)).astype(np.float32))
    ck = np.zeros((B, S, Hkv, d), np.float32)
    cv = np.zeros((B, S, Hkv, d), np.float32)
    cm = np.zeros((B, S), np.int32)
    live = start + T
    ck[:, :live] = rng.normal(size=(B, live, Hkv, d))
    cv[:, :live] = rng.normal(size=(B, live, Hkv, d))
    cm[:, :live] = 1
    if ragged:
        # left-padded prompts: first rows have leading invalid slots
        for b in range(B):
            n_pad = rng.integers(0, max(1, live // 2))
            cm[b, :n_pad] = 0
    return q, jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(cm)


@pytest.mark.parametrize(
    "B,T,S,Hq,Hkv,d,start,block",
    [
        (2, 1, 64, 4, 4, 16, 17, 16),     # decode step, MHA
        (2, 1, 64, 8, 2, 16, 33, 16),     # decode step, GQA rep=4
        (2, 12, 64, 4, 2, 16, 0, 16),     # prefill, GQA
        (1, 5, 40, 4, 4, 8, 20, 16),      # decode chunk not dividing S
        (2, 3, 48, 4, 4, 8, 10, 512),     # single chunk covers everything
        (1, 1, 40, 4, 4, 8, 35, 16),      # live reaches the CLAMPED last chunk
        (2, 4, 40, 8, 2, 8, 30, 16),      # clamped last chunk + GQA + T>1
    ],
)
def test_matches_dense(B, T, S, Hq, Hkv, d, start, block):
    rng = np.random.default_rng(B * 1000 + T + start)
    q, ck, cv, cm = make_case(rng, B, T, S, Hq, Hkv, d, start)
    out = chunked_cached_attention(q, ck, cv, cm, start, block=block)
    ref = dense_reference(q, ck, cv, cm, start)
    # compare only query rows with >=1 visible slot: a fully-masked row is
    # garbage in both paths (dense: uniform over ALL slots; chunked: uniform
    # over the visited prefix) and is masked downstream either way
    cm_np = np.asarray(cm)
    visible = np.zeros((B, T), bool)
    for t in range(T):
        visible[:, t] = cm_np[:, : start + t + 1].any(axis=1)
    sel = visible[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out) * sel, np.asarray(ref) * sel,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.spec_decode
def test_per_row_start_multi_token_window():
    """Speculative verify (llm/speculate.py) scores a T=K+1 window per slot
    with heterogeneous per-row cache depths (start=[B]) in one forward. Row
    b's query t must see exactly slots <= start[b] + t — equivalent to
    running each row alone with its scalar start."""
    rng = np.random.default_rng(11)
    B, T, S, Hq, Hkv, d, block = 3, 5, 64, 8, 2, 16, 16
    starts = np.asarray([3, 17, 40], np.int32)  # deepest row crosses chunks
    q = jnp.asarray(rng.normal(size=(B, T, Hq, d)).astype(np.float32))
    ck = np.zeros((B, S, Hkv, d), np.float32)
    cv = np.zeros((B, S, Hkv, d), np.float32)
    cm = np.zeros((B, S), np.int32)
    for b, st in enumerate(starts):
        live = int(st) + T
        ck[b, :live] = rng.normal(size=(live, Hkv, d))
        cv[b, :live] = rng.normal(size=(live, Hkv, d))
        cm[b, :live] = 1
        cm[b, : int(rng.integers(0, max(1, st // 2)))] = 0  # ragged left pad
    ck, cv, cm = jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(cm)

    out = chunked_cached_attention(q, ck, cv, cm, jnp.asarray(starts),
                                   block=block)
    for b, st in enumerate(starts):
        ref = dense_reference(q[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                              cm[b:b + 1], int(st))
        np.testing.assert_allclose(np.asarray(out[b:b + 1]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_dead_tail_is_never_read():
    """Slots beyond the live prefix may contain NaN and must not poison the
    output — the dynamic-bound loop never touches them (the dense path would
    turn them into NaN scores before masking... it survives via where, but
    the chunked path must not even read them)."""
    rng = np.random.default_rng(0)
    B, T, S, H, d, start = 2, 1, 128, 4, 16, 7
    q, ck, cv, cm = make_case(rng, B, T, S, H, H, d, start, ragged=False)
    live = start + T
    ck = ck.at[:, live + 16:].set(jnp.nan)  # beyond any chunk the loop visits
    cv = cv.at[:, live + 16:].set(jnp.nan)
    out = chunked_cached_attention(q, ck, cv, cm, start, block=16)
    assert np.isfinite(np.asarray(out)).all()


def test_generate_equivalence_end_to_end():
    """generate() over the KV cache (prefill + one-token steps through
    chunked_cached_attention) must emit the tokens that the UNCACHED forward
    over the whole sequence so far picks (greedy, so no RNG sensitivity) —
    a reference that shares no attention code with the cached path."""
    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.generate import generate

    cfg = M.GPTConfig(vocab_size=97, n_layer=2, n_head=4, n_kv_head=2,
                      d_model=64, max_seq_len=64, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[0, 0, 5, 9, 11], [0, 3, 1, 4, 1]], jnp.int32)
    mask = jnp.asarray([[0, 0, 1, 1, 1], [0, 1, 1, 1, 1]], jnp.int32)

    toks_cached, m1 = generate(cfg, params, prompt, mask,
                               jax.random.PRNGKey(1), max_new_tokens=8,
                               temperature=0.0)
    seq, seq_mask = prompt, mask
    for _ in range(8):
        logits, _ = M.apply(cfg, params, seq, attention_mask=seq_mask)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        seq_mask = jnp.concatenate([seq_mask, jnp.ones((2, 1), jnp.int32)],
                                   axis=1)
    np.testing.assert_array_equal(np.asarray(toks_cached),
                                  np.asarray(seq[:, prompt.shape[1]:]))
    np.testing.assert_array_equal(np.asarray(m1), np.ones((2, 8), np.int32))


def test_grad_through_cached_attention_matches_dense():
    """Differentiating through a cached forward (e.g. scoring logprobs
    against a prefilled KV cache) must work and agree with the dense path —
    the chunked forward routes grads through a dense custom VJP."""
    rng = np.random.default_rng(3)
    B, T, S, Hq, Hkv, d, start = 2, 4, 40, 4, 2, 8, 20
    q, ck, cv, cm = make_case(rng, B, T, S, Hq, Hkv, d, start)

    def loss_chunked(q, ck, cv):
        return jnp.sum(chunked_cached_attention(q, ck, cv, cm, start, block=16) ** 2)

    def loss_dense(q, ck, cv):
        return jnp.sum(dense_reference(q, ck, cv, cm, start) ** 2)

    gc = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, ck, cv)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, ck, cv)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# The paged entry against gather + insert + the contiguous entry
# --------------------------------------------------------------------------- #


def paged_gather(pool_k, pool_v, block_tables):
    """Every slot's whole extent as contiguous slabs [B, max_blocks * bs,
    ...] — what ``model.forward_paged`` built a layer a step until the loop
    took to fetching its own chunk (``model.paged_gather`` then; the
    reference now, and nothing in the program calls it)."""
    bs = pool_k.shape[1]
    B, mb = block_tables.shape

    def slab(pool):
        if pool is None:  # a latent cache has no V array
            return None
        g = jnp.take(pool, block_tables.reshape(-1), axis=0)
        return g.reshape(B, mb * bs, *pool.shape[2:])

    return slab(pool_k), slab(pool_v)


def gathered_reference(q, pool_k, pool_v, tables, new_k, new_v, write_pos,
                       valid, start, **kw):
    """The paged forward as it was: gather, in-slab insert (a position past
    the extent drops: jax scatter semantics), contiguous attention."""
    rows = jnp.arange(q.shape[0])[:, None]
    wp = write_pos if write_pos.ndim == 2 else write_pos[:, None]
    k_slab, v_slab = paged_gather(pool_k, pool_v, tables)
    k_slab = k_slab.at[rows, wp].set(new_k)
    if v_slab is not None:
        v_slab = v_slab.at[rows, wp].set(new_v)
    return chunked_cached_attention(q, k_slab, v_slab, valid, start, **kw)


def make_paged_case(seed, *, T, starts, mb, bs=4, Hq=4, Hkv=2, d=8,
                    latent=None, released=(), dtype=jnp.float32):
    """B = len(starts) slots over a pool whose block 0 is the (zero) sink.
    Tables are out of physical order and rows 0 and 1 share their first two
    blocks; row b holds real tokens at a ragged [pad_b, starts[b] + T);
    rows in ``released`` have an all-zero table and mask and a runaway
    start. ``latent`` = v_width: one array [nb, bs, d], no head axis."""
    rng = np.random.default_rng(seed)
    B, S = len(starts), mb * bs
    nb = 1 + B * mb
    tok = (d,) if latent else (Hkv, d)
    pool = rng.normal(size=(nb, bs, *tok)).astype(np.float32)
    pool[0] = 0.0
    pool_v = None if latent else rng.normal(size=pool.shape).astype(np.float32)
    if pool_v is not None:
        pool_v[0] = 0.0
    phys = rng.permutation(np.arange(1, nb)).reshape(B, mb)
    if B > 1:
        phys[1, :2] = phys[0, :2]  # a shared prefix: two blocks, two rows
    tables = phys.astype(np.int32)
    valid = np.zeros((B, S), np.int32)
    for b, st in enumerate(starts):
        valid[b, int(rng.integers(0, max(1, st // 2))):min(st + T, S)] = 1
    start = np.asarray(starts, np.int32)
    for b in released:
        tables[b], valid[b], start[b] = 0, 0, 10_000 + b
    write_pos = start[:, None] + np.arange(T)[None, :]
    q = rng.normal(size=(B, T, Hq, d)).astype(np.float32)
    new_k = rng.normal(size=(B, T, *tok)).astype(np.float32)
    new_v = None if latent else rng.normal(size=new_k.shape).astype(np.float32)
    cast = lambda a: None if a is None else jnp.asarray(a, dtype)  # noqa: E731
    return dict(
        q=cast(q), pool_k=cast(pool), pool_v=cast(pool_v),
        tables=jnp.asarray(tables), new_k=cast(new_k), new_v=cast(new_v),
        write_pos=jnp.asarray(write_pos if T > 1 else write_pos[:, 0]),
        valid=jnp.asarray(valid), start=jnp.asarray(start))


PAGED_CASES = {
    # T = 1, rows at depths in three different chunks (block = 2 pool
    # blocks = 8 slots), tables shuffled, rows 0 and 1 sharing a prefix
    "t1_ragged_starts": dict(T=1, starts=(5, 9, 27), mb=8, block=8),
    "t1_gqa_4_to_1": dict(T=1, starts=(3, 30), mb=8, block=16, Hq=8, Hkv=2),
    # the verify window: row 0's crosses the chunk edge at 8 (slots 6..9),
    # row 1's runs past the extent of 32 (slots 30..33: two of them drop)
    "window_crosses_chunk_and_extent": dict(T=4, starts=(6, 30, 17), mb=8,
                                            block=8),
    # S = 20 is no multiple of block = 8: the third chunk is clamped to 12
    "clamped_last_chunk_t1": dict(T=1, starts=(18, 11), mb=5, block=8),
    "clamped_last_chunk_window": dict(T=3, starts=(15, 17), mb=5, block=8),
    # one chunk covers the table; a block that is no whole number of pool
    # blocks is cut to one (10 -> 8); a block under one pool block -> one
    "block_covers_table": dict(T=1, starts=(13, 2), mb=4, block=512),
    "block_cut_to_pool_blocks": dict(T=2, starts=(13, 22), mb=6, block=10),
    "block_under_one_pool_block": dict(T=1, starts=(13, 22), mb=6, block=2),
    # the latent layout: no head axis, the value a slice of the key
    "latent_t1": dict(T=1, starts=(5, 9, 27), mb=8, block=8, latent=6,
                      Hq=4, d=10),
    "latent_window": dict(T=3, starts=(7, 29), mb=8, block=8, latent=6,
                          Hq=4, d=10),
    # a released row (all-zero table and mask, start far past S) beside
    # live rows, first, last and in the middle
    "released_row_last": dict(T=1, starts=(5, 9, 0), mb=8, block=8,
                              released=(2,)),
    "released_row_first_window": dict(T=3, starts=(0, 14, 21), mb=8, block=8,
                                      released=(0,)),
    "released_row_latent": dict(T=1, starts=(20, 0, 3), mb=8, block=8,
                                latent=6, Hq=4, d=10, released=(1,)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_entry_is_bit_equal_to_gather_insert_contiguous(case, dtype):
    spec = dict(PAGED_CASES[case])
    block = spec.pop("block")
    released = spec.get("released", ())
    c = make_paged_case(sorted(PAGED_CASES).index(case), dtype=dtype, **spec)
    bs, mb = c["pool_k"].shape[1], c["tables"].shape[1]
    kw = {}
    if spec.get("latent"):
        kw = dict(v_width=spec["latent"], scale=0.3)
    out = chunked_paged_attention(
        c["q"], c["pool_k"], c["pool_v"], c["tables"], c["new_k"],
        c["new_v"], c["write_pos"], c["valid"], c["start"], block=block,
        **kw)
    # the reference's chunks are the paged entry's: whole pool blocks
    ref_block = max(1, min(block // bs, mb)) * bs
    ref = gathered_reference(
        c["q"], c["pool_k"], c["pool_v"], c["tables"], c["new_k"],
        c["new_v"], c["write_pos"], c["valid"], c["start"], block=ref_block,
        **kw)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    out, ref = (np.asarray(x.astype(jnp.float32)) for x in (out, ref))
    live = [b for b in range(out.shape[0]) if b not in released]
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[live], ref[live])
    assert np.abs(out[live]).max() > 0
    # a released row reads the (zero) sink block and nothing else; nobody
    # reads its output, which is why it may differ in the number of chunks
    for b in released:
        assert not out[b].any() and not ref[b].any()


def test_paged_entry_matches_dense_softmax():
    """...and the shared reference is itself held to the dense masked
    softmax, so the two cannot be wrong together."""
    c = make_paged_case(3, T=1, starts=(5, 9, 27), mb=8)
    out = chunked_paged_attention(
        c["q"], c["pool_k"], c["pool_v"], c["tables"], c["new_k"],
        c["new_v"], c["write_pos"], c["valid"], c["start"], block=8)
    rows = jnp.arange(3)
    k_slab, v_slab = paged_gather(c["pool_k"], c["pool_v"], c["tables"])
    k_slab = k_slab.at[rows, c["write_pos"]].set(c["new_k"][:, 0])
    v_slab = v_slab.at[rows, c["write_pos"]].set(c["new_v"][:, 0])
    for b in range(3):
        ref = dense_reference(c["q"][b:b + 1], k_slab[b:b + 1],
                              v_slab[b:b + 1], c["valid"][b:b + 1],
                              int(c["start"][b]))
        np.testing.assert_allclose(np.asarray(out[b:b + 1]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("released", [(), (2,)], ids=["all_live", "released"])
def test_paged_loop_is_bounded_by_the_deepest_live_row(released):
    """Blocks past the deepest LIVE row are neither gathered nor read: they
    may hold NaN. A released row's runaway length must not stretch the loop
    (a masked NaN value would still poison ``p @ v``)."""
    c = make_paged_case(5, T=1, starts=(5, 9, 0), mb=8, released=released)
    # live depth 10 -> chunks 0 and 1 (block 8); poison every pool block a
    # live row maps from its logical block 4 (slot 16) on
    pool_k, pool_v = np.array(c["pool_k"]), np.array(c["pool_v"])
    dead = np.asarray(c["tables"])[:2, 4:].reshape(-1)
    pool_k[dead], pool_v[dead] = np.nan, np.nan
    out = chunked_paged_attention(
        c["q"], jnp.asarray(pool_k), jnp.asarray(pool_v), c["tables"],
        c["new_k"], c["new_v"], c["write_pos"], c["valid"], c["start"],
        block=8)
    assert np.isfinite(np.asarray(out)).all()
    clean = chunked_paged_attention(
        c["q"], c["pool_k"], c["pool_v"], c["tables"], c["new_k"],
        c["new_v"], c["write_pos"], c["valid"], c["start"], block=8)
    np.testing.assert_array_equal(np.asarray(out[:2]), np.asarray(clean[:2]))


def _avals(jaxpr):
    """Every variable's aval in a jaxpr and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v, "aval"):
                yield v.aval
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _avals(sub)


@pytest.mark.parametrize("layout", ["gqa", "latent"])
def test_paged_decode_step_builds_no_array_of_a_slots_whole_extent(layout):
    """The structure behind the speed-up: in ``paged_decode_step``'s jaxpr
    nothing has the shape [slots, max_blocks * bs, ...] but ``slot_mask``
    and what is derived from it (2-D, integer or boolean)."""
    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.generate import paged_decode_step

    slots, bs, mb = 3, 32, 40  # an extent of 1280: 2.5 chunks of 512
    S = mb * bs
    if layout == "latent":
        from agilerl_tpu.llm.presets import preset

        cfg = preset("tiny-mla-moe")
    else:
        cfg = M.GPTConfig(vocab_size=97, n_layer=2, n_head=4, n_kv_head=2,
                          d_model=32, max_seq_len=64, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    carry = (
        M.init_paged_cache(cfg, 1 + slots * mb, bs),
        jnp.zeros((slots, mb), jnp.int32), jnp.zeros((slots, S), jnp.int32),
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), bool), jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), bool),
        jnp.zeros((slots, 2), jnp.uint32),
    )
    jaxpr = jax.make_jaxpr(lambda p, c: paged_decode_step(
        cfg, p, c, lora=None, lora_scale=2.0, temperature=0.9, top_k=0,
        top_p=1.0, eos_id=1, pad_id=0, min_new_tokens=0))(params, carry)
    seen = [a for a in _avals(jaxpr.jaxpr)
            if getattr(a, "shape", ())[:2] == (slots, S)]
    assert seen, "slot_mask itself must be there"
    wide = {(a.shape, str(a.dtype)) for a in seen
            if len(a.shape) > 2 or a.dtype.kind == "f"}
    assert not wide, wide
