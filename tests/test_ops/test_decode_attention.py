"""Chunked cached attention (flash-decode) vs the dense masked-softmax path.

The chunked op must reproduce the dense cached-attention numerics exactly
(same visible set, f32 accumulation) for prefill (T=P, start=0), decode
(T=1, start>0), GQA (rep>1), and ragged left-padded masks."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from agilerl_tpu.ops.decode_attention import chunked_cached_attention


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
