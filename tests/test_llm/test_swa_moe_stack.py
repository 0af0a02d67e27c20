"""Window and full attention mixed by layer (a sliding window in the flash
kernels, in the cached and in the paged attention loop; positions by layer),
the period scan of ``model._run_layers``, ReGLU experts and a router that
reads the attention block's input, through the stack, the serving tier and
GRPO, at a tiny SmallThinker-class configuration in float32 against
``perfbench/reference/smallthinker_f32.py``; the prompt grid that follows
from ``max_seq_len``; what refuses."""

import dataclasses
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm import serving
from agilerl_tpu.llm.serving import ContinuousGenerator
from agilerl_tpu.ops import decode_attention as D
from agilerl_tpu.ops.flash_attention_vjp import (
    _live_tiles, flash_attention_diff, flash_plan)
from perfbench.reference import smallthinker_f32 as ref

G = importlib.import_module("agilerl_tpu.llm.generate")

WINDOW = 8
LAYOUT = (0, 1, 1, 1) * 2  # two periods of [global, window, window, window]
CFG = M.GPTConfig(
    vocab_size=256, n_layer=8, n_head=4, n_kv_head=2, head_size=16,
    d_model=48, max_seq_len=256, rope_theta=1_500_000.0,
    tie_embeddings=False, sliding_window=WINDOW, window_layout=LAYOUT,
    rope_layout=LAYOUT, n_experts=8, expert_top_k=2, capacity_factor=None,
    d_ff_expert=32, router_score="softmax", norm_topk=True,
    expert_act="relu", router_input="attn", dtype=jnp.float32)
REF = dict(n_head=CFG.n_head, n_kv=CFG.kv_heads, theta=CFG.rope_theta,
           eps=CFG.rms_eps, top_k=CFG.expert_top_k, window=WINDOW,
           window_layout=LAYOUT, rope_layout=LAYOUT)
#: at d_model 48 normal(0, 0.02) projections give scores of ~0.01 and an
#: attention that is uniform whatever the mask: drawn this much larger, the
#: scores spread by ~1.5 and the window, and the positions, show
QK_GAIN = 40.0


def sharpen(params):
    """``params`` with wq and wk of every layer scaled by ``QK_GAIN``."""
    def one(tree):
        return {k: (v * QK_GAIN if k in ("wq", "wk") else v)
                for k, v in tree.items()}

    return {**params, "runs": [[one(t) for t in run] if isinstance(run, list)
                               else one(run) for run in params["runs"]]}


@pytest.fixture(scope="module")
def params():
    p = sharpen(M.init_params(jax.random.PRNGKey(0), CFG))
    (run,) = p["runs"]  # one run, stored a position in the period
    assert isinstance(run, list) and len(run) == 4
    assert run[0]["wq"].shape == (2, 48, 64)
    assert run[1]["w_gate"].shape == (2, 8, 48, 32)
    assert "lm_head" in p
    return p


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, CFG.vocab_size, size=n).astype(np.int32)
            for n in lengths]


# --------------------------------------------------------------------------- #
# The configuration's layer pattern
# --------------------------------------------------------------------------- #


def test_the_layer_pattern_and_its_period():
    assert CFG.varies and CFG.stores_runs and CFG.n_window_layers == 6
    assert CFG.layer_runs() == [("attn", 0, 8)]  # a variant ends no run
    assert CFG.run_period(0, 8) == 4
    assert [CFG.layer_variant(i) for i in range(4)] == [
        (0, False), (8, True), (8, True), (8, True)]
    # a window in every layer: a run of equal layers, period 1
    every = dataclasses.replace(CFG, window_layout=None, rope_layout=None)
    assert every.run_period(0, 8) == 1 and every.n_window_layers == 8
    # no period: the whole run is one
    odd = dataclasses.replace(CFG, window_layout=(0, 1, 1, 0, 1, 1, 1, 1))
    assert odd.run_period(0, 8) == 8
    assert not M.GPTConfig(vocab_size=8).varies


def test_run_layer_reads_the_period_layout(params):
    blocks = [M.init_block(k, CFG, i) for i, k in enumerate(
        jax.random.split(jax.random.PRNGKey(0), CFG.n_layer + 3)[1:9])]
    stored = M.stack_run(blocks, 4)
    assert M.run_length(stored) == 8
    for i in (0, 3, 5):
        np.testing.assert_array_equal(M.run_layer(stored, i)["wo"],
                                      blocks[i]["wo"])
    np.testing.assert_array_equal(  # and it is what init_params drew
        M.run_layer(params["runs"][0], 6)["wv"], blocks[6]["wv"])


# --------------------------------------------------------------------------- #
# The flash kernels with a window
# --------------------------------------------------------------------------- #


def dense_attention(q, k, v, mask, window):
    T = q.shape[2]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) / math.sqrt(q.shape[-1])
    t = jnp.arange(T)
    ok = t[None, :] <= t[:, None]
    if window:
        ok = ok & (t[:, None] - t[None, :] < window)
    ok = ok[None, None]
    if mask is not None:
        ok = ok & (mask[:, None, None, :] > 0)
    return jnp.einsum("bhts,bhsd->bhtd",
                      jax.nn.softmax(jnp.where(ok, s, -1e30), -1), v)


# T 50 is no multiple of the tile 16; the window is smaller than, equal to
# and larger than a tile, and as long as the sequence (= no window); at T 96
# under a window of 40 some live tiles touch neither edge and run unmasked
@pytest.mark.parametrize("T,bq,bk,window,with_mask", [
    (50, 16, 16, 8, False), (50, 16, 16, 16, True), (50, 16, 16, 24, True),
    (50, 16, 16, 50, False), (48, 16, 32, 20, True), (37, None, None, 9, True),
    (96, 16, 16, 40, True),  # a band wide enough for interior tiles
])
def test_flash_forward_and_gradients_with_a_window(T, bq, bk, window,
                                                   with_mask):
    ks = jax.random.split(jax.random.PRNGKey(T + window), 4)
    q, k, v = (jax.random.normal(kk, (2, 2, T, 8)) for kk in ks[:3])
    w = jax.random.normal(ks[3], (2, 2, T, 8))
    mask = None
    if with_mask:  # a left-padded row
        mask = jnp.ones((2, T), jnp.int32).at[0, :5].set(0)
        w = w * mask[:, None, :, None]
    got = jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention_diff(
        q, k, v, mask, True, bq, bk, None, True, window) * w), (0, 1, 2))(
            q, k, v)
    want = jax.value_and_grad(lambda q, k, v: jnp.sum(dense_attention(
        q, k, v, mask, window) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_window_as_long_as_the_sequence_is_the_plain_kernel():
    q = jnp.ones((1, 1, 32, 8))
    text = lambda w: re.sub("0x[0-9a-f]+", "", str(jax.make_jaxpr(  # noqa: E731
        lambda q: flash_attention_diff(
            q, q, q, None, True, 16, 16, None, True, w))(q)))
    assert text(32) == text(0) and "flash_fwd_win" not in text(0)
    assert "flash_fwd_win" in text(31)
    with pytest.raises(ValueError, match="causal"):
        flash_attention_diff(q, q, q, None, False, 16, 16, None, True, 8)


def test_the_plan_counts_the_bands_live_tiles():
    # T 8192 under a window of 4096: 30 of the 36 causal tiles of 1024 x
    # 1024 are live, 108 of the 136 of 512 x 512
    assert _live_tiles(8, 8, 1024, 1024, True) == 36
    assert _live_tiles(8, 8, 1024, 1024, True, 4096) == 30
    assert _live_tiles(16, 16, 512, 512, True, 4096) == 108
    assert _live_tiles(16, 16, 512, 512, True) == 136
    for kind in ("fwd", "dq", "dkv"):
        plan = flash_plan(8192, 128, 128, jnp.bfloat16, kind,
                          vmem_capacity=128 * 2 ** 20, window=4096)
        assert plan[:3] == (1024, 1024, 8192), (kind, plan)
        # no window: the plan it was
        assert flash_plan(1024, 128, 128, jnp.bfloat16, kind,
                          vmem_capacity=128 * 2 ** 20) == flash_plan(
            1024, 128, 128, jnp.bfloat16, kind, vmem_capacity=128 * 2 ** 20,
            window=0)


# --------------------------------------------------------------------------- #
# The decode loop with a window
# --------------------------------------------------------------------------- #


def paged_case(depths, T, released=False):
    B, Hq, Hkv, d, bs, mb = 3, 4, 2, 8, 4, 16
    S = bs * mb
    ks = jax.random.split(jax.random.PRNGKey(sum(depths) + T), 5)
    pool_k = jax.random.normal(ks[0], (1 + B * mb, bs, Hkv, d))
    pool_v = jax.random.normal(ks[1], (1 + B * mb, bs, Hkv, d))
    tables = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    start = jnp.asarray(depths, jnp.int32)
    q = jax.random.normal(ks[2], (B, T, Hq, d))
    nk = jax.random.normal(ks[3], (B, T, Hkv, d))
    nv = jax.random.normal(ks[4], (B, T, Hkv, d))
    wp = start[:, None] + jnp.arange(T)[None]
    valid = (jnp.arange(S)[None, :] < (start[:, None] + T)).astype(jnp.int32)
    valid = valid.at[0, :2].set(0)  # a left-padded row
    if released:
        valid = valid.at[2].set(0)
    # gather every slot's whole extent and put the new K/V in
    kc = pool_k[tables].reshape(B, S, Hkv, d)
    vc = pool_v[tables].reshape(B, S, Hkv, d)
    slots = jnp.arange(S)[None, :, None, None]
    for t in range(T):
        at = (slots == wp[:, t][:, None, None, None])
        kc = jnp.where(at, nk[:, t][:, None], kc)
        vc = jnp.where(at, nv[:, t][:, None], vc)
    return dict(q=q, pool_k=pool_k, pool_v=pool_v, tables=tables, nk=nk,
                nv=nv, wp=wp if T > 1 else start, valid=valid, start=start,
                kc=kc, vc=vc)


# depths below, at and past the window; rows at different depths; a first
# chunk (16 slots) that straddles the window's edge; a released row
@pytest.mark.parametrize("window", [5, 16, 24, 100])
@pytest.mark.parametrize("depths,T,released", [
    ((3, 10, 20), 1, False), ((15, 16, 17), 1, False), ((40, 41, 59), 3, False),
    ((60, 30, 0), 1, True)])
def test_paged_attention_with_a_window_equals_gather_and_mask(
        window, depths, T, released):
    c = paged_case(depths, T, released)
    got = D.chunked_paged_attention(
        c["q"], c["pool_k"], c["pool_v"], c["tables"], c["nk"], c["nv"],
        c["wp"], c["valid"], c["start"], block=16, window=window)
    same = D.chunked_cached_attention(
        c["q"], c["kc"], c["vc"], c["valid"], c["start"], block=16,
        window=window)
    want = D._dense_reference(c["q"], c["kc"], c["vc"], c["valid"],
                              c["start"], window=window)
    live = np.asarray(c["valid"].sum(1) > 0)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(same)[live], np.asarray(want)[live],
                               atol=2e-6)
    if window >= 64:  # as long as the extent: no window
        plain = D.chunked_paged_attention(
            c["q"], c["pool_k"], c["pool_v"], c["tables"], c["nk"], c["nv"],
            c["wp"], c["valid"], c["start"], block=16)
        np.testing.assert_array_equal(np.asarray(got)[live],
                                      np.asarray(plain)[live])


@pytest.mark.parametrize("depths,window,chunks", [
    ((40, 41, 59), 0, 4),    # no window: chunks 0..3 of 16 slots
    ((40, 41, 59), 5, 2),    # slot 36 lies in chunk 2: chunks 2 and 3
    ((40, 41, 59), 24, 3),   # slot 17 lies in chunk 1
    ((60, 61, 62), 8, 1),    # slot 53 lies in chunk 3
    ((3, 10, 20), 100, 2),   # a window past every depth: as without one
])
def test_a_window_layers_loop_starts_at_the_chunk_that_holds_its_edge(
        depths, window, chunks):
    """The loop's trip count: chunks fetched, counted by the fetch."""
    c = paged_case(depths, 1)
    B, S = c["valid"].shape
    fetched = []

    def fetch(off):
        jax.debug.callback(lambda o: fetched.append(int(o)), off)
        return (jax.lax.dynamic_slice_in_dim(c["kc"], off, 16, 1),
                jax.lax.dynamic_slice_in_dim(c["vc"], off, 16, 1))

    out = D._online_softmax(c["q"], fetch, S, 2, c["valid"], c["start"], None,
                            16, None, None, window)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert len(fetched) == chunks, fetched
    last = max(depths) // 16
    assert sorted(fetched) == [16 * i for i in range(last - chunks + 1,
                                                     last + 1)]


# --------------------------------------------------------------------------- #
# The stack against the reference
# --------------------------------------------------------------------------- #


def test_forward_logits_match_the_reference(params):
    seqs = prompts(37, 29)  # 3-4 windows long
    toks, mask = G.left_pad(seqs, 0, 40)
    got, _ = M.apply(CFG, params, jnp.asarray(toks),
                     attention_mask=jnp.asarray(mask))
    for i, s in enumerate(seqs):
        want = ref.logits(params, s, **REF)
        assert np.abs(np.asarray(got[i, 40 - len(s):]) - want).max() \
            < ref.LOGIT_TOL


def test_flash_and_remat_agree_with_the_plain_forward(params):
    seqs = prompts(37, 29, seed=1)
    toks, mask = (jnp.asarray(a) for a in G.left_pad(seqs, 0, 40))
    want, _ = M.apply(CFG, params, toks, attention_mask=mask)
    cfg = dataclasses.replace(CFG, use_flash_attention=True, remat=True)
    got, _ = M.apply(cfg, params, toks, attention_mask=mask)
    real = np.asarray(mask, bool)
    assert np.abs(np.asarray(got - want))[real].max() < 1e-4


def test_the_period_scan_equals_the_layers_called_one_by_one(params):
    seqs = prompts(30, 21, seed=2)
    toks, mask = (jnp.asarray(a) for a in G.left_pad(seqs, 0, 32))
    scanned = jax.make_jaxpr(lambda p: M.apply(
        CFG, p, toks, attention_mask=mask)[0])(params)
    # ONE scan of two periods, four layers in its body
    assert str(scanned).count(" scan[") == 1
    want, _ = M.apply(dataclasses.replace(CFG, scan_layers=False), params,
                      toks, attention_mask=mask)
    got, _ = M.apply(CFG, params, toks, attention_mask=mask)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # adapters that differ between the periods: the layers are called
    lora = M.init_lora(jax.random.PRNGKey(1), CFG, 2, ("wq", "wv"))
    lora = jax.tree_util.tree_map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape),
        lora)
    with_lora, _ = M.apply(CFG, params, toks, attention_mask=mask, lora=lora)
    merged, _ = M.apply(CFG, M.merge_lora(params, lora, 2.0), toks,
                        attention_mask=mask)
    np.testing.assert_allclose(with_lora, merged, atol=2e-4)
    del lora["blocks"]["5"]["wv"]
    ragged, _ = M.apply(CFG, params, toks, attention_mask=mask, lora=lora)
    assert np.isfinite(np.asarray(ragged)).all()


def test_prefill_and_one_token_decode_through_the_cache(params):
    seqs = prompts(33, 26, seed=3)
    toks, mask = (jnp.asarray(a) for a in G.left_pad(seqs, 0, 36))
    full, _ = M.apply(CFG, params, toks, attention_mask=mask)
    pos = jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)
    cache = M.init_caches(CFG, 2, 48)
    assert cache.k.shape == (8, 2, 48, 2, 16)
    P = 28
    lg, cache = M.apply(CFG, params, toks[:, :P], attention_mask=mask[:, :P],
                        positions=pos[:, :P], cache=cache)
    real = np.asarray(mask[:, :P], bool)
    assert np.abs(np.asarray(lg - full[:, :P]))[real].max() < 1e-5
    for t in range(P, 36):
        lg, cache = M.apply(CFG, params, toks[:, t:t + 1],
                            attention_mask=mask[:, t:t + 1],
                            positions=pos[:, t:t + 1], cache=cache)
        assert float(jnp.abs(lg[:, 0] - full[:, t]).max()) < 1e-5


def generator(**kw):
    args = dict(max_new_tokens=12, prompt_buckets=(16, 32), slots=3,
                block_size=8, decode_chunk=4, capture_logprobs=True,
                metrics=observability.MetricsRegistry())
    args.update(kw)
    return ContinuousGenerator(CFG, **args)


@pytest.mark.parametrize("lengths", [(29, 32, 25), (24, 17, 32)])
def test_paged_decode_with_prefix_hits_matches_the_reference(params, lengths):
    """Sampled rollouts of prompts 3-4 windows long: the first of each is
    prefilled, the repeats are admitted by prefix hits (the last block
    copied). The tier's own log-probabilities of its sampled tokens against
    the reference's full forward, hit or miss."""
    a, b, c = prompts(*lengths, seed=4)
    seqs = [a, a, b, a, c, b]
    gen = generator()
    toks, masks, info = gen.generate(seqs, jax.random.PRNGKey(3), params)
    assert masks.all()
    assert info["prefix_hit_rows"] == [False, True, False, True, False, True]
    for i, s in enumerate(seqs):
        full = np.concatenate([s, toks[i]])
        want, _ = ref.token_logprobs(
            params, full, np.arange(len(s) - 1, len(full) - 1), **REF)
        assert np.abs(want - info["logprobs"][i]).max() < ref.LOGIT_TOL, i
    gauges = gen.metrics.dump()["gauges"]
    assert gauges["serving/window_layers"] == 6
    # the last chunk: rows 30-44 slots deep, blocks of 8, a window of 8
    assert gauges["serving/window_dead_bytes"] > 0
    per_layer_block = 8 * 2 * 16 * 4 * 2
    assert gauges["serving/window_dead_bytes"] % (6 * per_layer_block) == 0


def test_greedy_continuous_equals_generate_token_for_token(params):
    a, b = prompts(29, 32, seed=5)
    seqs = [a, a, b, b, a]
    toks, _, info = generator().generate(
        seqs, jax.random.PRNGKey(4), params, greedy=True)
    assert sum(info["prefix_hit_rows"]) == 3
    tk, mk = G.left_pad(seqs, 0, 32)
    want, _ = G.generate(CFG, params, jnp.asarray(tk), jnp.asarray(mk),
                         jax.random.PRNGKey(4), max_new_tokens=12,
                         temperature=0.0)
    np.testing.assert_array_equal(np.asarray(want), toks)


# --------------------------------------------------------------------------- #
# The two controls: each mechanism left out is seen
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("change", [
    dict(window_layout=(0,) * 8),  # the window layers run as full attention
    dict(rope_layout=(1,) * 8),    # rotary in the global layers too
])
def test_a_mechanism_left_out_fails_the_comparison(params, change):
    (s,) = prompts(40, seed=6)
    at = np.arange(24, 39)  # positions deeper than the window
    want, _ = ref.token_logprobs(params, s, at, **REF)
    wrong, _ = ref.token_logprobs(params, s, at, **{**REF, **change})
    assert np.abs(want - wrong).mean() > 100 * ref.LOGIT_TOL
    # and the program with the mechanism left out differs as much
    cfg = dataclasses.replace(CFG, **change)
    got = np.asarray(M.token_logprobs(cfg, params, jnp.asarray(s[None])))[0, at]
    assert np.abs(got - wrong).max() < 2e-4
    assert np.abs(got - want).mean() > 100 * ref.LOGIT_TOL


# --------------------------------------------------------------------------- #
# GRPO
# --------------------------------------------------------------------------- #


def make_agent(params, **kw):
    cfg = dataclasses.replace(CFG, dtype=jnp.float32)
    return GRPO(config=cfg, base_params=params, pad_token_id=0, eos_token_id=1,
                group_size=2, batch_size=2, max_output_tokens=8, seed=0,
                lora_rank=2, lora_targets=("wq", "wv"), continuous_decode=True,
                capture_logprobs=True, min_output_tokens=8, **kw)


def test_grpo_rolls_out_learns_and_moves_every_layers_adapters(params):
    agent = make_agent(params)
    (a,) = prompts(20, seed=7)
    batch = {"input_ids": a[None], "attention_mask": np.ones((1, 20), np.int32)}
    comp, cmask = agent.get_action(batch)
    info = agent.last_generation_info
    assert "slots" in info and info["prefix_hit_rows"] == [False, True]
    # GRPO passes no grid: it follows from the model's max_seq_len (256)
    gen = agent._get_continuous_generator()
    assert gen.prompt_buckets == serving.BASE_PROMPT_BUCKETS
    ids = np.concatenate([np.repeat(a[None], 2, 0), comp], axis=1)
    action = np.zeros((2, ids.shape[1] - 1), np.int32)
    action[:, 19:] = 1
    lp = np.asarray(agent.behavior_logprobs(ids, action))
    np.testing.assert_allclose(lp[:, 19:], info["logprobs"], atol=2e-5)
    before = jax.tree_util.tree_map(np.asarray, agent.actor.params)
    loss, kl = agent.learn((ids, action, np.asarray([[1.0, -1.0]], np.float32)))
    assert np.isfinite(loss) and np.isfinite(kl)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        agent.actor.params, before)
    for i in range(8):
        for target in ("wq", "wv"):
            assert moved["blocks"][str(i)][target]["B"] > 0, (i, target)


# --------------------------------------------------------------------------- #
# The prompt grid follows from the model
# --------------------------------------------------------------------------- #


def test_the_prompt_grid_follows_from_max_seq_len():
    base = serving.BASE_PROMPT_BUCKETS
    grid = lambda n: serving.default_prompt_buckets(  # noqa: E731
        M.GPTConfig(vocab_size=8, max_seq_len=n))
    assert grid(64) == grid(2048) == grid(4095) == base
    assert grid(4096) == base + (4096,)
    assert grid(8192) == grid(16383) == base + (4096, 8192)
    long = M.GPTConfig(vocab_size=32, n_layer=1, n_head=2, d_model=16,
                       max_seq_len=8192)
    gen = ContinuousGenerator(long, max_new_tokens=128, decode_chunk=32)
    assert gen.prompt_buckets[-1] == 8192 and gen.fits(2, 8064)
    assert gen.max_blocks == 260 and gen.n_blocks == 1 + 8 * 260
    short = ContinuousGenerator(dataclasses.replace(long, max_seq_len=2048),
                                max_new_tokens=128, decode_chunk=32)
    assert short.prompt_buckets == base and short.max_blocks == 68
    assert not short.fits(2, 2049)
    assert serving.BucketedGenerator(long).prompt_buckets[-1] == 8192
    # a grid that is passed wins
    assert ContinuousGenerator(long, prompt_buckets=(32, 64)).max_blocks == 4


@pytest.mark.parametrize("name, free_gb, grid, refuses", [
    # 8 slots x (8192 + 64) tokens x 128 KB a token: 8.66 GB
    ("llama3-8b", 16.9, None, False),
    ("llama3-8b", 16.9 - 16.1, None, True),  # beside its own weights
    # 8 slots x (32768 + 64) tokens x 56 KB a token: 15.07 GB
    ("qwen2-7b", 16.9 - 15.2, None, True),
    ("qwen2-7b", 16.9 - 15.2, serving.BASE_PROMPT_BUCKETS, False),
    # 2 attention layers on one K/V head: 1 KB a token, 2.15 GB at 262144
    ("jamba2-3b", 16.9 - 6.1, None, False),
])
def test_a_long_context_presets_pool_builds_or_refuses_with_its_sizes(
        monkeypatch, name, free_gb, grid, refuses):
    """Without a grid the pool follows from ``max_seq_len``: a preset with
    a long context gets a pool for it, and one the device cannot hold is
    refused with the sizes and what to pass — the allocator is not left to
    run out of memory."""
    from agilerl_tpu.llm.presets import preset

    cfg = preset(name, dtype=jnp.bfloat16)
    gen = ContinuousGenerator(cfg, prompt_buckets=grid)
    top = gen.prompt_buckets[-1]
    assert top == (cfg.max_seq_len if grid is None else 2048)
    assert gen.n_blocks == 1 + 8 * -(-(top + 64) // 32)
    monkeypatch.setattr(serving, "_device_bytes_free",
                        lambda: int(free_gb * 1e9))
    if refuses:
        with pytest.raises(ValueError, match=(
                rf"paged pool would hold \d+\.\d\d GB .*up to {top} \+ 64 "
                r"new tokens.*GB free: pass prompt_buckets=")):
            gen._refuse_a_pool_the_device_cannot_hold()
    else:
        gen._refuse_a_pool_the_device_cannot_hold()
    # the CPU backend states no memory: nothing to hold the pool to
    monkeypatch.undo()
    assert serving._device_bytes_free() is None


def test_grpo_sizes_its_rollout_tier_from_a_long_context(params):
    """``GRPO`` passes no grid and no ``n_blocks``: over a model with a
    context past 2048 its generator takes prompts up to it."""
    agent = make_agent(params)
    agent.model_config = dataclasses.replace(agent.model_config,
                                             max_seq_len=8192)
    gen = agent._get_continuous_generator()
    assert gen.prompt_buckets == serving.BASE_PROMPT_BUCKETS + (4096, 8192)
    assert gen.fits(2, 8192) and not gen.fits(2, 8193)
    assert gen.max_blocks == (8192 + 8) // 32 + 1


# --------------------------------------------------------------------------- #
# What refuses
# --------------------------------------------------------------------------- #


def test_what_this_stack_cannot_do_refuses_by_name(params):
    with pytest.raises(ValueError, match="sliding-window layers"):
        generator(speculate=True)
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "fsdp", "tp"))
    with pytest.raises(ValueError, match="sliding-window layers"):
        generator(mesh=mesh)
    with pytest.raises(ValueError, match="sliding-window layers"):
        serving.BucketedGenerator(CFG, mesh=mesh)
    with pytest.raises(ValueError, match="sliding-window layers"):
        make_agent(params).to_mesh(mesh)
    with pytest.raises(NotImplementedError, match="sliding-window layers"):
        make_agent(params).attach_rollout_fleet(object())
    agent = make_agent(params, sequence_parallel_axis="sp")
    ids = jnp.ones((2, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="sliding-window layers"):
        agent._resolve_learn_fns(ids, jnp.ones((2, 8), jnp.int32))


def test_configurations_the_layers_do_not_compute_refuse():
    for change in (dict(window_layout=(0, 1)), dict(rope_layout=[0] * 8),
                   dict(sliding_window=0)):
        with pytest.raises(ValueError, match="layout"):
            dataclasses.replace(CFG, **change)
    for change in (dict(attn_layer_period=2, router_input="ffn",
                        expert_act="silu"),
                   dict(cca_time0=2, cca_time1=2, router_input="ffn",
                        expert_act="silu")):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **change)
    with pytest.raises(ValueError, match="expert_act"):
        dataclasses.replace(CFG, expert_act="gelu")
    with pytest.raises(ValueError, match="dropless"):
        M.GPTConfig(vocab_size=32, n_experts=4, expert_act="relu")
    with pytest.raises(ValueError, match="dropless"):
        M.GPTConfig(vocab_size=32, router_input="attn")
    with pytest.raises(NotImplementedError, match="latent"):
        D.chunked_cached_attention(
            jnp.ones((1, 1, 2, 8)), jnp.ones((1, 4, 8)), None,
            jnp.ones((1, 4), jnp.int32), 0, scale=1.0, v_width=4, window=2)
    # a caller that does not carry the router's logits past attention
    blk = M.init_block(jax.random.PRNGKey(0), CFG, 0)
    with pytest.raises(ValueError, match="carries its logits"):
        M._block_ffn(CFG, blk, jnp.ones((1, 2, 48)), None, 2.0)
