"""Compressed convolutional attention (llm/cca.py), the router MLP with its
carried state (llm/moe.mlp_logits) and the scaled merge through the stack,
the serving tier and GRPO, at the preset ``tiny-cca-moe`` in float32,
against ``perfbench/reference/zaya_f32.py``: the three shapes of ``cca.qkv``,
causality, the value shift, the layer loop's second stream, top-1 routing,
``forward`` logits, prefill and paged decode through the pool AND the
rolling per-slot state with prefix hits and snapshots, GRPO with adapters on
``wq`` / ``wv1`` / ``wv2``, what refuses, and that the stacks that existed
lower to the programs they lowered to before."""

import dataclasses
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import cca, moe
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.llm.serving import ContinuousGenerator
from perfbench.reference import zaya_f32 as ref

G = importlib.import_module("agilerl_tpu.llm.generate")

CFG = preset("tiny-cca-moe", dtype=jnp.float32, remat=False,
             use_flash_attention=False)
REF = dict(n_head=CFG.n_head, n_kv=CFG.kv_heads, theta=CFG.rope_theta,
           rotary=CFG.rotary_share, eps=CFG.rms_eps)


@pytest.fixture(scope="module")
def params():
    p = M.init_params(jax.random.PRNGKey(0), CFG)
    (run,) = p["runs"]  # one run: gamma is stored for layer 0 too
    assert jax.tree_util.tree_leaves(run)[0].shape[0] == 3
    assert run["router"].shape == (3, 16, 8) and "wv" not in run
    # drawn off the values at which they would check nothing
    for leaf, at in (("router_bias", 0.0), ("tau", 1.0), ("router_gamma", 1.0),
                     ("conv0_b", 0.0), ("conv1_b", 0.0), ("router_in_b", 0.0)):
        assert float(jnp.abs(run[leaf] - at).min()) > 0, leaf
    for merge in ("merge1", "merge2"):
        assert float(jnp.abs(run[merge][:, (0, 2)] - 1.0).min()) > 0
        assert float(jnp.abs(run[merge][:, (1, 3)]).min()) > 0
    return p


def layer(params, i):
    return jax.tree_util.tree_map(lambda a: a[i], params["runs"][0])


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, CFG.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def normed_input(T, B=2, seed=5):
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, T, CFG.d_model))
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    return x, pos, jnp.ones((B, T), jnp.int32)


def zero_state(B=2):
    return tuple(s[0] for s in cca.init_state(CFG, 1, B))


# --------------------------------------------------------------------------- #
# cca.qkv
# --------------------------------------------------------------------------- #


def test_the_three_shapes_of_cca_agree(params):
    """Whole sequence = prefill of T - 1 from zero + one step = T steps."""
    blk = layer(params, 1)
    T = 9
    x, pos, mask = normed_input(T)
    whole = cca.qkv(CFG, blk, x, pos, mask)
    assert whole[3] is None and whole[4] is None
    assert whole[0].shape == (2, T, 4, 16) and whole[1].shape == (2, T, 2, 16)
    head = cca.qkv(CFG, blk, x[:, :-1], pos[:, :-1], mask[:, :-1],
                   zero_state())
    last = cca.qkv(CFG, blk, x[:, -1:], pos[:, -1:], mask[:, -1:], head[3])
    state, steps = zero_state(), []
    for t in range(T):
        *qkv, state, prev = cca.qkv(CFG, blk, x[:, t:t + 1], pos[:, t:t + 1],
                                    mask[:, t:t + 1], state)
        assert prev is None  # a step has no "before its last token"
        steps.append(qkv)
    for i in range(3):
        two = jnp.concatenate([head[i], last[i]], axis=1)
        many = jnp.concatenate([s[i] for s in steps], axis=1)
        np.testing.assert_allclose(two, whole[i], atol=1e-6)
        np.testing.assert_allclose(many, whole[i], atol=1e-6)
    # the state before a prefill's last token is the state after T - 2
    two_less = cca.qkv(CFG, blk, x[:, :-2], pos[:, :-2], mask[:, :-2],
                       zero_state())
    for a, b in zip(head[4], two_less[3]):
        np.testing.assert_allclose(a, b, atol=1e-6)
    for a, b in zip(last[3], state):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_cca_is_causal(params):
    blk = layer(params, 0)
    x, pos, mask = normed_input(10)
    t = 6
    moved = x.at[:, t].add(1.0)
    for a, b in zip(cca.qkv(CFG, blk, x, pos, mask)[:3],
                    cca.qkv(CFG, blk, moved, pos, mask)[:3]):
        np.testing.assert_array_equal(a[:, :t], b[:, :t])
        # the convolutions and the shift carry t into t + 1, no further
        assert float(jnp.abs(a[:, t + 1] - b[:, t + 1]).max()) > 0
    _, k, v, *_ = cca.qkv(CFG, blk, x, pos, mask)
    _, k2, v2, *_ = cca.qkv(CFG, blk, moved, pos, mask)
    np.testing.assert_array_equal(v[:, t + 2:], v2[:, t + 2:])
    # k0 = k1 = 2: position t reaches t + 2 through both convolutions
    assert float(jnp.abs(k[:, t + 2] - k2[:, t + 2]).max()) > 0
    np.testing.assert_array_equal(k[:, t + 3:], k2[:, t + 3:])


def test_value_head_one_is_the_previous_tokens(params):
    blk = layer(params, 2)
    x, pos, mask = normed_input(7)
    _, _, v, *_ = cca.qkv(CFG, blk, x, pos, mask)
    np.testing.assert_allclose(v[:, :, 0], x @ blk["wv1"], atol=1e-6)
    np.testing.assert_allclose(v[:, 1:, 1], (x @ blk["wv2"])[:, :-1],
                               atol=1e-6)
    np.testing.assert_array_equal(v[:, 0, 1], 0.0)


def test_a_masked_step_leaves_the_state_and_pads_add_zeros(params):
    blk = layer(params, 0)
    x, pos, mask = normed_input(6)
    *_, state, _ = cca.qkv(CFG, blk, x, pos, mask, zero_state())
    *_, after, _ = cca.qkv(CFG, blk, x[:, :1], pos[:, :1],
                           jnp.zeros((2, 1), jnp.int32), state)
    for a, b in zip(state, after):
        np.testing.assert_array_equal(a, b)
    # left padding: a row behind two pads equals the bare row
    padded = jnp.concatenate([jnp.ones((2, 2, CFG.d_model)), x], axis=1)
    pmask = jnp.concatenate([jnp.zeros((2, 2), jnp.int32), mask], axis=1)
    ppos = jnp.maximum(jnp.cumsum(pmask, -1) - 1, 0)
    for a, b in zip(cca.qkv(CFG, blk, x, pos, mask)[:3],
                    cca.qkv(CFG, blk, padded, ppos, pmask)[:3]):
        np.testing.assert_allclose(a, b[:, 2:], atol=1e-6)


# --------------------------------------------------------------------------- #
# The router and the layer loop's second stream
# --------------------------------------------------------------------------- #


def test_top_one_weight_is_the_probability_and_the_bias_moves_the_choice():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    kw = dict(top_k=1, score="softmax", norm_topk=False)
    choice, weight = moe.route(logits, None, **kw)
    pr = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_array_equal(choice[:, 0], pr.argmax(-1))
    np.testing.assert_allclose(weight[:, 0], pr.max(-1), rtol=1e-6)
    assert float(weight.max()) < 1.0  # unrenormalised: not 1
    bias = jnp.zeros((8,)).at[5].set(10.0)
    choice_b, weight_b = moe.route(logits, bias, **kw)
    assert (choice_b == 5).all() and not (choice == 5).all()
    np.testing.assert_allclose(weight_b[:, 0], pr[:, 5], rtol=1e-6)


def test_the_router_mlp_is_step_eight(params):
    blk = layer(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, CFG.d_model))
    s_prev = jax.random.normal(jax.random.PRNGKey(2), (6, CFG.router_hidden))
    logits, z = moe.mlp_logits(x, blk, s_prev, CFG.rms_eps)
    want_z = x @ blk["router_in"] + blk["router_in_b"] \
        + blk["router_gamma"] * s_prev
    u = want_z / np.sqrt(np.mean(want_z ** 2, -1, keepdims=True) + CFG.rms_eps)
    u = jax.nn.gelu(u * blk["router_norm"] @ blk["router_w1"]
                    + blk["router_b1"], approximate=False)
    u = jax.nn.gelu(u @ blk["router_w2"] + blk["router_b2"],
                    approximate=False)
    np.testing.assert_allclose(z, want_z, atol=1e-5)
    np.testing.assert_allclose(logits, u @ blk["router"], atol=1e-5)
    assert logits.shape == (6, CFG.n_experts)


def test_scan_equals_unrolled_and_layer_zero_has_no_gamma(params):
    toks = jnp.asarray(np.stack(prompts(20, 20)))
    scanned, _ = M.apply(CFG, params, toks)
    unrolled, _ = M.apply(dataclasses.replace(CFG, scan_layers=False),
                          params, toks)
    np.testing.assert_allclose(scanned, unrolled, atol=1e-5)

    def with_gamma(i, value):
        run = dict(params["runs"][0])
        run["router_gamma"] = run["router_gamma"].at[i].set(value)
        return {**params, "runs": [run]}

    # layer 0 mixes with zeros: its gamma is stored and multiplies nothing
    first, _ = M.apply(CFG, with_gamma(0, 7.0), toks)
    np.testing.assert_array_equal(first, scanned)
    # layer 1's does carry layer 0's router state in
    second, _ = M.apply(CFG, with_gamma(1, 7.0), toks)
    assert float(jnp.abs(second - scanned).max()) > 1e-4


# --------------------------------------------------------------------------- #
# Against the reference
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("loop", ["scanned", "unrolled"])
def test_forward_logits_match_the_reference_with_left_padding(params, loop):
    cfg = dataclasses.replace(CFG, scan_layers=loop == "scanned")
    seqs = prompts(24, 17, 9)
    toks, mask = G.left_pad(seqs, 0, 24)
    got, _ = M.apply(cfg, params, jnp.asarray(toks),
                     attention_mask=jnp.asarray(mask))
    for i, s in enumerate(seqs):
        want = ref.logits(params, s, **REF)
        assert np.abs(np.asarray(got[i, 24 - len(s):]) - want).max() \
            < ref.LOGIT_TOL, i


def test_the_reference_in_bf16_fails_the_logit_tolerance(params):
    s, = prompts(24)
    want = ref.logits(params, s, **REF)
    lossy = ref.logits(params, s, dtype=jnp.bfloat16, **REF)
    assert np.abs(lossy - want).max() > 10 * ref.LOGIT_TOL


def test_dense_cached_prefill_and_decode_match_the_full_forward(params):
    seqs = prompts(20, 14)
    toks, mask = G.left_pad(seqs, 0, 20)
    toks, mask = jnp.asarray(toks), jnp.asarray(mask)
    full, _ = M.apply(CFG, params, toks, attention_mask=mask)
    pos = jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)
    cache = M.init_caches(CFG, 2, 32)
    assert cache.k.shape == cache.v.shape == (3, 2, 32, 2, 16)
    (p_win, c0_win, v_prev), = cache.state  # one run of three layers
    assert p_win.shape == c0_win.shape == (3, 2, 1, 96)
    assert v_prev.shape == (3, 2, 16)
    P = 12
    lg, cache = M.apply(CFG, params, toks[:, :P], attention_mask=mask[:, :P],
                        positions=pos[:, :P], cache=cache)
    assert cache.prev_state is not None
    real = np.asarray(mask[:, :P], bool)
    assert np.abs(np.asarray(lg - full[:, :P]))[real].max() < 1e-5
    for t in range(P, 20):
        lg, cache = M.apply(CFG, params, toks[:, t:t + 1],
                            attention_mask=mask[:, t:t + 1],
                            positions=pos[:, t:t + 1], cache=cache)
        assert float(jnp.abs(lg[:, 0] - full[:, t]).max()) < 1e-5


# --------------------------------------------------------------------------- #
# The serving tier: the paged pool and the rolling state beside it
# --------------------------------------------------------------------------- #


def generator(**kw):
    args = dict(max_new_tokens=12, prompt_buckets=(16, 32), slots=3,
                block_size=8, decode_chunk=4, capture_logprobs=True,
                metrics=observability.MetricsRegistry())
    args.update(kw)
    return ContinuousGenerator(CFG, **args)


def check_rows_against_reference(params, seqs, toks, lps, tol=ref.LOGIT_TOL):
    for i, s in enumerate(seqs):
        full = np.concatenate([s, toks[i]])
        want, _ = ref.token_logprobs(
            params, full, np.arange(len(s) - 1, len(full) - 1), **REF)
        assert np.abs(want - lps[i]).max() < tol, i


# 16 = a prompt bucket exactly; 8 = one block; 9 / 17 cross a block and a
# bucket; rows sit at different depths in every chunk
@pytest.mark.parametrize("lengths", [(13, 16, 9), (8, 17, 16)])
def test_paged_decode_through_pool_and_rolling_state_matches_the_reference(
        params, lengths):
    """Sampled rollouts, each prompt three times: the first is prefilled,
    the repeats are admitted by prefix hits (the last block copied AND the
    rolling state restored from its snapshot). The tier's own
    log-probabilities of its sampled tokens against the reference's full
    forward, hit or miss."""
    a, b, c = prompts(*lengths)
    seqs = [a, a, b, a, c, b, c, b]
    gen = generator()
    toks, masks, info = gen.generate(seqs, jax.random.PRNGKey(3), params)
    assert masks.all()
    assert info["prefix_hit_rows"] == [
        False, True, False, True, False, True, True, True]
    assert not np.array_equal(toks[0], toks[1])  # sampled: the rows differ
    check_rows_against_reference(params, seqs, toks, info["logprobs"])


def test_greedy_continuous_equals_generate_token_for_token(params):
    a, b = prompts(13, 16, seed=2)
    seqs = [a, a, b, b, a]
    toks, _, info = generator().generate(
        seqs, jax.random.PRNGKey(4), params, greedy=True)
    assert sum(info["prefix_hit_rows"]) == 3
    tk, mk = G.left_pad(seqs, 0, 16)
    want, _ = G.generate(CFG, params, jnp.asarray(tk), jnp.asarray(mk),
                         jax.random.PRNGKey(4), max_new_tokens=12,
                         temperature=0.0)
    np.testing.assert_array_equal(np.asarray(want), toks)


def test_a_prefix_hit_row_equals_the_same_row_prefilled_afresh(params):
    a, b = prompts(13, 9, seed=3)
    seqs = [a, a, b, a]
    hit, _, hit_info = generator().generate(
        seqs, jax.random.PRNGKey(5), params, greedy=True)
    fresh, _, fresh_info = generator(prefix_cache=False).generate(
        seqs, jax.random.PRNGKey(5), params, greedy=True)
    assert hit_info["prefix_hit_rows"] == [False, True, False, True]
    assert not any(fresh_info["prefix_hit_rows"])
    np.testing.assert_array_equal(hit, fresh)
    np.testing.assert_allclose(hit_info["logprobs"], fresh_info["logprobs"],
                               atol=1e-5)


def test_the_pool_keeps_ordinary_kv_and_a_state_a_slot(params):
    gen = generator()
    a, = prompts(13)
    gen.generate([a, a], jax.random.PRNGKey(1), params)
    pool = gen._pool
    assert pool.k.shape == pool.v.shape == (3, gen.n_blocks, 8, 2, 16)
    (p_win, c0_win, v_prev), = pool.state
    assert p_win.shape == c0_win.shape == (3, gen.slots, 1, 96)
    assert v_prev.shape == (3, gen.slots, 16)
    assert pool.snap[0][0].shape == (3, gen.slots + 1, 1, 96)  # + the sink
    dump = gen.metrics.dump()
    assert dump["gauges"]["serving/state_cache_bytes"] == \
        M.state_cache_bytes(pool) == 3 * (2 * gen.slots + 1) * (96 + 96 + 16) * 4
    assert dump["counters"]["serving/state_snapshots_stored_total"] == 1
    # 3 chunks of 4 steps x 3 layers x 8 experts; 3 rows (a free slot's row
    # routes too) x 1 choice touch 1 to 3 experts a layer a step
    assert dump["counters"]["serving/moe_expert_slots_total"] == 3 * 4 * 3 * 8
    hit = dump["counters"]["serving/moe_experts_hit_total"]
    assert 3 * 4 * 3 * 1 <= hit <= 3 * 4 * 3 * gen.slots


def test_a_weight_epoch_change_drops_the_snapshots(params):
    gen = generator()
    a, = prompts(13)
    gen.generate([a, a], jax.random.PRNGKey(1), params)
    assert len(gen._snapshots) == 1
    other = jax.tree_util.tree_map(lambda x: x, params)  # a new tree
    toks, _, info = gen.generate([a, a], jax.random.PRNGKey(1), other)
    assert info["prefix_hit_rows"] == [False, True]  # prefilled again
    counters = gen.metrics.dump()["counters"]
    assert counters["serving/state_snapshot_evictions_total"] >= 1
    check_rows_against_reference(params, [a, a], toks, info["logprobs"])


def test_a_chain_without_its_snapshot_admits_as_a_miss(params):
    a, b, c = prompts(13, 16, 9)
    gen = generator(slots=1)  # one snapshot entry a slot
    toks, _, info = gen.generate([a, b, a, a], jax.random.PRNGKey(2), params)
    # b's prefill took the one entry: a's chain is cached, its snapshot is
    # gone, so the third request prefills again (and the fourth hits)
    assert info["prefix_hit_rows"] == [False, False, False, True]
    check_rows_against_reference(params, [a, b, a, a], toks, info["logprobs"])


# --------------------------------------------------------------------------- #
# GRPO
# --------------------------------------------------------------------------- #


def make_agent(params, **kw):
    args = dict(config=CFG, base_params=params, pad_token_id=0,
                eos_token_id=1, group_size=2, batch_size=4,
                max_output_tokens=8, lora_rank=2,
                lora_targets=("wq", "wv1", "wv2"), continuous_decode=True,
                capture_logprobs=True, min_output_tokens=8, seed=0)
    args.update(kw)
    return GRPO(**args)


def test_grpo_trains_adapters_on_wq_wv1_wv2_and_nothing_of_the_base(params):
    agent = make_agent(params)
    assert sorted(agent.actor.params["blocks"]["0"]) == ["wq", "wv1", "wv2"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, CFG.vocab_size, size=(1, 12)).astype(np.int32)
    batch = {"input_ids": prompt,  # get_action repeats it group_size times
             "attention_mask": np.ones((1, 12), np.int32)}
    comp, cmask = agent.get_action(batch)
    info = agent.last_generation_info
    assert "slots" in info and info["prefix_hit_rows"] == [False, True]
    ids = np.concatenate([np.repeat(prompt, 2, 0), comp], axis=1)
    action = np.concatenate(
        [np.zeros((2, 11), np.int32), cmask.astype(np.int32)], axis=1)
    # the tier's captured log-probabilities are the learn side's own
    lp = np.asarray(agent.behavior_logprobs(ids, action))
    np.testing.assert_allclose(lp[:, 11:], info["logprobs"], atol=2e-5)
    before = jax.tree_util.tree_map(np.asarray, agent.actor.params)
    base_before = jax.tree_util.tree_map(np.asarray, agent.base_params)
    rewards = np.asarray([[1.0, -1.0]], np.float32)
    loss, kl = agent.learn((ids, action, rewards))
    assert np.isfinite(loss) and np.isfinite(kl)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        agent.actor.params, before)
    for i in ("0", "1", "2"):
        for target in ("wq", "wv1", "wv2"):
            assert moved["blocks"][i][target]["B"] > 0, (i, target)
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           jax.tree_util.tree_map(np.asarray,
                                                  agent.base_params),
                           base_before)


def test_the_adapter_gradient_matches_the_reference_with_it_merged(params):
    """d(sum of log-probabilities)/d(adapter) along a random direction
    against central differences of the REFERENCE with the adapter merged
    into the weights."""
    seq = prompts(24, seed=9)[0]
    lora = M.init_lora(jax.random.PRNGKey(1), CFG, 2, ("wq", "wv1", "wv2"))
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    lora = jax.tree_util.tree_map(  # B is zero-initialised: move off it
        lambda x: x + 0.05 * jax.random.normal(k1, x.shape), lora)
    direction = jax.tree_util.tree_map(
        lambda x: jax.random.normal(k2, x.shape), lora)

    def program_loss(lo):
        return M.token_logprobs(CFG, params, jnp.asarray(seq[None]),
                                lora=lo).sum()

    def reference_loss(eps):
        lo = jax.tree_util.tree_map(lambda x, d: x + eps * d, lora, direction)
        merged = M.merge_lora(params, lo, 2.0)
        return float(ref.token_logprobs(
            merged, seq, np.arange(len(seq) - 1), **REF)[0].sum())

    grad = jax.grad(program_loss)(lora)
    got = sum(float((g * d).sum()) for g, d in zip(
        jax.tree_util.tree_leaves(grad),
        jax.tree_util.tree_leaves(direction)))
    eps = 1e-2
    want = (reference_loss(eps) - reference_loss(-eps)) / (2 * eps)
    assert abs(got - want) < 2e-2 * max(1.0, abs(want)), (got, want)


# --------------------------------------------------------------------------- #
# What refuses
# --------------------------------------------------------------------------- #


def test_what_would_lose_the_rolling_state_refuses_by_name(params):
    with pytest.raises(ValueError, match="rolling state"):
        generator(speculate=True)
    with pytest.raises(NotImplementedError, match="rolling state"):
        generator().submit_prefilled(
            np.arange(3, 9, dtype=np.int32), k_prompt=None, v_prompt=None,
            tok0=0, done0=False, key_next=None)
    pool = M.init_paged_cache(CFG, 5, 8, slots=2, snapshots=2)
    with pytest.raises(NotImplementedError, match="rolling state"):
        M.forward_paged(CFG, params, jnp.ones((2, 3), jnp.int32),
                        jnp.zeros((2, 3), jnp.int32),
                        jnp.zeros((2, 3), jnp.int32), pool,
                        jnp.zeros((2, 4), jnp.int32),
                        jnp.ones((2, 32), jnp.int32))
    with pytest.raises(ValueError, match="per slot"):
        M.init_paged_cache(CFG, 5, 8)
    agent = make_agent(params, sequence_parallel_axis="sp")
    ids = jnp.ones((2, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="rolling"):
        agent._resolve_learn_fns(ids, jnp.ones((2, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="rolling state"):
        agent.attach_rollout_fleet(object())
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "fsdp", "tp"))
    with pytest.raises(ValueError, match="conv0_w"):
        make_agent(params).to_mesh(mesh)
    with pytest.raises(ValueError, match="rolling-state cache"):
        generator(mesh=mesh)


def test_configurations_the_layers_do_not_compute_refuse():
    with pytest.raises(ValueError, match="wv1"):
        M.init_lora(jax.random.PRNGKey(0), CFG, 2, ("wq", "wv"))
    with pytest.raises(ValueError, match="FFN projections"):
        M.init_lora(jax.random.PRNGKey(0), CFG, 2, ("wq", "w_up"))
    for change in (dict(kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=8,
                        v_head_dim=8),
                   dict(attn_layer_period=2), dict(qkv_bias=True),
                   dict(capacity_factor=1.25, router_hidden=0,
                        router_bias=False, norm_topk=True),
                   dict(n_kv_head=1, n_head=4)):
        with pytest.raises(ValueError, match="compressed convolutional"):
            dataclasses.replace(CFG, **change)
    with pytest.raises(ValueError, match="CCA stack's layers alone"):
        M.GPTConfig(vocab_size=32, rotary_share=0.5)
    with pytest.raises(ValueError, match="CCA stack's layers alone"):
        M.GPTConfig(vocab_size=32, scaled_merge=True)
    with pytest.raises(ValueError, match="router MLP"):
        dataclasses.replace(CFG, n_experts=0)


# --------------------------------------------------------------------------- #
# The stacks that existed lower to the programs they lowered to
# --------------------------------------------------------------------------- #

#: sha256 (first 16 digits) of the lowered text of each program at the commit
#: before CCA (13b37f2), on this installation: ``_run_layers`` carries a tree
#: and ``route`` takes its logits from outside now, and neither may change a
#: program of a stack that is not CCA. The three ``*/decode`` are re-pinned at
#: PR 36's text, on purpose: ``forward_paged`` traces the projections and the
#: FFN under ``observability.device_scope`` (``decode/proj``, ``decode/ffn``),
#: which puts a frontend attribute on their operations so that the compile
#: cache cannot hand out an executable without the names.
#: ``DECODE_WITHOUT_SCOPES`` keeps their hashes at 13b37f2: with the helper
#: made a no-op they lower to that text still
#: (``tests/test_observability/test_device_scopes.py``). The six others are as
#: they were: the learn and prefill programs do not take the paged path, and
#: the kernels' names reach the text on a TPU only (on the CPU ``GRPO`` and
#: these programs take the XLA paths).
LOWERED_BEFORE = {
    "dense/learn": "7362b82078421db5", "dense/prefill": "a7b4cd3eefd44ed0",
    "dense/decode": "31f583c02d1bd975", "hybrid/learn": "71ddee0df427142c",
    "hybrid/prefill": "706650d3357b7a97", "hybrid/decode": "22f626907db74d67",
    "mla/learn": "76a183e39c02d915", "mla/prefill": "94359864533a67a6",
    "mla/decode": "0030db76f1caa9c2",
}
DECODE_WITHOUT_SCOPES = {
    "dense/decode": "6a4be7926152dfe0", "hybrid/decode": "e4f107c2e258c788",
    "mla/decode": "9ca3bf96cc6f62c2",
}
STACKS = {
    "dense": lambda: M.GPTConfig(
        vocab_size=97, n_layer=3, n_head=4, n_kv_head=2, d_model=32, d_ff=64,
        max_seq_len=64, qkv_bias=True, dtype=jnp.float32),
    "hybrid": lambda: M.GPTConfig(
        vocab_size=97, n_layer=8, n_head=4, n_kv_head=1, d_model=32, d_ff=64,
        max_seq_len=128, rope=False, attn_layer_period=4, attn_layer_offset=1,
        mamba_d_state=8, mamba_dt_rank=4, dtype=jnp.float32),
    "mla": lambda: preset("tiny-mla-moe", dtype=jnp.float32, remat=False,
                          use_flash_attention=False),
}


def lowered(cfg, what):
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.eval_shape(lambda: M.init_lora(
        jax.random.PRNGKey(1), cfg, 2,
        ("wq", "wkv_b") if cfg.is_mla else ("wq", "wv")))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    kw = {"return_aux": True} if cfg.is_dropless else {}
    if what == "learn":
        return jax.jit(lambda p, lo, t, m: M.token_logprobs(
            cfg, p, t, attention_mask=m, lora=lo, **kw)).lower(
                shapes, lora, i32(2, 16), i32(2, 16))
    if what == "prefill":
        return jax.jit(lambda p, lo, t, m, c: M.forward(
            cfg, p, t, attention_mask=m, cache=c, lora=lo)).lower(
                shapes, lora, i32(2, 16), i32(2, 16),
                jax.eval_shape(lambda: M.init_caches(cfg, 2, 32)))
    slots = {"slots": 3, "snapshots": 3} if cfg.is_hybrid else {}
    pool = jax.eval_shape(lambda: M.init_paged_cache(cfg, 9, 8, **slots))
    return jax.jit(lambda p, lo, t, pos, c, tab, sm: M.forward_paged(
        cfg, p, t, pos, pos, c, tab, sm, lora=lo, **kw)).lower(
            shapes, lora, i32(3, 1), i32(3), pool, i32(3, 4), i32(3, 32))


@pytest.mark.parametrize("program", sorted(LOWERED_BEFORE))
def test_stacks_without_cca_lower_to_the_text_they_lowered_to(program):
    stack, what = program.split("/")
    text = lowered(STACKS[stack](), what).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == LOWERED_BEFORE[program]


# --------------------------------------------------------------------------- #
# ... and through a window in the kernels, a period scan and a router fed
# from outside the FFN (PR 38)
# --------------------------------------------------------------------------- #

#: sha256 (first 16 digits) of more programs' lowered text at the commit
#: before a sliding window reached the flash kernels, the cached and the paged
#: attention loop, ``_run_layers`` got its period scan and ``_block_ffn`` its
#: logits from outside (e3d311a, PR 37), on this installation: with no window
#: configured none of them may change. ``*/learn_flash`` are GRADIENTS through
#: ``token_logprobs`` with the flash kernels on — in interpret mode here, so
#: the text holds the three kernels' bodies, index maps and grids operation by
#: operation (q, k 24 wide and v 16 under latent attention) —, ``mesh/`` the
#: same under ``flash_shard_axes`` on an fsdp 4 x tp 2 mesh, ``dense/step``
#: the one-token decode through ``KVCache`` (``chunked_cached_attention``),
#: ``dense/verify`` the multi-token paged forward, ``cca/*`` the CCA stack's
#: three programs, ``flash_plan`` the tiles chosen at T 64-16384 for heads of
#: 128 / 128 and 192 / 128.
LOWERED_AT_PR37 = {
    "cca/learn": "2ea31a4c80162d01", "cca/prefill": "f6edafb875432fa6",
    "cca/decode": "f63083a8726e3a21", "dense/learn_flash": "3933b328fe2d072e",
    "mla/learn_flash": "de2b0a6bbfe253e4", "cca/learn_flash": "b85af7493ee50f96",
    "dense/step": "f54fdbc3c50c3d61", "dense/verify": "5eeb5c0d0de6e3fe",
    "mesh/learn_flash": "e27b6bc262a2eb43", "flash_plan": "b32bf4203f00e13b",
}
STACKS["cca"] = lambda: preset("tiny-cca-moe", dtype=jnp.float32, remat=False,
                               use_flash_attention=False)


def lowered_more(cfg, what):
    """``lowered`` for the programs above."""
    targets = (("wq", "wkv_b") if cfg.is_mla else
               ("wq", "wv1", "wv2") if cfg.is_cca else ("wq", "wv"))
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.eval_shape(lambda: M.init_lora(jax.random.PRNGKey(1), cfg, 2,
                                              targets))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    kw = {"return_aux": True} if cfg.is_dropless else {}
    if what == "learn":
        return jax.jit(lambda p, lo, t, m: M.token_logprobs(
            cfg, p, t, attention_mask=m, lora=lo, **kw)).lower(
                shapes, lora, i32(2, 16), i32(2, 16))
    if what == "learn_flash":
        def loss(lo, p, t, m):
            out = M.token_logprobs(cfg, p, t, attention_mask=m, lora=lo,
                                   flash=True, **kw)
            return (out[0] if kw else out).sum()

        return jax.jit(jax.grad(loss)).lower(lora, shapes, i32(2, 40),
                                             i32(2, 40))
    if what in ("prefill", "step"):
        T = 16 if what == "prefill" else 1
        return jax.jit(lambda p, lo, t, m, c: M.forward(
            cfg, p, t, attention_mask=m, cache=c, lora=lo)).lower(
                shapes, lora, i32(2, T), i32(2, T),
                jax.eval_shape(lambda: M.init_caches(cfg, 2, 32)))
    slots = {"slots": 3, "snapshots": 3} if cfg.state_kind is not None else {}
    pool = jax.eval_shape(lambda: M.init_paged_cache(cfg, 9, 8, **slots))
    shape = (3, 3) if what == "verify" else (3,)
    return jax.jit(lambda p, lo, t, pos, c, tab, sm: M.forward_paged(
        cfg, p, t, pos, pos, c, tab, sm, lora=lo, **kw)).lower(
            shapes, lora, i32(3, shape[-1] if what == "verify" else 1),
            i32(*shape), pool, i32(3, 4), i32(3, 32))


def mesh_learn_flash_text():
    from agilerl_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp=1, fsdp=4, tp=2)
    cfg = dataclasses.replace(STACKS["dense"](), use_flash_attention=True,
                              flash_shard_axes=(("dp", "fsdp"), "tp"))
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    with mesh:
        return jax.jit(jax.grad(lambda p, t, m: M.token_logprobs(
            cfg, p, t, attention_mask=m).mean())).lower(
                shapes, i32(8, 32), i32(8, 32)).as_text()


def flash_plans_text():
    from agilerl_tpu.ops.flash_attention_vjp import flash_plan

    return repr([
        tuple(flash_plan(T, d, dv, jnp.bfloat16, kind,
                         vmem_capacity=128 * 2 ** 20))
        for T in (64, 1024, 1152, 2048, 4096, 8192, 16384)
        for d, dv in ((128, 128), (192, 128))
        for kind in ("fwd", "dq", "dkv")])


@pytest.mark.parametrize("program", sorted(LOWERED_AT_PR37))
def test_programs_without_a_window_lower_to_the_text_they_lowered_to(program):
    if program == "flash_plan":
        text = flash_plans_text()
    elif program == "mesh/learn_flash":
        text = mesh_learn_flash_text()
    else:
        stack, what = program.split("/")
        text = lowered_more(STACKS[stack](), what).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == LOWERED_AT_PR37[program]
