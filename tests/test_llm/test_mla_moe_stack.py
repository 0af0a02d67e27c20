"""Latent attention (llm/mla.py) and the dropless expert layer (llm/moe.py)
through the stack, the serving tier and GRPO, at the preset ``tiny-mla-moe``
in float32, against ``perfbench/reference/deepseek_v3_f32.py``: routing,
``forward`` logits, absorbed against expanded attention, prefill and paged
decode through the latent cache with prefix hits, the layout of the pool,
the layer runs, GRPO with adapters on ``wq`` / ``wkv_b``, and what refuses."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO, make_update_fn
from agilerl_tpu.llm import mla, moe
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.llm.serving import ContinuousGenerator
from perfbench.reference import deepseek_v3_f32 as ref

G = importlib.import_module("agilerl_tpu.llm.generate")

CFG = preset("tiny-mla-moe", dtype=jnp.float32, remat=False,
             use_flash_attention=False)
REF = dict(n_head=CFG.n_head, nope=CFG.qk_nope_dim, rope=CFG.qk_rope_dim,
           theta=CFG.rope_theta, eps=CFG.rms_eps, top_k=CFG.expert_top_k,
           scale=CFG.routed_scale)


@pytest.fixture(scope="module")
def params():
    p = M.init_params(jax.random.PRNGKey(0), CFG)
    assert [jax.tree_util.tree_leaves(run)[0].shape[0]
            for run in p["runs"]] == [1, 2]  # one tree a run
    assert float(jnp.abs(p["runs"][1]["router_bias"]).min()) > 0  # drawn
    assert "router" not in p["runs"][0] and "wv" not in p["runs"][1]
    return p


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, CFG.vocab_size, size=n).astype(np.int32)
            for n in lengths]


# --------------------------------------------------------------------------- #
# Routing and the dropless dispatch
# --------------------------------------------------------------------------- #


def test_the_bias_moves_the_choice_and_not_the_weight():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    w = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    kw = dict(top_k=2, score="sigmoid", norm_topk=False)
    choice, weights = moe.route(moe.linear_logits(x, w), None, **kw)
    bias = jnp.zeros((8,)).at[5].set(10.0)  # expert 5 wins every choice
    choice_b, weights_b = moe.route(moe.linear_logits(x, w), bias, **kw)
    assert (choice_b[:, 0] == 5).all() and not (choice[:, 0] == 5).all()
    s = jax.nn.sigmoid(x @ w)
    np.testing.assert_allclose(  # the weight is the score WITHOUT the bias
        weights_b, jnp.take_along_axis(s, choice_b, axis=-1), rtol=1e-6)
    assert float(weights_b.max()) < 1.0


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_renormalisation_and_scale(score):
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 8))
    _, plain = moe.route(moe.linear_logits(x, w), top_k=3, score=score, norm_topk=False)
    _, normed = moe.route(moe.linear_logits(x, w), top_k=3, score=score, norm_topk=True,
                          scale=2.5)
    np.testing.assert_allclose(normed.sum(-1), 2.5, rtol=1e-5)
    np.testing.assert_allclose(
        normed, 2.5 * plain / plain.sum(-1, keepdims=True), rtol=1e-5)


def test_the_learn_step_builds_no_weight_gradient_of_the_experts(params):
    """The base is frozen: every grouped matmul of the update, forward and
    backward, keeps the experts as its GROUPED operand (a weight gradient's
    would contract the ragged rows instead)."""
    import optax

    tx = optax.sgd(1e-2)
    lora = M.init_lora(jax.random.PRNGKey(1), CFG, 2, ("wq", "wkv_b"))
    update = make_update_fn(CFG, tx, 2.0, use_flash=False)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "mask": jnp.ones((2, 16), jnp.int32),
             "loss_mask": jnp.ones((2, 15)), "old_lp": jnp.zeros((2, 15)),
             "ref_lp": jnp.zeros((2, 15)), "advantage": jnp.ones((2,))}
    jaxpr = jax.make_jaxpr(update)(params, lora, tx.init(lora), batch, 0.2,
                                   0.04).jaxpr
    dots = grouped_matmuls(jaxpr)
    # 3 forward + 3 backward w.r.t. the rows, in the expert run's one body
    assert len(dots) == 6, len(dots)
    assert all(d.rhs_group_dimensions == (0,) for d in dots), dots


def grouped_matmuls(jaxpr):
    from jax._src import core

    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "ragged_dot_general":
            out.append(e.params["ragged_dot_dimension_numbers"])
        for j in core.jaxprs_in_params(e.params):
            out += grouped_matmuls(j)
    return out


# --------------------------------------------------------------------------- #
# The stack
# --------------------------------------------------------------------------- #


def test_layer_runs_of_a_dense_then_expert_stack():
    assert CFG.layer_runs() == [("attn", 0, 1), ("attn", 1, 2)]
    assert [CFG.is_moe_layer(i) for i in range(3)] == [False, True, True]
    assert CFG.stores_runs and CFG.is_mla and CFG.is_dropless
    big = dataclasses.replace(CFG, n_layer=7)
    assert big.layer_runs() == [("attn", 0, 1), ("attn", 1, 6)]
    assert big.n_moe_layers == 6
    with pytest.raises(ValueError, match="capacity dispatch"):
        dataclasses.replace(CFG, capacity_factor=1.25)


def scan_calls(jaxpr) -> int:
    from jax._src import core

    n = 0
    for e in jaxpr.eqns:
        n += e.primitive.name == "scan"
        n += sum(scan_calls(j) for j in core.jaxprs_in_params(e.params))
    return n


def test_the_learn_step_holds_one_expert_body_not_one_a_layer(params):
    toks = jnp.ones((2, 16), jnp.int32)
    trace = lambda cfg: jax.make_jaxpr(  # noqa: E731
        lambda p: M.token_logprobs(cfg, p, toks, chunk_size=512))(params).jaxpr
    # the expert run's scan and the chunked head's
    assert scan_calls(trace(CFG)) == 2
    assert scan_calls(trace(dataclasses.replace(CFG, scan_layers=False))) == 1


@pytest.mark.parametrize("path", ["xla", "flash", "unrolled"])
def test_forward_logits_match_the_reference_with_left_padding(params, path):
    cfg = dataclasses.replace(CFG, use_flash_attention=path == "flash",
                              scan_layers=path != "unrolled")
    seqs = prompts(15, 11)
    toks, mask = G.left_pad(seqs, 0, 20)
    logits, _ = M.apply(cfg, params, jnp.asarray(toks),
                        attention_mask=jnp.asarray(mask))
    for row, seq in enumerate(seqs):
        want = ref.logits(params, seq, **REF)
        got = np.asarray(logits[row, 20 - len(seq):])
        assert np.abs(got - want).max() < ref.LOGIT_TOL


def test_a_bfloat16_run_of_the_reference_fails_the_logit_tolerance(params):
    seq = prompts(48, seed=5)[0]
    want = ref.logits(params, seq, **REF)
    lossy = ref.logits(params, seq, dtype=jnp.bfloat16, **REF)
    assert np.abs(lossy - want).max() > ref.LOGIT_TOL


@pytest.mark.parametrize("adapter", [False, True])
def test_absorbed_attention_equals_expanded(params, adapter):
    """One layer's mixer on the same positions: the whole sequence expanded,
    and position by position absorbed over the latent slab."""
    blk = jax.tree_util.tree_map(lambda a: a[0], params["runs"][1])
    lora = None
    if adapter:
        lora = M.init_lora(jax.random.PRNGKey(3), CFG, 2, ("wq", "wkv_b"))
        lora = jax.tree_util.tree_map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  a.shape), lora)["blocks"]["1"]
    B, T = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, CFG.d_model))
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    mask = jnp.ones((B, T), jnp.int32)
    q_nope, q_rope, lat = mla.project(CFG, blk, x, pos, lora, 2.0)
    assert lat.shape == (B, T, mla.latent_width(CFG))
    want = mla.attend_expanded(CFG, blk, q_nope, q_rope, lat, mask, lora,
                               2.0, False)
    slab = jnp.zeros((B, 16, lat.shape[-1])).at[:, :T].set(lat)
    valid = jnp.zeros((B, 16), jnp.int32).at[:, :T].set(1)
    whole = mla.attend_absorbed(CFG, blk, q_nope, q_rope, slab, valid, 0,
                                lora, 2.0)
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-6)
    for t in (0, 5, T - 1):  # the one-token step, per-row start
        one = mla.attend_absorbed(
            CFG, blk, q_nope[:, t:t + 1], q_rope[:, t:t + 1], slab, valid,
            jnp.full((B,), t), lora, 2.0)
        np.testing.assert_allclose(one[:, 0], want[:, t], rtol=1e-4,
                                   atol=1e-6)


def test_dense_cached_prefill_and_decode_match_the_full_forward(params):
    seqs = prompts(20, 14)
    toks, mask = G.left_pad(seqs, 0, 20)
    toks, mask = jnp.asarray(toks), jnp.asarray(mask)
    full, _ = M.apply(CFG, params, toks, attention_mask=mask)
    pos = jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)
    cache = M.init_caches(CFG, 2, 32)
    assert cache.v is None and cache.k.shape == (3, 2, 32, 40)
    P = 12
    lg, cache = M.apply(CFG, params, toks[:, :P], attention_mask=mask[:, :P],
                        positions=pos[:, :P], cache=cache)
    real = np.asarray(mask[:, :P], bool)
    assert np.abs(np.asarray(lg - full[:, :P]))[real].max() < 1e-5
    for t in range(P, 20):
        lg, cache = M.apply(CFG, params, toks[:, t:t + 1],
                            attention_mask=mask[:, t:t + 1],
                            positions=pos[:, t:t + 1], cache=cache)
        assert float(jnp.abs(lg[:, 0] - full[:, t]).max()) < 1e-5


# --------------------------------------------------------------------------- #
# The serving tier over the latent pool
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
def test_paged_decode_through_the_latent_cache_matches_the_reference(
        params, lengths):
    """Sampled rollouts, each prompt three times: the first is prefilled,
    the repeats are admitted by prefix hits (the last latent block copied).
    The tier's own log-probabilities of its sampled tokens against the
    reference's full expanded forward, hit or miss."""
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


def test_the_pool_holds_one_latent_array_and_counts_the_experts_hit(params):
    gen = generator()
    a, = prompts(13)
    gen.generate([a, a], jax.random.PRNGKey(1), params)
    pool = gen._pool
    width = CFG.kv_lora_rank + CFG.qk_rope_dim
    assert pool.v is None
    assert pool.k.shape == (3, gen.n_blocks, 8, width)
    # bytes a block: layers x block x latent width x itemsize, against what
    # the expanded keys and values of every head would take
    assert M.paged_block_bytes(pool) == 3 * 8 * width * 4
    expanded = 3 * 8 * CFG.n_head * (
        CFG.qk_nope_dim + CFG.qk_rope_dim + CFG.v_head_dim) * 4
    assert expanded == 4 * M.paged_block_bytes(pool)
    dump = gen.metrics.dump()
    assert dump["gauges"]["serving/pool_block_bytes"] == \
        M.paged_block_bytes(pool)
    hit = dump["counters"]["serving/moe_experts_hit_total"]
    slots = dump["counters"]["serving/moe_expert_slots_total"]
    # 3 chunks of 4 steps x 2 expert layers x 8 experts; 3 rows x 2 choices
    # touch between 2 and 6 experts a layer a step
    assert slots == 3 * 4 * 2 * 8
    assert 3 * 4 * 2 * 2 <= hit <= 3 * 4 * 2 * 6


# --------------------------------------------------------------------------- #
# GRPO
# --------------------------------------------------------------------------- #


def make_agent(params, **kw):
    args = dict(config=CFG, base_params=params, pad_token_id=0,
                eos_token_id=1, group_size=2, batch_size=4,
                max_output_tokens=8, lora_rank=2,
                lora_targets=("wq", "wkv_b"), continuous_decode=True,
                capture_logprobs=True, min_output_tokens=8, seed=0)
    args.update(kw)
    return GRPO(**args)


def test_grpo_trains_with_adapters_on_wq_and_wkv_b(params):
    observability.get_registry().gauge("moe/load_max_over_mean").set(0.0)
    agent = make_agent(params)
    assert sorted(agent.actor.params["blocks"]["0"]) == ["wkv_b", "wq"]
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
    rewards = np.asarray([[1.0, -1.0]], np.float32)
    loss, kl = agent.learn((ids, action, rewards))
    assert np.isfinite(loss) and np.isfinite(kl)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        agent.actor.params, before)
    for layer in ("0", "1", "2"):
        assert moved["blocks"][layer]["wq"]["B"] > 0
        assert moved["blocks"][layer]["wkv_b"]["B"] > 0
    load = observability.get_registry().gauge("moe/load_max_over_mean").value
    assert 1.0 <= load <= CFG.n_experts


def test_what_is_not_carried_over_refuses_by_name(params):
    with pytest.raises(ValueError, match="wkv_b"):
        M.init_lora(jax.random.PRNGKey(0), CFG, 2, ("wq", "wv"))
    with pytest.raises(ValueError, match="FFN projections"):
        M.init_lora(jax.random.PRNGKey(0), CFG, 2, ("wq", "w_up"))
    with pytest.raises(ValueError, match="latent attention"):
        dataclasses.replace(CFG, rope=False)
