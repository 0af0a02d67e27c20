"""Single-block layers named by a pattern (Mamba-2 mixers, latent
squared-ReLU experts of which the chip holds a share, attention without
positions) through the stack, the serving tier and GRPO, at a tiny
NemotronH-class configuration in float32 against
``perfbench/reference/nemotron_h_f32.py``: the chunked SSD against the
sequential recurrence, the share of the experts, the experts' activations,
``forward`` logits, prefill and decode through both caches (a prefix hit's
state restore among them), GRPO's adapters, and what refuses."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm import moe, nemotron_h, ssm2
from agilerl_tpu.llm.serving import ContinuousGenerator
from agilerl_tpu.ops.ssd import ssd, ssd_reference
from perfbench.reference import nemotron_h_f32 as ref
from tests.test_llm.test_moe_dispatch import argsort_experts

G = importlib.import_module("agilerl_tpu.llm.generate")

PATTERN = "MEMEM*EME"
CFG = M.GPTConfig(
    vocab_size=128, n_layer=9, n_head=4, n_kv_head=2, head_size=16,
    d_model=64, max_seq_len=128, rope=False, tie_embeddings=False,
    rms_eps=1e-5, layer_pattern=PATTERN, mamba_heads=8, mamba_head_dim=16,
    mamba_groups=2, mamba_d_state=16, mamba_d_conv=4, mamba_chunk=8,
    n_experts=16, expert_top_k=4, capacity_factor=None, d_ff_expert=24,
    d_ff_shared=48, moe_latent=32, router_score="sigmoid", router_bias=True,
    norm_topk=True, routed_scale=5.0, expert_act="relu2",
    experts_held=(4, 8), dtype=jnp.float32)
REF = dict(pattern=PATTERN, n_head=4, n_kv=2, eps=CFG.rms_eps,
           mamba=(8, 16, 2, 16), top_k=4, routed_scale=5.0, first=4)
TARGETS = ("wq", "wv", "in_proj", "out_proj")
#: jitted, so that a test compiles a shape once instead of dispatching
#: every operation
apply = jax.jit(lambda p, t, **kw: M.apply(CFG, p, t, **kw))
mixer = jax.jit(lambda *a: ssm2.mixer(CFG, *a))


@pytest.fixture(scope="module")
def params():
    p = M.init_params(jax.random.PRNGKey(0), CFG)
    assert [k for k, _, _ in CFG.layer_runs()] == [
        "mamba2", "moe", "mamba2", "moe", "mamba2", "attn", "moe", "mamba2",
        "moe"]
    assert p["runs"][1]["w_up"].shape == (1, 8, 32, 24)  # 8 of 16 held
    assert p["runs"][1]["router"].shape == (1, 64, 16)  # routes over all
    assert "w_gate" not in p["runs"][1] and "lm_head" in p
    return p


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, CFG.vocab_size, size=n).astype(np.int32)
            for n in lengths]


# --------------------------------------------------------------------------- #
# The chunked SSD and the Mamba-2 mixer
# --------------------------------------------------------------------------- #


def ssd_inputs(B=2, T=27, H=8, P=4, G=2, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (B, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))),
            -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0, maxval=2.7)),
            jax.random.normal(ks[3], (B, T, G, N)),
            jax.random.normal(ks[4], (B, T, G, N)),
            jax.random.normal(ks[5], (H,)))


@pytest.mark.parametrize("chunk", [8, 27])
def test_the_chunked_ssd_is_the_recurrence_and_leaves_its_state(chunk):
    """Across chunk boundaries and a ragged tail, from a start state, with
    left pads that decay nothing and add nothing."""
    x, dt, a, bm, cm, d = ssd_inputs()
    mask = jnp.ones((2, 27)).at[1, :5].set(0)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (2, 8, 4, 16))
    chunked = jax.jit(lambda *z: ssd(*z, chunk=chunk))
    y, h = chunked(x, dt, a, bm, cm, d, mask, h0)
    y_ref, h_ref = jax.jit(ssd_reference)(x, dt, a, bm, cm, d, mask, h0)
    np.testing.assert_allclose(y, y_ref, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h, h_ref, atol=2e-4, rtol=2e-4)
    # a row's left pads leave its start state as it was
    _, h_pad = chunked(x[:, :5], dt[:, :5], a, bm[:, :5], cm[:, :5], d,
                       mask[:, :5], h0)
    np.testing.assert_array_equal(h_pad[1], h0[1])


def test_the_chunked_ssd_differentiates_like_the_recurrence():
    x, dt, a, bm, cm, d = ssd_inputs(T=19)
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def loss(f):
        return lambda *args: (f(*args)[0] * probe).sum() + f(*args)[1].sum()

    got = jax.jit(jax.grad(loss(lambda *z: ssd(*z, chunk=8)),
                           argnums=range(6)))(x, dt, a, bm, cm, d)
    want = jax.jit(jax.grad(loss(ssd_reference), argnums=range(6)))(
        x, dt, a, bm, cm, d)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-3, rtol=2e-3)


def test_the_mixers_three_shapes_agree(params):
    """The whole sequence; a prefill (its state and the one before its last
    token); one-token steps from it."""
    blk = M.run_layer(params["runs"][0], 0)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 21, CFG.d_model))
    mask = jnp.ones((2, 21), jnp.int32)
    whole, _, _ = mixer(blk, u, mask)
    zero = tuple(s[0] for s in ssm2.init_state(CFG, 1, 2))
    head, state, prev = mixer(blk, u[:, :13], mask[:, :13], zero)
    np.testing.assert_allclose(head, whole[:, :13], atol=1e-5)
    again, _, _ = mixer(blk, u[:, 12:13], mask[:, 12:13], prev)
    np.testing.assert_allclose(again[:, 0], whole[:, 12], atol=1e-5)
    for t in range(13, 21):
        out, state, _ = mixer(blk, u[:, t:t + 1], mask[:, t:t + 1], state)
        np.testing.assert_allclose(out[:, 0], whole[:, t], atol=1e-5)
    # a masked step (a free slot) leaves window and state as they are
    _, kept, _ = mixer(blk, u[:, :1], jnp.zeros((2, 1), jnp.int32), state)
    jax.tree_util.tree_map(np.testing.assert_array_equal, kept, state)


# --------------------------------------------------------------------------- #
# The experts: activations, the latent and the chip's share
# --------------------------------------------------------------------------- #


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """4 shares of 4 experts: their outputs, with the shared expert counted
    once, add up to the layer that holds all 16; each share's draw is the
    slice of the uncut layer's."""
    uncut = M.init_params(jax.random.PRNGKey(0), dataclasses.replace(
        CFG, experts_held=None))
    blk = M.run_layer(uncut["runs"][1], 0)
    np.testing.assert_array_equal(
        blk["w_up"][4:12], M.run_layer(params["runs"][1], 0)["w_up"])
    x = jax.random.normal(jax.random.PRNGKey(2), (40, CFG.d_model))
    kw = dict(top_k=4, score="sigmoid", norm_topk=True, scale=5.0,
              act="relu2")
    whole, load = moe.latent_ffn(x, blk, first=0, **kw)
    shared = moe.shared_expert(x, None, blk["ws_up"], blk["ws_down"], "relu2")
    parts, held = [], []
    for first in (0, 4, 8, 12):
        share = {**blk, "w_up": blk["w_up"][first:first + 4],
                 "w_down": blk["w_down"][first:first + 4]}
        out, _ = moe.latent_ffn(x, share, first=first, **kw)
        parts.append(out - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-4)
    assert int(load.sum()) == 40 * 4


def test_only_held_pairs_reach_the_grouped_matmuls(params, monkeypatch):
    """The group sizes handed to ``ragged_dot`` sum to the pairs that fell
    on the held experts, not to every routed pair."""
    sizes = []
    real = jax.lax.ragged_dot

    def spy(lhs, rhs, group_sizes, **kw):
        sizes.append(np.asarray(group_sizes))
        return real(lhs, rhs, group_sizes, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", spy)
    blk = M.run_layer(params["runs"][1], 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (50, CFG.d_model))
    _, load = moe.latent_ffn(x, blk, top_k=4, score="sigmoid",
                             norm_topk=True, scale=5.0, act="relu2", first=4)
    held = int(np.asarray(load)[4:12].sum())
    assert len(sizes) == 2 and all(s.shape == (8,) for s in sizes)
    assert all(int(s.sum()) == held for s in sizes)
    assert 0 < held < 50 * 4
    out, aux = nemotron_h._experts(CFG, blk, x)
    assert float(aux[2]) == held


def test_rows_after_the_last_group_are_never_read(params, monkeypatch):
    """XLA:TPU's grouped matmul leaves the rows after the last group
    unwritten (on the chip: NaN log-probabilities in every decode step, PR
    40's first chip run), and so are those rows of its lhs cotangent (the
    backward is a grouped matmul too); a stand-in that fills them with NaN
    both ways changes nothing of the layer's output nor of the gradient
    that reaches its input (and through it every adapter upstream)."""
    real = jax.lax.ragged_dot

    def nan_after_groups(out, group_sizes):
        rows = jnp.arange(out.shape[0])[:, None] < group_sizes.sum()
        return jnp.where(rows, out, jnp.nan)

    @jax.custom_vjp
    def unwritten(lhs, rhs, group_sizes):
        return nan_after_groups(real(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return unwritten(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return nan_after_groups(d_lhs, group_sizes), d_rhs, None

    unwritten.defvjp(fwd, bwd)
    blk = M.run_layer(params["runs"][1], 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (30, CFG.d_model))
    r = jax.random.normal(jax.random.PRNGKey(4), (30, CFG.d_model))
    kw = dict(top_k=4, score="sigmoid", norm_topk=True, scale=5.0,
              act="relu2", first=4)
    loss = lambda x: (moe.latent_ffn(x, blk, **kw)[0] * r).sum()  # noqa: E731
    want, _ = moe.latent_ffn(x, blk, **kw)
    want_grad = jax.grad(loss)(x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda lhs, rhs, group_sizes, **_: unwritten(
                            lhs, rhs, group_sizes))
    got, _ = moe.latent_ffn(x, blk, **kw)
    np.testing.assert_array_equal(got, want)
    got_grad = jax.grad(loss)(x)
    assert np.isfinite(np.asarray(got_grad)).all()
    np.testing.assert_array_equal(got_grad, want_grad)
    # the dispatch's own backward gathers each token's cotangent from the
    # sorted rows by the inverse permutation: the rows after the groups are
    # NaN in the stand-in's lhs cotangent, and no held pair reads them.
    # Against the argsort formulation (its scatter-add transposes) with the
    # real grouped matmul
    lat = x @ blk["lat_down"]
    choice, weights = moe.route(
        moe.linear_logits(x, blk["router"]), blk["router_bias"], top_k=4,
        score="sigmoid", norm_topk=True, scale=5.0)
    args = (None, blk["w_up"], blk["w_down"])
    probe = r[:, :lat.shape[1]]
    grads = lambda f: jax.grad(  # noqa: E731
        lambda a, w: (f(a, choice, w, *args, act="relu2", first=4)
                      * probe).sum(), argnums=(0, 1))(lat, weights)
    got_grads = grads(moe.dropless_experts)
    monkeypatch.setattr(jax.lax, "ragged_dot", real)
    for g, w in zip(got_grads, grads(argsort_experts)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# The stack against the reference
# --------------------------------------------------------------------------- #


def test_forward_logits_match_the_reference_with_left_padding(params):
    seqs = prompts(27, 19)
    toks, mask = G.left_pad(seqs, 0, 27)
    got = np.asarray(apply(params, jnp.asarray(toks),
                           attention_mask=jnp.asarray(mask))[0])
    for row, s in enumerate(seqs):
        want = ref.logits(params, s, **REF)
        assert np.abs(got[row, 27 - len(s):] - want).max() < ref.LOGIT_TOL


def test_cached_prefill_and_decode_match_the_full_forward(params):
    seqs = prompts(20, 14)
    toks, mask = G.left_pad(seqs, 0, 20)
    toks, mask = jnp.asarray(toks), jnp.asarray(mask)
    full, _ = apply(params, toks, attention_mask=mask)
    cache = M.init_caches(CFG, 2, 32)
    assert cache.k.shape == (1, 2, 32, 2, 16)  # the one attention layer
    assert len(cache.state) == 4  # a run (of one) a Mamba-2 layer
    conv, state = cache.state[0]
    assert conv.shape == (1, 2, 3, 8 * 16 + 2 * 2 * 16)
    assert state.shape == (1, 2, 8, 16, 16) and state.dtype == jnp.float32
    P = 12
    lg, cache = apply(params, toks[:, :P], attention_mask=mask[:, :P],
                      cache=cache)
    assert cache.prev_state is not None
    real = np.asarray(mask[:, :P], bool)
    assert np.abs(np.asarray(lg - full[:, :P]))[real].max() < 1e-5
    for t in range(P, 20):
        lg, cache = apply(params, toks[:, t:t + 1],
                          attention_mask=mask[:, t:t + 1], cache=cache)
        assert float(jnp.abs(lg[:, 0] - full[:, t]).max()) < 1e-5


def generator(**kw):
    args = dict(max_new_tokens=12, prompt_buckets=(16, 32), slots=3,
                block_size=8, decode_chunk=4, capture_logprobs=True,
                metrics=observability.MetricsRegistry())
    args.update(kw)
    return ContinuousGenerator(CFG, **args)


def test_paged_decode_with_prefix_hits_matches_the_reference(params):
    """Sampled rollouts, each prompt several times: the repeats are admitted
    by prefix hits (the last block copied AND the Mamba-2 states restored
    from their snapshot). The tier's own log-probabilities of its sampled
    tokens against the reference's full forward, hit or miss."""
    a, b, c = prompts(13, 17, 8)  # within a block, across one, one whole
    seqs = [a, a, b, a, c, b]
    gen = generator()
    toks, masks, info = gen.generate(seqs, jax.random.PRNGKey(3), params)
    assert masks.all()
    assert info["prefix_hit_rows"] == [False, True, False, True, False, True]
    for i, s in enumerate(seqs):
        full = np.concatenate([s, toks[i]])
        want, _ = ref.token_logprobs(
            params, full, np.arange(len(s) - 1, len(full) - 1), **REF)
        assert np.abs(want - info["logprobs"][i]).max() < 5e-5, i
    dump = gen.metrics.dump()
    pool = gen._pool
    assert dump["gauges"]["serving/ssm_state_bytes"] == \
        M.state_cache_bytes(pool) == 4 * (2 * gen.slots + 1) * 4 * (
            3 * 192 + 8 * 16 * 16)
    assert dump["counters"]["serving/state_snapshot_restores_total"] == 3
    # the hits are over the 8 held experts of each of the 4 expert layers
    assert CFG.n_moe_layers == 4
    assert dump["counters"]["serving/moe_expert_slots_total"] % (4 * 8 * 4) == 0


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


# --------------------------------------------------------------------------- #
# GRPO
# --------------------------------------------------------------------------- #


def make_agent(params, **kw):
    args = dict(config=CFG, base_params=params, pad_token_id=0,
                eos_token_id=1, group_size=2, batch_size=4,
                max_output_tokens=8, lora_rank=2, lora_targets=TARGETS,
                continuous_decode=True, capture_logprobs=True,
                min_output_tokens=8, seed=0)
    args.update(kw)
    return GRPO(**args)


def test_grpo_trains_adapters_on_attention_and_mamba2_projections(params):
    agent = make_agent(params)
    blocks = agent.actor.params["blocks"]
    assert sorted(blocks["0"]) == ["in_proj", "out_proj"]
    assert sorted(blocks["5"]) == ["wq", "wv"] and blocks["1"] == {}
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, CFG.vocab_size, size=(1, 12)).astype(np.int32)
    comp, cmask = agent.get_action(
        {"input_ids": prompt, "attention_mask": np.ones((1, 12), np.int32)})
    info = agent.last_generation_info
    assert "slots" in info and info["prefix_hit_rows"] == [False, True]
    ids = np.concatenate([np.repeat(prompt, 2, 0), comp], axis=1)
    action = np.concatenate(
        [np.zeros((2, 11), np.int32), cmask.astype(np.int32)], axis=1)
    lp = np.asarray(agent.behavior_logprobs(ids, action))
    # a row that sampled the pad id is masked there by the learn side
    rows = (comp != 0).all(axis=1)
    assert rows.any()
    np.testing.assert_allclose(lp[rows, 11:], info["logprobs"][rows],
                               atol=2e-5)
    before = jax.tree_util.tree_map(np.asarray, agent.actor.params)
    # the update counts the pairs that fell on held experts in its own
    # forward: what a forward of the batch from the same adapters counts
    _, _, aux = jax.jit(lambda p, t, m, lo: M.forward(
        CFG, p, t, attention_mask=m, lora=lo, return_aux=True))(
            params, jnp.asarray(ids), jnp.asarray(ids != 0, jnp.int32),
            agent.actor.params)
    counted = observability.get_registry().counter("moe/held_pairs")
    held = counted.value
    loss, kl = agent.learn((ids, action, np.asarray([[1.0, -1.0]])))
    assert np.isfinite(loss) and np.isfinite(kl)
    assert counted.value - held == float(aux[2]) * agent.update_epochs > 0
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        agent.actor.params, before)
    for i, layer in moved["blocks"].items():
        for target, ab in layer.items():
            assert ab["B"] > 0, (i, target)


def test_the_adapted_log_probabilities_match_the_reference(params):
    lora = M.init_lora(jax.random.PRNGKey(1), CFG, 2, TARGETS)
    lora = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(7), a.shape),
        lora)
    s, = prompts(22)
    got = jax.jit(lambda p, t, lo: M.token_logprobs(CFG, p, t, lora=lo))(
        params, jnp.asarray(s)[None], lora)[0]
    want, _ = ref.token_logprobs(params, s, np.arange(21), lora=lora, **REF)
    assert np.abs(np.asarray(got) - want).max() < 5e-5


# --------------------------------------------------------------------------- #
# What refuses
# --------------------------------------------------------------------------- #


def test_what_would_lose_the_mamba2_state_refuses_by_name(params):
    with pytest.raises(NotImplementedError, match="Mamba-2"):
        M.forward_paged(CFG, params, jnp.zeros((3, 2), jnp.int32),
                        jnp.zeros((3, 2), jnp.int32),
                        jnp.zeros((3, 2), jnp.int32), gen_pool(),
                        jnp.zeros((3, 4), jnp.int32),
                        jnp.ones((3, 32), jnp.int32))


def gen_pool():
    return M.init_paged_cache(CFG, 9, 8, slots=3, snapshots=3)


def test_configurations_the_layers_do_not_compute_refuse():
    with pytest.raises(ValueError, match="unknown \\['-'\\]"):
        dataclasses.replace(CFG, layer_pattern="MEM-M*EME")
    with pytest.raises(ValueError, match="got 8 letters"):
        dataclasses.replace(CFG, layer_pattern="MEMEM*EM")
    with pytest.raises(ValueError, match="without positions"):
        dataclasses.replace(CFG, rope=True)
    with pytest.raises(ValueError, match="held experts inside"):
        dataclasses.replace(CFG, experts_held=(12, 8))
    with pytest.raises(ValueError, match="pattern stack's layers alone"):
        M.GPTConfig(vocab_size=32, mamba_heads=4)
    with pytest.raises(ValueError, match="name no projection"):
        M.init_lora(jax.random.PRNGKey(0), CFG, 2, ("w_up",))
