"""A hybrid stack (state-space layers beside attention layers, the layer
pattern of ``jamba2-3b`` at a tiny size: attention every fourth layer, two
periods) against the benchmark's plain reference
``perfbench/reference/jamba_f32.py``: ``forward`` logits, the dense cached
path, the continuous tier through its recurrent-state cache (prefilled rows
and prefix-hit rows, on log-probabilities, not tokens), a LoRA gradient on a
Mamba projection, one scan body a run of layers, and the refusals."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.serving import ContinuousGenerator
from perfbench.reference import jamba_f32 as ref

G = importlib.import_module("agilerl_tpu.llm.generate")

CFG = M.GPTConfig(
    vocab_size=97, n_layer=8, n_head=4, n_kv_head=1, d_model=32, d_ff=64,
    max_seq_len=128, rope=False, attn_layer_period=4, attn_layer_offset=1,
    mamba_d_state=8, mamba_dt_rank=4, dtype=jnp.float32)
REF = dict(n_head=4, n_kv=1, eps=CFG.rms_eps)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with the published A_log / dt initialisation and
    non-zero conv and dt biases (init_mamba_mixer draws them so)."""
    p = M.init_params(jax.random.PRNGKey(0), CFG)
    assert [jax.tree_util.tree_leaves(run)[0].shape[0]
            for run in p["runs"]] == [1, 1, 3, 1, 2]  # one tree a run
    blk = p["runs"][0]  # layer 0, a state-space layer
    assert float(jnp.abs(blk["conv_b"]).min()) > 0
    assert float(jnp.abs(blk["dt_bias"]).min()) > 0
    return p


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 97, size=n).astype(np.int32) for n in lengths]


def test_layer_pattern_and_runs():
    assert [CFG.layer_kind(i) for i in range(8)] == [
        "mamba", "attn", "mamba", "mamba", "mamba", "attn", "mamba", "mamba"]
    assert CFG.layer_runs() == [("mamba", 0, 1), ("attn", 1, 1),
                                ("mamba", 2, 3), ("attn", 5, 1),
                                ("mamba", 6, 2)]
    from agilerl_tpu.llm.presets import preset

    big = preset("jamba2-3b")
    assert [n for _, _, n in big.layer_runs()] == [7, 1, 13, 1, 6]
    assert (big.mamba_d_inner, big.mamba_rank, big.head_dim) == (5120, 160, 128)


@pytest.mark.parametrize("loop", ["scanned", "unrolled"])
def test_forward_logits_match_the_reference_with_left_padding(params, loop):
    # unrolled: a run's layers called one by one
    cfg = dataclasses.replace(CFG, scan_layers=loop == "scanned")
    seqs = prompts(15, 11)
    toks, mask = G.left_pad(seqs, 0, 20)
    logits, _ = M.apply(cfg, params, jnp.asarray(toks),
                        attention_mask=jnp.asarray(mask))
    for row, seq in enumerate(seqs):
        want = ref.logits(params, seq, **REF)
        got = np.asarray(logits[row, 20 - len(seq):])
        assert np.abs(got - want).max() < ref.LOGIT_TOL


def test_a_bf16_state_fails_the_logit_tolerance(params):
    seq = prompts(64, seed=5)[0]
    want = ref.logits(params, seq, **REF)
    lossy = ref.logits(params, seq, bf16_state=True, **REF)
    assert np.abs(lossy - want).max() > ref.LOGIT_TOL


def test_dense_cached_prefill_and_decode_match_the_full_forward(params):
    seqs = prompts(20, 14)
    toks, mask = G.left_pad(seqs, 0, 20)
    toks, mask = jnp.asarray(toks), jnp.asarray(mask)
    full, _ = M.apply(CFG, params, toks, attention_mask=mask)
    pos = jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)
    cache = M.init_caches(CFG, 2, 32)
    P = 12
    lg, cache = M.apply(CFG, params, toks[:, :P], attention_mask=mask[:, :P],
                        positions=pos[:, :P], cache=cache)
    assert cache.prev_state is not None  # the state before the last token
    real = np.asarray(mask[:, :P], bool)
    assert np.abs(np.asarray(lg - full[:, :P]))[real].max() < 1e-5
    for t in range(P, 20):
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


def check_rows_against_reference(params, seqs, toks, lps, tol=ref.LOGIT_TOL):
    for i, s in enumerate(seqs):
        full = np.concatenate([s, toks[i]])
        want = ref.token_logprobs(
            params, full, np.arange(len(s) - 1, len(full) - 1), **REF)
        assert np.abs(want - lps[i]).max() < tol, i


# 16 = a prompt bucket exactly; 8 = one block; 9 / 17 cross a block and a
# bucket; the decode budget of 12 is three chunks of 4
@pytest.mark.parametrize("lengths", [(13, 16, 9), (8, 17, 16)])
def test_paged_decode_through_the_state_cache_matches_the_reference(
        params, lengths):
    """Sampled rollouts (so that rows differ), each prompt three times: the
    first is prefilled, the repeats are admitted by prefix hits and start
    from a snapshot. The tier's own log-probabilities of its sampled tokens
    against the reference's full forward, hit or miss."""
    a, b, c = prompts(*lengths)
    seqs = [a, a, b, a, c, b, c, b]
    gen = generator()
    toks, masks, info = gen.generate(
        seqs, jax.random.PRNGKey(3), params)
    assert masks.all()
    hits = info["prefix_hit_rows"]
    assert hits == [False, True, False, True, False, True, True, True]
    assert not np.array_equal(toks[0], toks[1])  # sampled: the rows differ
    check_rows_against_reference(params, seqs, toks, info["logprobs"])
    counters = gen.metrics.dump()["counters"]
    assert counters["serving/state_snapshots_stored_total"] == 3
    assert counters["serving/state_snapshot_restores_total"] == 5
    assert gen.metrics.dump()["gauges"]["serving/state_cache_bytes"] == \
        M.state_cache_bytes(gen._pool) > 0


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


def test_slot_reuse_leaves_no_state_behind(params):
    """Three slots, eight requests: every slot is reused after a finish. A
    row's tokens and log-probabilities are what a generator that has served
    nothing else gives it."""
    a, b, c = prompts(13, 16, 9, seed=7)
    seqs = [a, b, c, b, a, c, a, b]
    key = jax.random.PRNGKey(5)
    busy = generator(prefix_cache=False)
    toks, _, info = busy.generate(seqs, key, params)
    assert not any(info["prefix_hit_rows"])
    for i in (5, 7):
        fresh = generator(prefix_cache=False, slots=1)
        fresh._next_ticket = 0
        t1, _, i1 = fresh.generate([seqs[i]], jax.random.fold_in(key, 1000),
                                   params)
        # the same request key in both: row i's key is fold_in(key, i)
        alone = generator(prefix_cache=False, slots=1)
        ticket = alone.submit(seqs[i], key=jax.random.fold_in(key, i),
                              no_shed=True)
        alone.run_until_drained(params)
        t_alone, _ = alone.result(ticket)
        np.testing.assert_array_equal(t_alone, toks[i])
    check_rows_against_reference(params, seqs, toks, info["logprobs"])


def test_a_weight_epoch_change_drops_the_snapshots(params):
    a, b = prompts(13, 16)
    gen = generator()
    gen.generate([a, a, b], jax.random.PRNGKey(1), params)
    assert len(gen._snapshots) == 2
    other = jax.tree_util.tree_map(lambda x: x, params)  # a new tree
    toks, _, info = gen.generate([a, a], jax.random.PRNGKey(1), other)
    # the first a prefilled again under the new weights, the second hit it
    assert info["prefix_hit_rows"] == [False, True]
    assert len(gen._snapshots) == 1
    counters = gen.metrics.dump()["counters"]
    assert counters["serving/state_snapshot_evictions_total"] == 2
    check_rows_against_reference(params, [a, a], toks, info["logprobs"])


def test_a_chain_without_its_snapshot_admits_as_a_miss(params):
    a, b, c = prompts(13, 16, 9)
    gen = generator(slots=1)  # one snapshot entry a slot
    toks, _, info = gen.generate([a, b, a, a], jax.random.PRNGKey(2), params)
    # b's prefill took the one entry: a's chain is cached, its snapshot is
    # gone, so the third request prefills again (and the fourth hits)
    assert info["prefix_hit_rows"] == [False, False, False, True]
    check_rows_against_reference(params, [a, b, a, a], toks, info["logprobs"])


def test_lora_gradient_on_a_mamba_projection_matches_finite_differences(params):
    """d(sum of token log-probabilities)/d(adapter) of the program against
    central differences of the REFERENCE's loss with the adapter merged into
    the weights, along a random direction."""
    seq = prompts(24, seed=9)[0]
    lora = M.init_lora(jax.random.PRNGKey(1), CFG, 2, ("in_proj", "out_proj"))
    assert sorted(lora["blocks"]["0"]) == ["in_proj", "out_proj"]
    assert lora["blocks"]["1"] == {}  # an attention layer: nothing to adapt
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    lora = jax.tree_util.tree_map(  # B is zero-initialised: move off it
        lambda x: x + 0.05 * jax.random.normal(k1, x.shape), lora)
    direction = jax.tree_util.tree_map(
        lambda x: jax.random.normal(k2, x.shape), lora)

    def program_loss(lo):
        return M.token_logprobs(CFG, params, jnp.asarray(seq[None]),
                                lora=lo).sum()

    def reference_loss(lo):
        merged = M.merge_lora(params, lo)
        return float(ref.token_logprobs(
            merged, seq, np.arange(len(seq) - 1), **REF).sum())

    grad = jax.grad(program_loss)(lora)
    along = sum(float(jnp.vdot(g, d)) for g, d in zip(
        jax.tree_util.tree_leaves(grad), jax.tree_util.tree_leaves(direction)))
    eps = 2e-3
    step = lambda s: jax.tree_util.tree_map(  # noqa: E731
        lambda x, d: x + s * d, lora, direction)
    numeric = (reference_loss(step(eps)) - reference_loss(step(-eps))) / (2 * eps)
    assert abs(reference_loss(lora) - float(program_loss(lora))) < 1e-3
    assert abs(along - numeric) < 0.03 * abs(numeric) + 1e-3


def scan_calls(jaxpr) -> int:
    from jax._src import core

    n = 0
    for e in jaxpr.eqns:
        n += e.primitive.name.startswith("custom_vjp")
        n += sum(scan_calls(j) for j in core.jaxprs_in_params(e.params))
    return n


def test_the_learn_step_holds_one_mamba_body_a_run_not_one_a_layer(params):
    toks = jnp.ones((2, 16), jnp.int32)
    trace = lambda cfg: jax.make_jaxpr(  # noqa: E731
        lambda p: M.token_logprobs(cfg, p, toks))(params).jaxpr
    assert scan_calls(trace(CFG)) == 3  # runs of 1, 3 and 2 Mamba layers
    unrolled = dataclasses.replace(CFG, scan_layers=False)
    assert scan_calls(trace(unrolled)) == 6  # one a layer


def test_what_is_not_hybrid_aware_refuses_by_name(params):
    with pytest.raises(ValueError, match="recurrent-state rollback"):
        generator(speculate=True)
    gen = generator()
    with pytest.raises(NotImplementedError, match="submit_prefilled"):
        gen.submit_prefilled(
            np.arange(3, 9, dtype=np.int32), k_prompt=None, v_prompt=None,
            tok0=0, done0=False, key_next=None)
    with pytest.raises(ValueError, match="LoRA targets"):
        M.init_lora(jax.random.PRNGKey(0), CFG, 2, ("w_q",))
    pool = M.init_paged_cache(CFG, 4, 8, slots=2, snapshots=1)
    with pytest.raises(NotImplementedError, match="speculative verify"):
        M.forward_paged(
            CFG, params, jnp.ones((2, 3), jnp.int32),
            jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32), pool,
            jnp.zeros((2, 2), jnp.int32), jnp.ones((2, 16), jnp.int32))
    with pytest.raises(ValueError, match="pass slots"):
        M.init_paged_cache(CFG, 4, 8)

    agent = GRPO(config=CFG, base_params=params, pad_token_id=0,
                 eos_token_id=1, group_size=2, batch_size=2,
                 max_output_tokens=4, lora_rank=2,
                 lora_targets=("wq", "in_proj"), sequence_parallel_axis="sp")
    ids = jnp.ones((2, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="sequence_parallel_axis"):
        agent._resolve_learn_fns(ids, jnp.ones((2, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="attach_rollout_fleet"):
        agent.attach_rollout_fleet(object())
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "fsdp", "tp"))
    with pytest.raises(ValueError, match="state-space layers' leaves"):
        agent.to_mesh(mesh)
