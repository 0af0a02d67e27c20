"""The weights a rollout computes on (ISSUE 39): ``model.compute_params``
casts the matrices to the compute type once a rollout where every prefill
and decode-chunk program cast them once a call, ``serving.rollout_weights``
gives the copy its lifetime and its counters, ``GRPO.get_action`` hands it
to the continuous tier. Nothing is computed differently: each consumer of a
cast leaf rounds it to the compute type before it reads it, so the copy and
the masters give the same bits on every backend — tokens, masks and
captured log-probabilities. The head is the one matrix that is not cast:
``logits_fn`` reads it at f32."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.llm.serving import ContinuousGenerator, rollout_weights

pytestmark = pytest.mark.serving

BF16 = jnp.dtype(jnp.bfloat16)

#: per stack: (configuration with f32 masters and bf16 compute, adapter
#: targets, the names of the leaves the copy holds in bf16 — written out
#: here, not read from the helper: a leaf the path reads at f32 (the head,
#: router, score MLP, state-space leaves, norm scales) fails the
#: bit-identity below if the helper casts it, and the list if it does not)
STACKS = {
    "dense_gqa": (
        lambda: M.GPTConfig(vocab_size=96, n_layer=2, n_head=4, n_kv_head=2,
                            d_model=32, max_seq_len=256, qkv_bias=True,
                            tie_embeddings=False),
        ("wq", "wv"),
        {"tok_emb", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}),
    "hybrid": (
        lambda: M.GPTConfig(vocab_size=97, n_layer=4, n_head=4, n_kv_head=1,
                            d_model=32, d_ff=64, max_seq_len=128, rope=False,
                            attn_layer_period=4, attn_layer_offset=1,
                            mamba_d_state=8, mamba_dt_rank=4),
        ("wq", "in_proj"),  # a tied head: the embedding stays as stored
        {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "in_proj",
         "x_proj", "dt_proj", "out_proj"}),
    "dropless_experts": (
        lambda: preset("tiny-cca-moe", remat=False,
                       use_flash_attention=False),
        ("wq", "wv1"),  # a tied head again
        {"wq", "wk", "wv1", "wv2", "wo", "conv1_w", "w_gate", "w_up",
         "w_down"}),
    "latent_experts": (
        lambda: preset("tiny-mla-moe", remat=False,
                       use_flash_attention=False),
        ("wq", "wkv_b"),
        {"tok_emb", "wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down",
         "ws_gate", "ws_up", "ws_down"}),
}


def names_by_dtype(tree):
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.setdefault(jnp.dtype(x.dtype), set()).add(path[-1].key)
    return out


def through_bf16(tree, names):
    """The f32 tree with the leaves ``names`` taken through bf16 and back."""
    def one(path, x):
        if path[-1].key in names:
            return x.astype(BF16).astype(x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(one, tree)


def build(stack):
    make, targets, cast = STACKS[stack]
    cfg = make()
    assert jnp.dtype(cfg.dtype) == BF16
    masters = M.init_params(jax.random.PRNGKey(0), cfg)
    assert set(names_by_dtype(masters)) == {jnp.dtype(jnp.float32)}
    lora = M.init_lora(jax.random.PRNGKey(1), cfg, rank=2, targets=targets)
    # B starts at zero, where an adapter checks nothing
    lora = jax.tree_util.tree_map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape),
        lora)
    return cfg, masters, lora, cast


def generator(cfg, **kw):
    args = dict(max_new_tokens=8, pad_id=0, eos_id=None, prompt_buckets=(16,),
                slots=3, block_size=8, decode_chunk=4, capture_logprobs=True,
                metrics=observability.MetricsRegistry())
    args.update(kw)
    return ContinuousGenerator(cfg, **args)


def rollout(cfg, tree, lora, greedy, seed=0):
    rng = np.random.default_rng(seed)
    # more requests than slots; rows 1 and 3 share a prompt (a prefix hit)
    seqs = [rng.integers(3, 95, size=n).astype(np.int32) for n in (5, 9, 14)]
    seqs.append(seqs[1].copy())
    comp, cmask, info = generator(cfg).generate(
        seqs, jax.random.PRNGKey(3), tree, lora=lora, greedy=greedy)
    assert info["prefix_hit_rows"] == [False, False, False, True]
    return comp, cmask, info["logprobs"]


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
@pytest.mark.parametrize("stack", list(STACKS))
def test_the_copy_generates_what_the_masters_generate(stack, greedy):
    cfg, masters, lora, cast = build(stack)
    copy = M.compute_params(cfg, masters)
    by_dtype = names_by_dtype(copy)
    assert by_dtype[BF16] == cast
    assert not by_dtype[jnp.dtype(jnp.float32)] & cast
    got = rollout(cfg, copy, lora, greedy)
    want = rollout(cfg, masters, lora, greedy)
    for g, w in zip(got, want):  # tokens, masks, log-probabilities: the bits
        np.testing.assert_array_equal(g, w)
    assert got[1].sum() == got[1].size and np.abs(got[2]).min() > 0


def test_why_the_head_is_not_cast():
    """``logits_fn`` is an f32 product: exact here, so a head taken through
    bf16 shows, by the head's rounding alone — a head weight moves by at
    most 2**-9 of itself, a logit (32 products of |h| ~ 1 with |w| ~ 0.02)
    by ~1e-3. On the chip the default precision rounds that product's
    operands to bf16 anyway, and a bf16 head still does not give the same
    bits (PERF.md, section 6, PR 39): it stays as stored."""
    cfg, masters, lora, cast = build("dense_gqa")
    assert "lm_head" not in cast
    got = rollout(cfg, masters, lora, greedy=False)
    want = rollout(cfg, through_bf16(masters, {"lm_head"}), lora, greedy=False)
    np.testing.assert_array_equal(got[0], want[0])  # no flip at this size
    diff = np.abs(got[2] - want[2]).max()
    assert 0 < diff < 4e-3, diff


# -- the helper's contract ------------------------------------------------- #


class Compiles:
    """Programs JAX compiled since construction (its monitoring events)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, seconds, **_):
        if event.endswith("backend_compile_duration"):
            self.n += 1


def test_one_program_and_no_leaf_copied_for_nothing(monkeypatch):
    cfg, masters, _, cast = build("dense_gqa")
    # a shape no other test of this process casts: the count below is exact
    for blk in masters["blocks"].values():
        blk["w_up"] = jnp.ones((32, 72), jnp.float32)
    compiles = Compiles()
    copy = M.compute_params(cfg, masters)
    assert compiles.n == 1  # the whole tree: ONE program
    again = M.compute_params(cfg, masters)
    assert compiles.n == 1
    # a tree with nothing to cast: the same object, nothing dispatched
    with monkeypatch.context() as mp:
        mp.setattr(M, "_copy_program", None)
        assert M.compute_params(cfg, copy) is copy
        f32 = M.GPTConfig(**{**cfg.__dict__, "dtype": jnp.float32})
        assert M.compute_params(f32, masters) is masters
    # outside the per-layer trees a leaf that is not cast is the SAME array
    # (handed through a jit it would be a copy); a cast one is a new buffer
    assert set(copy) == {"tok_emb", "lm_head", "ln_f", "runs"}
    assert copy["lm_head"] is masters["lm_head"]
    assert copy["ln_f"] is masters["ln_f"]
    assert copy["tok_emb"].dtype == BF16
    assert copy["tok_emb"].unsafe_buffer_pointer() \
        != again["tok_emb"].unsafe_buffer_pointer()
    # the per-layer trees come back as init_params stores a stack by runs
    (run,) = copy["runs"]
    want = M.stack_run([masters["blocks"][str(i)]
                        for i in range(cfg.n_layer)])
    assert set(run) == set(want)
    for name, x in want.items():
        assert run[name].dtype == (BF16 if name in cast else jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(run[name].astype(jnp.float32)),
            np.asarray(x.astype(run[name].dtype).astype(jnp.float32)))


def test_a_tree_stored_by_runs_keeps_its_layout():
    cfg, masters, _, cast = build("hybrid")
    copy = M.compute_params(cfg, masters)
    assert jax.tree_util.tree_structure(copy) \
        == jax.tree_util.tree_structure(masters)
    for (path, old), new in zip(
            jax.tree_util.tree_flatten_with_path(masters)[0],
            jax.tree_util.tree_leaves(copy)):
        if path[-1].key in cast:
            assert new.dtype == BF16 and new.shape == old.shape
        else:
            assert new is old


def test_under_a_plan_every_leaf_keeps_its_sharding():
    from agilerl_tpu.parallel.plan import make_grpo_plan

    cfg = M.GPTConfig(vocab_size=128, n_layer=2, n_head=4, n_kv_head=2,
                      d_model=64, max_seq_len=128, tie_embeddings=False)
    plan = make_grpo_plan(fsdp=4, tp=2)
    mesh = plan.build_mesh()
    placed = plan.place(
        "params", M.init_params(jax.random.PRNGKey(0), cfg), mesh)
    assert placed["blocks"]["0"]["wq"].sharding.spec == P("fsdp", "tp")
    copy = M.compute_params(cfg, placed)
    for name in ("tok_emb", "lm_head", "ln_f"):
        assert copy[name].sharding == placed[name].sharding, name
    assert copy["tok_emb"].dtype == BF16
    (run,) = copy["runs"]  # the layer axis replicated, the rest as placed
    for name, x in placed["blocks"]["0"].items():
        assert run[name].sharding.mesh == x.sharding.mesh
        assert run[name].sharding.spec == P(None, *x.sharding.spec), name
    assert run["wq"].dtype == BF16
    assert run["wq"].sharding.spec == P(None, "fsdp", "tp")


# -- lifetime, counters, GRPO ----------------------------------------------- #


def agent_for(cfg, base, lora, **kw):
    args = dict(config=cfg, base_params=base, pad_token_id=0, eos_token_id=1,
                group_size=4, batch_size=4, max_output_tokens=8,
                min_output_tokens=8, lora_rank=2, lora_targets=("wq", "wv"),
                continuous_decode=True, capture_logprobs=True, seed=0)
    args.update(kw)
    agent = GRPO(**args)
    agent.actor.params = lora
    return agent


def test_get_action_casts_once_a_rollout_and_lets_the_copy_go():
    cfg, masters, lora, cast = build("dense_gqa")
    reg = observability.get_registry()
    made = reg.counter("serving/weight_cast_total")
    live = reg.gauge("serving/weight_cast_bytes")
    flushes = reg.counter("serving/prefix_cache_invalidations_total")
    batch = {"input_ids": np.arange(3, 15, dtype=np.int32)[None],
             "attention_mask": np.ones((1, 12), np.int32)}

    seen = []
    real = ContinuousGenerator.generate

    def spy(self, sequences, key, params, **kw):
        leaf = params["tok_emb"]
        seen.append((weakref.ref(leaf), leaf.dtype, live.value, id(params)))
        return real(self, sequences, key, params, **kw)

    # f32 masters: one copy a rollout, alive inside it and gone after it
    agent = agent_for(cfg, masters, lora)
    n0, f0 = made.value, flushes.value
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ContinuousGenerator, "generate", spy)
        comp, cmask = agent.get_action(batch)
    ref, dtype, nbytes, _ = seen.pop()
    assert dtype == BF16 and made.value == n0 + 1
    # the cast matrices at two bytes, and the layers' vectors stacked beside
    flat = jax.tree_util.tree_flatten_with_path(masters)[0]
    assert nbytes == sum(
        x.size * 2 if p[-1].key in cast else x.nbytes for p, x in flat
        if p[-1].key in cast or p[0].key == "blocks")
    assert live.value == 0
    gc.collect()
    assert ref() is None  # no live reference to the copy is left
    assert agent._get_continuous_generator()._weights is None
    info = agent.last_generation_info
    # ONE tree object a rollout = one weight epoch: 3 of 4 rows of the group
    # hit the prefix cache, and the epoch's end is the one flush
    assert info["prefix_hit_rows"] == [False, True, True, True]
    assert flushes.value == f0 + 1
    assert agent.base_params is masters
    assert agent.base_params["tok_emb"].dtype == jnp.float32
    # the next rollout: one more copy, still one flush a rollout
    agent.get_action(batch)
    assert made.value == n0 + 2 and flushes.value == f0 + 2
    assert live.value == 0

    # the same seed from a base stored in the compute type: the same
    # rollout, the stored tree itself in the programs' hands, no copy
    stored = M.compute_params(cfg, masters)
    twin = agent_for(cfg, stored, lora)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ContinuousGenerator, "generate", spy)
        comp2, cmask2 = twin.get_action(batch)
    assert seen.pop()[3] == id(stored)
    assert made.value == n0 + 2 and live.value == 0
    np.testing.assert_array_equal(comp, comp2)
    np.testing.assert_array_equal(cmask, cmask2)
    np.testing.assert_array_equal(
        info["logprobs"], twin.last_generation_info["logprobs"])
    # and it is what the generator gives from the masters themselves
    # (get_action draws the rollout's key from the agent's stream)
    key = agent_for(cfg, masters, lora).next_key()
    seqs = [batch["input_ids"][0]] * 4
    want, wmask, winfo = generator(
        cfg, eos_id=1, min_new_tokens=8,
        temperature=agent.temperature).generate(
            seqs, key, masters, lora=lora)
    np.testing.assert_array_equal(comp, want)
    np.testing.assert_array_equal(cmask, wmask)
    np.testing.assert_array_equal(info["logprobs"], winfo["logprobs"])


def test_rollout_weights_ends_the_epoch_it_opened():
    cfg, masters, lora, _ = build("dense_gqa")
    gen = generator(cfg)
    reg = gen.metrics
    seqs = [np.arange(3, 12, dtype=np.int32)] * 2
    with rollout_weights(gen, cfg, masters) as weights:
        assert weights is not masters
        assert reg.gauge("serving/weight_cast_bytes").value > 0
        gen.generate(seqs, jax.random.PRNGKey(0), weights, lora=lora)
        assert gen._weights[0] is weights
    assert gen._weights is None
    assert reg.gauge("serving/weight_cast_bytes").value == 0
    assert reg.counter("serving/weight_cast_total").value == 1
    assert reg.dump()["histograms"]["rollout/weight_cast"]["count"] == 1
    # nothing cached under the ended epoch answers the next one
    _, _, info = gen.generate(seqs, jax.random.PRNGKey(0), masters, lora=lora)
    assert info["prefix_hit_rows"] == [False, True]
    # a stored tree passes through: no copy, no counter, the epoch stays
    stored = M.compute_params(cfg, masters)
    with rollout_weights(gen, cfg, stored) as weights:
        assert weights is stored
        gen.generate(seqs, jax.random.PRNGKey(0), weights, lora=lora)
    assert gen._weights[0] is stored
    assert reg.counter("serving/weight_cast_total").value == 1
