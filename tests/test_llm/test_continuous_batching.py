"""Continuous in-flight batching + paged KV serving (ISSUE 7 tentpole,
ROADMAP item 3): greedy paged decode is token-for-token identical to the
dense ``llm/generate.generate`` path, the scheduler's compiled-program set is
bounded by the grid (NOT by request count or admission order), prefix-cache
hits skip prefill, and SLO admission control sheds with visible telemetry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.generate import generate, left_pad
from agilerl_tpu.llm.serving import ContinuousGenerator, measured_cache_size
from agilerl_tpu.observability import MemorySink, MetricsRegistry

pytestmark = pytest.mark.serving

CFG = M.GPTConfig(vocab_size=96, n_layer=2, n_head=4, n_kv_head=2,
                  d_model=32, max_seq_len=256, dtype=jnp.float32)


def _params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


def _ragged(rng, n, lo, hi):
    return [rng.integers(3, 95, size=rng.integers(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _gen(**kw):
    defaults = dict(max_new_tokens=8, pad_id=0, eos_id=None,
                    prompt_buckets=(32,), slots=3, block_size=8,
                    decode_chunk=4, metrics=MetricsRegistry())
    defaults.update(kw)
    return ContinuousGenerator(CFG, **defaults)


def test_greedy_parity_with_dense_generate():
    """The tier-1 equivalence gate: greedy paged-KV decode through the
    continuous scheduler — with MORE requests than slots, so slots recycle
    mid-stream — emits exactly the dense generate() tokens and masks."""
    params = _params()
    rng = np.random.default_rng(0)
    seqs = _ragged(rng, 7, 4, 28)  # 7 requests over 3 slots
    gen = _gen()
    comp, cmask, info = gen.generate(seqs, jax.random.PRNGKey(1), params,
                                     greedy=True)
    toks, mask = left_pad(seqs, 0, 32)
    dcomp, dcmask = generate(CFG, params, jnp.asarray(toks),
                             jnp.asarray(mask), jax.random.PRNGKey(1),
                             max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(comp, np.asarray(dcomp))
    np.testing.assert_array_equal(cmask, np.asarray(dcmask))


def test_greedy_parity_with_eos_early_exit():
    """Same gate with EOS active: rows finish at different depths, free
    their slot, and queued rows take over — outputs still dense-identical."""
    params = _params()
    rng = np.random.default_rng(2)
    seqs = _ragged(rng, 6, 4, 28)
    # pick an eos the model actually emits so rows genuinely stop early
    free, _, _ = _gen(max_new_tokens=16, decode_chunk=4).generate(
        seqs, jax.random.PRNGKey(1), _params(), greedy=True)
    eos = int(free[0, 2])
    gen = _gen(max_new_tokens=16, decode_chunk=4, eos_id=eos)
    comp, cmask, _ = gen.generate(seqs, jax.random.PRNGKey(1), params,
                                  greedy=True)
    toks, mask = left_pad(seqs, 0, 32)
    dcomp, dcmask = generate(CFG, params, jnp.asarray(toks),
                             jnp.asarray(mask), jax.random.PRNGKey(1),
                             max_new_tokens=16, temperature=0.0, eos_id=eos)
    np.testing.assert_array_equal(comp, np.asarray(dcomp))
    np.testing.assert_array_equal(cmask, np.asarray(dcmask))


def test_greedy_parity_with_the_uncached_forward():
    """The paged tier against a reference that shares NO attention code with
    it: greedy decode through the continuous scheduler (paged gather +
    chunked_cached_attention) emits, request by request, the tokens the
    UNCACHED forward over the bare sequence so far picks."""
    params = _params()
    rng = np.random.default_rng(3)
    seqs = _ragged(rng, 4, 4, 20)
    comp, cmask, _ = _gen().generate(seqs, jax.random.PRNGKey(1), params,
                                     greedy=True)
    for row, seq in enumerate(seqs):
        seq = jnp.asarray(seq)[None]
        for _ in range(8):
            logits, _ = M.apply(CFG, params, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(seq.dtype)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(comp[row], np.asarray(seq[0, -8:]))
    np.testing.assert_array_equal(cmask, np.ones_like(cmask))


def test_compiled_programs_bounded_by_grid_not_requests():
    """The compile-count regression gate, asserted through CompileGuard (the
    one way steady-state no-recompile is checked repo-wide, ISSUE 11):
    serving many waves of ragged requests in shuffled admission orders must
    not grow the program set beyond (prefill per bucket) + (ONE decode
    chunk) + (block copy)."""
    from agilerl_tpu.analysis import CompileGuard

    params = _params()
    rng = np.random.default_rng(4)
    gen = _gen(prompt_buckets=(16, 32))
    seqs = _ragged(rng, 5, 4, 30)
    gen.generate(seqs, jax.random.PRNGKey(0), params, greedy=True)
    # both buckets touched + decode (+ maybe copy): grid bound
    after_first = gen.compiled_programs
    assert 0 < after_first <= 2 + 1 + 1
    # the copy program may appear once (first prefix hit); nothing else may
    with CompileGuard(sizer=lambda: gen.compiled_programs, max_new=1,
                      label="serving waves") as waves_guard:
        for wave in range(3):
            order = rng.permutation(len(seqs))
            wave_seqs = [seqs[i] for i in order] + _ragged(rng, 4, 4, 30)
            gen.generate(wave_seqs, jax.random.PRNGKey(wave + 1), params,
                         greedy=True)
    # steady state: a repeat batch may not compile ANYTHING new
    with CompileGuard(sizer=lambda: gen.compiled_programs,
                      label="serving steady state"):
        gen.generate(seqs, jax.random.PRNGKey(99), params, greedy=True)
    assert waves_guard.new_compilations <= 1


def test_prefix_cache_prefills_once_for_repeated_prompts():
    """Identical prompts (GRPO group repeats, best-of-N, retries) prefill
    once: later admissions reuse the cached prompt blocks."""
    params = _params()
    rng = np.random.default_rng(5)
    base = _ragged(rng, 1, 10, 20)[0]
    reg = MetricsRegistry()
    gen = _gen(metrics=reg)
    comp, _, info = gen.generate([base] * 5, jax.random.PRNGKey(1), params,
                                 greedy=True)
    assert info["prefix_cache_hits"] == 4, info
    assert reg.counter("serving/prefix_cache_misses_total").value == 1
    # all five rows identical under greedy
    for i in range(1, 5):
        np.testing.assert_array_equal(comp[0], comp[i])
    # and identical to a fresh no-cache run
    gen2 = _gen(prefix_cache=False, metrics=MetricsRegistry())
    comp2, _, info2 = gen2.generate([base] * 5, jax.random.PRNGKey(1),
                                    params, greedy=True)
    assert info2["prefix_cache_hits"] == 0
    np.testing.assert_array_equal(comp, comp2)


def test_blocks_freed_at_finish_and_reused():
    """A finished request's blocks return to the allocator immediately —
    total pool usage stays bounded across many sequential waves even with a
    pool far smaller than (requests x worst-case extent)."""
    params = _params()
    rng = np.random.default_rng(6)
    # 3 slots x 5 max blocks would fully provision at 16; force a tight pool
    gen = _gen(n_blocks=12, prefix_cache=False, metrics=MetricsRegistry())
    free0 = gen.allocator.available()
    for wave in range(3):
        gen.generate(_ragged(rng, 6, 4, 28), jax.random.PRNGKey(wave),
                     params, greedy=True)
        assert gen.allocator.available() == free0  # everything came back
    assert gen._occupancy() == 0


def test_per_request_budgets_and_slot_recycling():
    """submit(max_new=...) budgets are honoured per request: short rows
    finish early (trimmed + padded to the generator budget) and the decode
    keeps running only for live rows."""
    params = _params()
    rng = np.random.default_rng(7)
    seqs = _ragged(rng, 4, 4, 20)
    gen = _gen(max_new_tokens=16, decode_chunk=4)
    budgets = [2, 6, 10, 16]
    tickets = [gen.submit(s, max_new=b, key=jax.random.fold_in(
        jax.random.PRNGKey(1), i), no_shed=True)
        for i, (s, b) in enumerate(zip(seqs, budgets))]
    gen.run_until_drained(params, greedy=True)
    toks32, mask32 = left_pad(seqs, 0, 32)
    dcomp, _ = generate(CFG, params, jnp.asarray(toks32), jnp.asarray(mask32),
                        jax.random.PRNGKey(1), max_new_tokens=16,
                        temperature=0.0)
    for i, (t, b) in enumerate(zip(tickets, budgets)):
        toks, emits = gen.result(t)
        assert toks.shape == (b,) and emits.shape == (b,)
        np.testing.assert_array_equal(toks, np.asarray(dcomp)[i, :b])
        assert emits.sum() == b


def test_admission_control_sheds_with_telemetry():
    """Load shedding: queue overflow and TTFT-SLO breach both shed (None
    ticket), count in shed_requests_total, and emit a structured event;
    no_shed bypasses. Queue-wait histograms populate for admitted rows."""
    params = _params()
    rng = np.random.default_rng(8)
    reg = MetricsRegistry(sink=MemorySink())
    gen = _gen(metrics=reg, max_queue=2, ttft_slo_s=1e-9, min_slo_samples=1)
    seqs = _ragged(rng, 4, 4, 20)
    # fill the TTFT histogram past the (absurdly tight) SLO via one served
    # request, then every unprivileged submit sheds
    gen.generate([seqs[0]], jax.random.PRNGKey(0), params, greedy=True)
    assert gen.submit(seqs[1]) is None
    assert reg.counter("serving/shed_requests_total").value == 1
    (ev,) = [e for e in reg.sink.events if e["kind"] == "serving_shed"]
    assert ev["reason"] == "ttft_slo"
    # no_shed (the GRPO rollout mode) bypasses the breach
    t = gen.submit(seqs[1], no_shed=True)
    assert t is not None
    gen.run_until_drained(params, greedy=True)
    gen.result(t)
    # queue-overflow shedding with the SLO satisfied
    gen2 = _gen(metrics=MetricsRegistry(sink=MemorySink()), max_queue=2)
    assert gen2.submit(seqs[0], no_shed=True) is not None
    assert gen2.submit(seqs[1], no_shed=True) is not None
    assert gen2.submit(seqs[2]) is None  # queue full
    ev2 = [e for e in gen2.metrics.sink.events
           if e["kind"] == "serving_shed"]
    assert ev2 and ev2[0]["reason"] == "queue_full"
    gen2.run_until_drained(params, greedy=True)
    summary = gen2.latency_summary()
    assert summary["shed_requests_total"] == 1
    assert summary["queue_wait_s"]["count"] == 2
    assert summary["slot_occupancy"] == 0


def test_free_block_watermark_sheds():
    params = _params()
    rng = np.random.default_rng(9)
    reg = MetricsRegistry(sink=MemorySink())
    # watermark above the whole pool: everything unprivileged sheds
    gen = _gen(metrics=reg, free_block_watermark=2.0)
    assert gen.submit(_ragged(rng, 1, 4, 10)[0]) is None
    ev = [e for e in reg.sink.events if e["kind"] == "serving_shed"]
    assert ev and ev[0]["reason"] == "free_block_watermark"


def test_latency_summary_has_continuous_slo_readout():
    params = _params()
    rng = np.random.default_rng(10)
    reg = MetricsRegistry()
    gen = _gen(metrics=reg)
    gen.generate(_ragged(rng, 4, 4, 20), jax.random.PRNGKey(1), params,
                 greedy=True)
    s = gen.latency_summary()
    assert s["ttft_s"]["count"] == 4
    assert s["decode_time_per_token_s"]["count"] >= 1
    assert s["queue_wait_s"]["count"] == 4
    assert s["requests_total"] == 4 and s["rows_total"] == 4
    assert s["tokens_decoded_total"] == 4 * 8
    assert s["shed_requests_total"] == 0
    assert s["free_blocks"] == gen.allocator.available()


def test_generate_input_validation():
    gen = _gen()
    params = _params()
    with pytest.raises(ValueError, match="empty sequence list"):
        gen.generate([], jax.random.PRNGKey(0), params)
    rng = np.random.default_rng(11)
    with pytest.raises(ValueError, match="fits"):
        gen.generate(_ragged(rng, 2, 40, 50), jax.random.PRNGKey(0), params)
    with pytest.raises(ValueError, match="bucket grid"):
        gen.submit(np.zeros(0, np.int32))
    # a zero budget must refuse loudly, not fall back to the full budget
    with pytest.raises(ValueError, match="max_new"):
        gen.submit(np.arange(3, 10, dtype=np.int32), max_new=0)


def test_wedged_scheduler_raises_instead_of_spinning():
    """A pool too small for even one request must raise, not livelock."""
    params = _params()
    gen = _gen(n_blocks=3)  # one request needs 4 prompt + 1 decode blocks
    gen.submit(np.arange(3, 20, dtype=np.int32), no_shed=True)
    with pytest.raises(RuntimeError, match="wedged"):
        gen.run_until_drained(params, greedy=True)


def test_weight_update_invalidates_prefix_cache():
    """Cached prompt KV is only valid for the weights that prefilled it: a
    NEW lora tree (GRPO swaps the actor adapter every learn step) must
    flush the cache — the repeated prompt re-prefills and the output
    matches a cache-free generator under the new weights."""
    params = _params()
    lora1 = M.init_lora(jax.random.PRNGKey(1), CFG, rank=4)
    lora2 = M.init_lora(jax.random.PRNGKey(2), CFG, rank=4)
    # make lora2 a real delta (B is zero-init -> adapters start as no-ops)
    lora2 = jax.tree_util.tree_map(
        lambda x: x + 0.05 * jnp.ones_like(x), lora2)
    rng = np.random.default_rng(20)
    seqs = [rng.integers(3, 95, size=12).astype(np.int32)] * 3
    reg = MetricsRegistry()
    gen = _gen(metrics=reg)
    gen.generate(seqs, jax.random.PRNGKey(0), params, lora=lora1,
                 greedy=True)
    comp2, _, info2 = gen.generate(seqs, jax.random.PRNGKey(0), params,
                                   lora=lora2, greedy=True)
    # the weight swap flushed the cache: NO stale hit, one flush counted
    assert info2["prefix_cache_hits"] == 2  # within-call repeats only
    assert reg.counter(
        "serving/prefix_cache_invalidations_total").value == 1
    fresh = _gen(metrics=MetricsRegistry())
    comp_fresh, _, _ = fresh.generate(seqs, jax.random.PRNGKey(0), params,
                                      lora=lora2, greedy=True)
    np.testing.assert_array_equal(comp2, comp_fresh)
    # same trees again: no flush
    gen.generate(seqs, jax.random.PRNGKey(0), params, lora=lora2,
                 greedy=True)
    assert reg.counter(
        "serving/prefix_cache_invalidations_total").value == 1


def test_exactly_sized_pool_serves_repeat_prompt_as_miss():
    """A pool provisioned for exactly one request must keep serving the
    IDENTICAL prompt: the prefix hit is unaffordable (+1 copy block), so
    admission falls back to a miss that evicts the cold cached blocks
    instead of wedging."""
    params = _params()
    rng = np.random.default_rng(21)
    seq = rng.integers(3, 95, size=20).astype(np.int32)
    # bucket 32 / bs 8 -> 4 prompt + 1 decode block; pool = 1 + 5
    gen = _gen(n_blocks=6, slots=1)
    c1, _, _ = gen.generate([seq], jax.random.PRNGKey(0), params,
                            greedy=True)
    c2, _, info2 = gen.generate([seq], jax.random.PRNGKey(0), params,
                                greedy=True)
    assert info2["prefix_cache_hits"] == 0  # served as a miss, not wedged
    np.testing.assert_array_equal(c1, c2)


def test_prefix_cache_disabled_keeps_allocator_clean():
    """prefix_cache=False: no hashing, no registration — finished prompt
    blocks go straight back to the free list, nothing parks in the LRU."""
    params = _params()
    rng = np.random.default_rng(22)
    gen = _gen(prefix_cache=False, metrics=MetricsRegistry())
    avail0 = gen.allocator.available()
    gen.generate(_ragged(rng, 4, 4, 20), jax.random.PRNGKey(0), params,
                 greedy=True)
    assert gen.allocator.evictable_blocks == 0
    assert gen.allocator.free_blocks == avail0


def test_generate_rejects_empty_row_before_enqueueing_any():
    """A mid-batch invalid row must fail BEFORE any submit — otherwise the
    earlier rows would be orphaned in the queue and served (and leaked) by
    the next caller."""
    gen = _gen()
    rng = np.random.default_rng(23)
    seqs = _ragged(rng, 2, 4, 10) + [np.zeros(0, np.int32)]
    with pytest.raises(ValueError, match="bucket grid"):
        gen.generate(seqs, jax.random.PRNGKey(0), _params())
    assert len(gen._queue) == 0 and gen._occupancy() == 0


# -- satellite: compiled_programs hardening on the installed jax ----------- #


def test_measured_cache_size_present_on_installed_jax():
    """The installed jax (0.9.0) DOES expose _cache_size; the measured
    counter must be live, not the sentinel."""
    f = jax.jit(lambda x: x + 1)
    assert measured_cache_size(f) == 0
    f(jnp.ones(2))
    assert measured_cache_size(f) == 1


def test_measured_cache_size_degrades_to_sentinel_not_raise():
    """The missing-API path (a future jax renaming _cache_size): the guard
    must return the -1 sentinel — never raise mid-generate."""
    def plain(x):
        return x

    assert measured_cache_size(plain) == -1
    f = jax.jit(lambda x: x + 1)
    assert measured_cache_size(f, plain) == -1  # one missing poisons honestly
    gen = _gen()
    gen._decode = plain  # simulate the rename on a live generator
    assert gen.compiled_programs == -1


# -- satellite: GRPO fallback + continuous opt-in -------------------------- #


def test_grpo_continuous_opt_in_and_group_prefix_hits():
    from agilerl_tpu.algorithms.grpo import GRPO

    agent = GRPO(config=CFG, pad_token_id=0, eos_token_id=1, group_size=3,
                 batch_size=4, max_output_tokens=8, seed=0,
                 continuous_decode=True)
    assert agent.continuous_decode and agent.init_dict["continuous_decode"]
    rng = np.random.default_rng(12)
    ids = rng.integers(3, 95, size=(2, 10)).astype(np.int32)
    mask = np.ones_like(ids)
    comp, cmask = agent.get_action({"input_ids": ids,
                                    "attention_mask": mask})
    assert comp.shape == (6, 8) and cmask.shape == (6, 8)
    # group_size repeats of each prompt prefill ONCE
    assert agent.last_generation_info["prefix_cache_hits"] == 2 * (3 - 1)
    # greedy eval path
    comp, _ = agent.get_action({"input_ids": ids, "attention_mask": mask},
                               training=False)
    assert comp.shape == (2, 8)


@pytest.mark.parametrize("bucketed, continuous, tier", [
    (True, False, "bucketed"),  # the defaults
    (True, True, "continuous"),
    # continuous-only is a valid config: the bucketed flag does not gate it
    (False, True, "continuous"),
    (False, False, "dense"),  # the dense generate, exact RNG stream
])
def test_grpo_decode_flags_pick_the_tier(bucketed, continuous, tier):
    """The two constructor flags are the only switch of the rollout tier
    (no environment variable is read) and are independent."""
    from agilerl_tpu.algorithms.grpo import GRPO

    flags = {}
    if (bucketed, continuous) != (True, False):
        flags = dict(bucketed_decode=bucketed, continuous_decode=continuous)
    agent = GRPO(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
                 batch_size=4, max_output_tokens=8, seed=0, **flags)
    assert agent.bucketed_decode == bucketed
    assert agent.continuous_decode == continuous
    ids = np.random.default_rng(12).integers(3, 95, size=(2, 10)).astype(np.int32)
    comp, _ = agent.get_action({"input_ids": ids,
                                "attention_mask": np.ones_like(ids)})
    assert comp.shape == (4, 8)
    info = agent.last_generation_info
    if tier == "dense":
        assert info is None
    else:
        assert ("slots" in info) == (tier == "continuous")


def test_grpo_prompt_overflow_falls_back_to_dense():
    """Satellite: an over-grid rollout batch (prompt LONGER than the largest
    bucket — the axis the row-overflow test doesn't cover) must fall back to
    llm/generate.generate instead of crashing the training loop, on both
    serving paths."""
    from agilerl_tpu.algorithms.grpo import GRPO

    for continuous in (False, True):
        agent = GRPO(config=CFG, pad_token_id=0, eos_token_id=1,
                     group_size=2, batch_size=4, max_output_tokens=8, seed=0,
                     continuous_decode=continuous)
        gen = (agent._get_continuous_generator() if continuous
               else agent._get_bucketed_generator())
        too_long = gen.prompt_buckets[-1] + 5
        assert not gen.fits(2, too_long)
        rng = np.random.default_rng(13)
        # seed telemetry with an in-grid call, then overflow must clear it
        ids = rng.integers(3, 95, size=(2, 10)).astype(np.int32)
        agent.get_action({"input_ids": ids,
                          "attention_mask": np.ones_like(ids)})
        assert agent.last_generation_info is not None
        ids = rng.integers(3, 95, size=(1, too_long)).astype(np.int32)
        comp, cmask = agent.get_action(
            {"input_ids": ids, "attention_mask": np.ones_like(ids)})
        assert comp.shape == (2, 8) and cmask.shape == (2, 8)
        assert agent.last_generation_info is None  # stale telemetry cleared
