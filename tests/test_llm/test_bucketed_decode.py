"""Bucketed ragged decode (llm/serving.py — the vLLM continuous-batching
role, VERDICT r3 next #3): bounded compile set across ragged sweeps, host
early-exit on EOS, greedy parity with the dense generate path.
Ref: /root/reference/agilerl/algorithms/core/base.py:3101 (vLLM glue)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.generate import generate, left_pad
from agilerl_tpu.llm.serving import BucketedGenerator

pytestmark = pytest.mark.serving

CFG = M.GPTConfig(vocab_size=96, n_layer=2, n_head=4, n_kv_head=2,
                  d_model=32, max_seq_len=256, dtype=jnp.float32)


def _params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


def _ragged(rng, n, lo, hi):
    return [rng.integers(3, 95, size=rng.integers(lo, hi)).astype(np.int32)
            for _ in range(n)]


def test_greedy_parity_with_dense_generate():
    """Bucketed greedy decode must match generate() token-for-token (same
    prefill maths, same per-step decode; RNG is unused when greedy)."""
    params = _params()
    rng = np.random.default_rng(0)
    seqs = _ragged(rng, 5, 4, 20)
    gen = BucketedGenerator(CFG, max_new_tokens=16, pad_id=0, eos_id=None,
                            prompt_buckets=(32,), row_buckets=(8,),
                            decode_chunk=8)
    comp, cmask, info = gen.generate(seqs, jax.random.PRNGKey(1), params,
                                     greedy=True)
    # dense reference at the SAME bucket padding
    toks, mask = left_pad(seqs, 0, 32)
    dcomp, dcmask = generate(CFG, params, jnp.asarray(toks), jnp.asarray(mask),
                             jax.random.PRNGKey(1), max_new_tokens=16,
                             temperature=0.0)
    np.testing.assert_array_equal(comp, np.asarray(dcomp)[:5])
    np.testing.assert_array_equal(cmask, np.asarray(dcmask)[:5])


def test_bounded_compile_set_across_ragged_sweep():
    """Any mix of prompt lengths / batch sizes inside one bucket pair
    compiles exactly 2 programs (prefill + decode chunk); a second prompt
    bucket adds at most 2 more (<=3 asked by VERDICT; we assert the exact
    bound per bucket). ``compiled_programs`` is the MEASURED jit cache size
    (VERDICT r4 #4), not a self-reported signature count."""
    params = _params()
    rng = np.random.default_rng(1)
    gen = BucketedGenerator(CFG, max_new_tokens=8, pad_id=0, eos_id=None,
                            prompt_buckets=(32, 64), row_buckets=(8,),
                            decode_chunk=8)
    assert gen.compiled_programs == 0  # measured: nothing traced yet
    for n, lo, hi in [(3, 4, 10), (5, 10, 30), (8, 5, 25), (2, 20, 31)]:
        gen.generate(_ragged(rng, n, lo, hi), jax.random.PRNGKey(n), params)
    assert gen.compiled_programs == 2, (
        f"ragged sweep within one bucket compiled {gen.compiled_programs}"
    )
    # crossing into the second prompt bucket adds exactly one prefill + one
    # decode program
    gen.generate(_ragged(rng, 4, 40, 60), jax.random.PRNGKey(9), params)
    assert gen.compiled_programs == 4


def test_compile_accounting_detects_retracing():
    """The measured counter must CATCH a per-call retrace the old
    shape-signature proxy was blind to: hitting the same bucket pair with a
    different dtype (the 'accidentally-traced knob' failure class) grows the
    jit cache, and compiled_programs must report it."""
    params = _params()
    rng = np.random.default_rng(5)
    gen = BucketedGenerator(CFG, max_new_tokens=8, pad_id=0, eos_id=None,
                            prompt_buckets=(32,), row_buckets=(8,),
                            decode_chunk=8)
    gen.generate(_ragged(rng, 3, 4, 10), jax.random.PRNGKey(0), params)
    assert gen.compiled_programs == 2
    # same bucket pair, perturbed param dtype -> a genuine retrace; the old
    # proxy (signature set keyed on (kind, Bb, Pb, greedy)) would still
    # report 2 and the regression would pass silently
    params64 = dict(params)
    params64["tok_emb"] = params["tok_emb"].astype(jnp.float16)
    gen.generate(_ragged(rng, 3, 4, 10), jax.random.PRNGKey(1), params64)
    assert gen.compiled_programs >= 3, (
        "measured compile accounting failed to detect a retrace"
    )


def test_generate_input_validation():
    """Out-of-grid batches raise a clear error pointing at fits() instead of
    crashing inside max()/_round_up (ADVICE r4)."""
    params = _params()
    gen = BucketedGenerator(CFG, max_new_tokens=8, pad_id=0, eos_id=None,
                            prompt_buckets=(32,), row_buckets=(8,),
                            decode_chunk=8)
    with pytest.raises(ValueError, match="empty sequence list"):
        gen.generate([], jax.random.PRNGKey(0), params)
    rng = np.random.default_rng(6)
    with pytest.raises(ValueError, match="fits"):
        gen.generate(_ragged(rng, 9, 4, 10), jax.random.PRNGKey(0), params)
    with pytest.raises(ValueError, match="fits"):
        gen.generate(_ragged(rng, 2, 40, 50), jax.random.PRNGKey(0), params)


def test_early_exit_skips_remaining_chunks():
    """When every row emits EOS early, decode stops within one chunk instead
    of burning max_new_tokens steps — the no-wasted-decode property."""
    params = _params()
    rng = np.random.default_rng(2)
    seqs = _ragged(rng, 4, 4, 12)
    # deterministic immediate EOS: with a zeroed embedding table every logit
    # is 0, greedy argmax is token 0 — declare THAT the eos token
    eos, pad = 0, 2
    forced = dict(params)
    forced["tok_emb"] = jnp.zeros_like(params["tok_emb"])
    gen = BucketedGenerator(CFG, max_new_tokens=64, pad_id=pad, eos_id=eos,
                            prompt_buckets=(16,), row_buckets=(8,),
                            decode_chunk=8)
    comp, cmask, info = gen.generate(seqs, jax.random.PRNGKey(3), forced,
                                     greedy=True)
    # every row emits EOS at the very first token -> zero decode chunks run
    assert info["decode_steps"] == 1, info
    assert comp.shape == (4, 64) and cmask.shape == (4, 64)
    # mask covers up to/including first EOS only
    assert (cmask.sum(axis=1) <= 1).all()

    # mixed case: real params, but declare eos = the token greedy decode
    # emits at step 3 for row 0 — decode must stop within one chunk of the
    # LAST row finishing, strictly before all 8 chunks
    base_gen = BucketedGenerator(CFG, max_new_tokens=64, pad_id=pad,
                                 eos_id=None, prompt_buckets=(16,),
                                 row_buckets=(8,), decode_chunk=8)
    free, _, _ = base_gen.generate(seqs, jax.random.PRNGKey(3), params,
                                   greedy=True)
    eos2 = int(free[0, 3])
    gen2 = BucketedGenerator(CFG, max_new_tokens=64, pad_id=pad, eos_id=eos2,
                             prompt_buckets=(16,), row_buckets=(8,),
                             decode_chunk=8)
    # does every row emit eos2 somewhere? only assert early exit when so
    if all((free[i] == eos2).any() and int(np.argmax(free[i] == eos2)) < 40
           for i in range(len(seqs))):
        _, _, info2 = gen2.generate(seqs, jax.random.PRNGKey(3), params,
                                    greedy=True)
        assert info2["decode_steps"] < 64, info2


def test_grpo_get_action_uses_bucketed_path():
    """GRPO routes ragged prompt batches through the bucketed generator:
    repeated calls with different (B, P) stay within the bucket compile
    bound and report telemetry."""
    from agilerl_tpu.algorithms.grpo import GRPO

    agent = GRPO(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
                 batch_size=4, max_output_tokens=8, seed=0)
    assert agent.bucketed_decode
    rng = np.random.default_rng(3)
    for B, P in [(2, 10), (3, 14), (2, 21)]:
        ids = rng.integers(3, 95, size=(B, P)).astype(np.int32)
        mask = np.ones((B, P), np.int32)
        comp, cmask = agent.get_action({"input_ids": ids,
                                        "attention_mask": mask})
        assert comp.shape == (B * 2, 8) and cmask.shape == (B * 2, 8)
    info = agent.last_generation_info
    assert info is not None and info["compiled_programs"] <= 2
    # greedy eval path works too
    comp, cmask = agent.get_action(
        {"input_ids": ids, "attention_mask": mask}, training=False)
    assert comp.shape == (2, 8)


def test_grpo_dense_fallback_by_constructor_flag():
    from agilerl_tpu.algorithms.grpo import GRPO

    agent = GRPO(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
                 batch_size=4, max_output_tokens=8, seed=0,
                 bucketed_decode=False)
    assert not agent.bucketed_decode and not agent.continuous_decode
    ids = np.random.default_rng(0).integers(3, 95, size=(2, 10)).astype(np.int32)
    comp, cmask = agent.get_action({"input_ids": ids,
                                    "attention_mask": np.ones_like(ids)})
    assert comp.shape == (4, 8)
    assert agent.last_generation_info is None  # the dense generate ran


def test_grpo_row_overflow_falls_back_to_dense():
    """More rows than the largest row bucket must route to the dense path
    (not crash in _round_up) and clear stale bucketed telemetry."""
    from agilerl_tpu.algorithms.grpo import GRPO

    agent = GRPO(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
                 batch_size=4, max_output_tokens=8, seed=0)
    gen = agent._get_bucketed_generator()
    assert not gen.fits(gen.row_buckets[-1] + 1, 10)
    rng = np.random.default_rng(4)
    # seed telemetry with a bucketed call first
    ids = rng.integers(3, 95, size=(2, 10)).astype(np.int32)
    agent.get_action({"input_ids": ids, "attention_mask": np.ones_like(ids)})
    assert agent.last_generation_info is not None
    # overflow rows: B*G = (row_bucket+2) -> dense, telemetry cleared
    nb = gen.row_buckets[-1] // 2 + 1
    ids = rng.integers(3, 95, size=(nb, 10)).astype(np.int32)
    comp, cmask = agent.get_action(
        {"input_ids": ids, "attention_mask": np.ones_like(ids)})
    assert comp.shape == (nb * 2, 8)
    assert agent.last_generation_info is None


def test_greedy_parity_with_scan_layers_off():
    """The layer loop calling every layer over the STACKED cache
    (``scan_layers=False``) must emit exactly the same tokens as the
    scanned path."""
    params = _params()
    rng = np.random.default_rng(3)
    seqs = _ragged(rng, 4, 4, 20)
    gen = BucketedGenerator(CFG, max_new_tokens=12, pad_id=0, eos_id=None,
                            prompt_buckets=(32,), row_buckets=(4,),
                            decode_chunk=6)
    comp, cmask, _ = gen.generate(seqs, jax.random.PRNGKey(2), params,
                                  greedy=True)
    gen2 = BucketedGenerator(dataclasses.replace(CFG, scan_layers=False),
                             max_new_tokens=12, pad_id=0, eos_id=None,
                             prompt_buckets=(32,), row_buckets=(4,),
                             decode_chunk=6)
    comp2, cmask2, _ = gen2.generate(seqs, jax.random.PRNGKey(2), params,
                                     greedy=True)
    np.testing.assert_array_equal(comp, comp2)
    np.testing.assert_array_equal(cmask, cmask2)
