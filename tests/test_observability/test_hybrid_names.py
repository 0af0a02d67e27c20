"""What a hybrid stack adds to the tracing: the four names inside its
programs (``ssm/scan``, ``ssm/scan_bwd``, ``ssm/conv``, ``ssm/step``), the
host phase ``sched/state_restore`` and the recurrent-state cache's counters
(docs/observability.md); and the paged forward's own name, ``paged/attend``,
in the decode chunk of a hybrid and of a dense stack."""

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu import observability
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.serving import ContinuousGenerator

CFG = M.GPTConfig(
    vocab_size=61, n_layer=4, n_head=2, n_kv_head=1, d_model=16, d_ff=32,
    max_seq_len=64, rope=False, attn_layer_period=4, attn_layer_offset=1,
    mamba_d_state=4, mamba_dt_rank=2, dtype=jnp.float32)


def text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_the_programs_carry_the_four_names():
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    toks = jnp.ones((1, 12), jnp.int32)
    forward = text(lambda p: M.token_logprobs(CFG, p, toks), params)
    assert "ssm/conv" in forward and "ssm/scan" in forward
    assert "ssm/scan_bwd" not in forward and "ssm/step" not in forward
    lora = M.init_lora(jax.random.PRNGKey(1), CFG, 2, ("in_proj",))
    grad = text(jax.grad(lambda lo: M.token_logprobs(
        CFG, params, toks, lora=lo).sum()), lora)
    assert "ssm/scan_bwd" in grad
    cache = M.init_caches(CFG, 1, 16)
    step = text(lambda p, c: M.forward(CFG, p, toks[:, :1], cache=c)[0],
                params, cache)
    assert "ssm/step" in step and "ssm/scan" not in step


def paged_step_text(config, slots=2, bs=8, mb=3):
    """One paged decode step (the body of the decode chunk) over a pool."""
    params = M.init_params(jax.random.PRNGKey(0), config)
    pool = M.init_paged_cache(config, 1 + slots * mb, bs, slots=slots,
                              snapshots=1)
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    return text(lambda p, c: M.forward_paged(
        config, p, ints(slots, 1), ints(slots), ints(slots), c,
        ints(slots, mb), ints(slots, mb * bs))[0], params, pool)


def test_the_paged_decode_step_carries_paged_attend():
    """``paged_attend_share`` reads this name; a hybrid stack's step has it
    in its attention layers beside ``ssm/step`` in the others, a dense
    stack's in every layer, a contiguous cache's step nowhere."""
    hybrid = paged_step_text(CFG)
    assert "paged/attend" in hybrid and "ssm/step" in hybrid
    dense = M.GPTConfig(vocab_size=61, n_layer=2, n_head=4, n_kv_head=2,
                        d_model=16, d_ff=32, max_seq_len=64,
                        dtype=jnp.float32)
    step = paged_step_text(dense)
    assert "paged/attend" in step and "ssm/step" not in step
    params = M.init_params(jax.random.PRNGKey(0), dense)
    cache = M.init_caches(dense, 1, 16)
    contiguous = text(lambda p, c: M.forward(
        dense, p, jnp.ones((1, 1), jnp.int32), cache=c)[0], params, cache)
    assert "paged/attend" not in contiguous


def test_a_hit_is_one_state_restore_phase_and_one_restore_count():
    reg = observability.MetricsRegistry()
    gen = ContinuousGenerator(
        CFG, max_new_tokens=4, prompt_buckets=(8,), slots=2, block_size=8,
        decode_chunk=4, metrics=reg)
    prompt = np.arange(3, 9, dtype=np.int32)
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    _, _, info = gen.generate([prompt] * 3, jax.random.PRNGKey(2), params)
    assert info["prefix_hit_rows"] == [False, True, True]
    dump = reg.dump()
    assert dump["histograms"]["sched/state_restore"]["count"] == 2
    assert dump["counters"]["serving/state_snapshot_restores_total"] == 2
    assert dump["counters"]["serving/state_snapshots_stored_total"] == 1
    assert dump["gauges"]["serving/state_cache_bytes"] > 0
