"""What a hybrid stack adds to the tracing: the four names inside its
programs (``ssm/scan``, ``ssm/scan_bwd``, ``ssm/conv``, ``ssm/step``), the
host phase ``sched/state_restore`` and the recurrent-state cache's counters
(docs/observability.md)."""

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
