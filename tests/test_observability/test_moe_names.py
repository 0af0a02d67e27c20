"""What latent attention and the dropless expert layer add to the tracing:
the seven names inside their programs (``moe/route``, ``moe/experts``,
``moe/shared``, ``moe/combine``, ``mla/expand``, ``mla/absorb``,
``mla/attend``), the serving tier's two counters of the experts a decode
step touched and the pool's gauge, and the learn step's gauge
``moe/load_max_over_mean`` (docs/observability.md). A dense stack's decode
chunk keeps its outputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.llm.serving import ContinuousGenerator

CFG = preset("tiny-mla-moe", dtype=jnp.float32, remat=False,
             use_flash_attention=False)
MOE = ("moe/route", "moe/experts", "moe/shared", "moe/combine")


def text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


def test_the_programs_carry_the_seven_names(params):
    toks = jnp.ones((1, 12), jnp.int32)
    forward = text(lambda p: M.token_logprobs(CFG, p, toks), params)
    assert all(name in forward for name in MOE + ("mla/expand",))
    assert "mla/absorb" not in forward and "mla/attend" not in forward
    cache = M.init_caches(CFG, 1, 16)
    step = text(lambda p, c: M.forward(CFG, p, toks[:, :1], cache=c)[0],
                params, cache)
    assert all(name in step for name in MOE + ("mla/absorb", "mla/attend"))
    assert "mla/expand" not in step


def test_the_paged_forward_attends_under_both_names(params):
    """Over a latent pool the chunk loop runs under latent attention's own
    ``mla/attend`` AND the paged forward's ``paged/attend`` (the name
    ``paged_attend_share`` reads), with ``mla/absorb`` around it."""
    slots, bs, mb = 2, 8, 3
    pool = M.init_paged_cache(CFG, 1 + slots * mb, bs)
    assert pool.v is None
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    step = text(lambda p, c: M.forward_paged(
        CFG, p, ints(slots, 1), ints(slots), ints(slots), c,
        ints(slots, mb), ints(slots, mb * bs))[0], params, pool)
    assert "mla/attend/jit(chunked_paged_attention)" in step
    assert "paged/attend/while" in step and "mla/absorb" in step
    assert all(name in step for name in MOE) and "mla/expand" not in step


def generator(config, reg):
    return ContinuousGenerator(
        config, max_new_tokens=4, prompt_buckets=(8,), slots=2, block_size=8,
        decode_chunk=4, metrics=reg)


def test_a_decode_chunk_counts_the_experts_it_touched(params):
    reg = observability.MetricsRegistry()
    gen = generator(CFG, reg)
    prompt = np.arange(3, 9, dtype=np.int32)
    gen.generate([prompt] * 2, jax.random.PRNGKey(2), params)
    dump = reg.dump()
    # one chunk of 4 steps x 2 expert layers x 8 experts
    assert dump["counters"]["serving/moe_expert_slots_total"] == 64
    assert 4 * 2 * 2 <= dump["counters"]["serving/moe_experts_hit_total"] <= 64
    assert dump["gauges"]["serving/pool_block_bytes"] == \
        3 * 8 * (CFG.kv_lora_rank + CFG.qk_rope_dim) * 4


def test_a_dense_stacks_decode_chunk_has_no_such_output():
    dense = M.GPTConfig(vocab_size=61, n_layer=2, n_head=2, d_model=16,
                        d_ff=32, max_seq_len=64, dtype=jnp.float32)
    reg = observability.MetricsRegistry()
    gen = generator(dense, reg)
    gen.generate([np.arange(3, 9, dtype=np.int32)], jax.random.PRNGKey(2),
                 M.init_params(jax.random.PRNGKey(0), dense))
    assert not [k for k in reg.dump()["counters"] if "moe" in k]


def test_learn_sets_the_load_gauge(params):
    gauge = observability.get_registry().gauge("moe/load_max_over_mean")
    gauge.set(0.0)
    agent = GRPO(config=CFG, base_params=params, pad_token_id=0,
                 eos_token_id=1, group_size=2, batch_size=2,
                 max_output_tokens=4, lora_rank=2,
                 lora_targets=("wq", "wkv_b"), seed=0)
    ids = np.random.default_rng(0).integers(3, 200, size=(2, 12)).astype(np.int32)
    action = np.zeros((2, 11), np.int32)
    action[:, 7:] = 1
    agent.learn((ids, action, np.asarray([[1.0, -1.0]], np.float32)))
    # 24 positions x 2 choices over 8 experts: the fullest holds at least
    # the mean and at most everything
    assert 1.0 <= gauge.value <= 8.0
