"""What a stack that mixes window and full attention adds to the tracing
(docs/observability.md): ``attn/win`` / ``attn/full`` around a learn-path
attention block by kind, ``paged/attend_win`` inside ``paged/attend`` for a
window layer's loop of the paged forward, ``moe/score`` around a router fed
from outside the FFN, the windowed kernels' own names, and the two gauges
(``tests/test_llm/test_swa_moe_stack.py`` asserts their values). The
per-layer readers match these strings (``perfbench/layer_metrics/
attn_learn_share.py``, ``_flash_swa.py``)."""

import re

import jax
import jax.numpy as jnp

from agilerl_tpu.llm import model as M
from agilerl_tpu.llm import moe
from agilerl_tpu.ops import decode_attention as D

LAYOUT = (0, 1, 1, 1) * 2
CFG = M.GPTConfig(
    vocab_size=64, n_layer=8, n_head=4, n_kv_head=2, head_size=8, d_model=32,
    max_seq_len=64, tie_embeddings=False, sliding_window=8,
    window_layout=LAYOUT, rope_layout=LAYOUT, n_experts=4, expert_top_k=2,
    capacity_factor=None, d_ff_expert=16, expert_act="relu",
    router_input="attn", dtype=jnp.float32)


def text(fn, *args):
    """The compiled program's text: an operation's ``op_name`` there is the
    whole name stack, which is what a device trace's ``tf_op`` carries."""
    return jax.jit(fn).lower(*args).compile().as_text()


def scanned(program, name):
    """The name on an operation INSIDE the period scan's body."""
    return re.search(r'op_name="[^"]*while/body[^"]*' + re.escape(name),
                     program)


def test_the_names_are_what_the_readers_match():
    assert (M.WINDOW_SCOPE, M.FULL_SCOPE) == ("attn/win", "attn/full")
    assert (D.PAGED_SCOPE, D.PAGED_WINDOW_SCOPE) == (
        "paged/attend", "paged/attend_win")
    assert D.PAGED_WINDOW_SCOPE.startswith(D.PAGED_SCOPE)
    assert moe.SCORE_SCOPE == "moe/score"


def test_the_learn_program_names_attention_by_kind_through_the_scan():
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), CFG))
    lora = jax.eval_shape(lambda: M.init_lora(jax.random.PRNGKey(1), CFG, 2))
    toks = jnp.ones((2, 24), jnp.int32)
    cfg = CFG.__class__(**{**CFG.__dict__, "remat": True})
    grad = text(jax.grad(lambda lo, p: M.token_logprobs(
        cfg, p, toks, lora=lo, return_aux=True)[0].sum()), lora, params)
    for name in ("attn/win", "attn/full", "moe/score", "moe/route",
                 "moe/experts", "moe/combine"):
        assert scanned(grad, name), name
    # the router's early logits are the router's, not the attention block's
    assert not re.search(r'op_name="[^"]*attn/(win|full)[^"]*moe/score', grad)
    # a stack without variants carries neither name
    plain = M.GPTConfig(vocab_size=64, n_layer=2, n_head=4, d_model=32,
                        max_seq_len=64, dtype=jnp.float32)
    plain_text = text(lambda p: M.token_logprobs(plain, p, toks),
                      jax.eval_shape(lambda: M.init_params(
                          jax.random.PRNGKey(0), plain)))
    assert "attn/full" not in plain_text and "attn/win" not in plain_text


def test_the_paged_forward_names_a_window_layers_loop():
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), CFG))
    pool = jax.eval_shape(lambda: M.init_paged_cache(CFG, 9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    program = text(lambda p, t, pos, c, tab, sm: M.forward_paged(
        CFG, p, t, pos, pos, c, tab, sm, return_aux=True)[0],
        params, i32(3, 1), i32(3), pool, i32(3, 4), i32(3, 32))
    assert scanned(program, "paged/attend/paged/attend_win")
    # the global layers' loop is under paged/attend alone
    assert re.search(r'op_name="[^"]*paged/attend/(?!paged/attend_win)',
                     program)
    assert scanned(program, "moe/score")
    # learn carries no paged name, the cached forward no attention scope
    cache = jax.eval_shape(lambda: M.init_caches(CFG, 2, 32))
    prefill = text(lambda p, t, c: M.forward(CFG, p, t, cache=c)[0],
                   params, i32(2, 16), cache)
    assert "attn/win" not in prefill and "paged/attend" not in prefill


def test_the_windowed_kernels_carry_their_own_names():
    from agilerl_tpu.ops.flash_attention_vjp import flash_attention_diff

    q = jnp.ones((1, 2, 64, 8))
    both = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention_diff(
        q, q, q, None, True, 16, 16, None, True, 24).sum()))(q))
    for name in ("flash_fwd_win", "flash_dq_win", "flash_dkv_win"):
        assert name in both, name
    plain = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention_diff(
        q, q, q, None, True, 16, 16).sum()))(q))
    assert "_win" not in plain and "flash_dkv" in plain
