"""What compressed convolutional attention and the router MLP add to the
tracing: four names inside their programs — ``cca/project`` (the
projections), ``cca/mix`` (convolutions, mean, norms, temperature, rotary,
value shift), ``cca/state`` (the read and write of the rolling state) and
``moe/score`` (the router MLP) — in the learn, prefill and decode programs,
through the layer scan, and NOT under ``moe/route``, which keeps meaning
sort, gather and un-sort (docs/observability.md). The per-layer readers
match these strings in an operation's ``op_name``
(``perfbench/layer_metrics/cca_mix_share.py``, ``router_mlp_share.py``)."""

import re

import jax
import jax.numpy as jnp
import pytest

from agilerl_tpu.llm import cca, moe
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset

CFG = preset("tiny-cca-moe", dtype=jnp.float32, remat=False,
             use_flash_attention=False)
NAMES = (cca.PROJECT_SCOPE, cca.MIX_SCOPE, cca.STATE_SCOPE, moe.SCORE_SCOPE,
         "moe/route", "moe/experts", "moe/combine")


def text(fn, *args):
    """The compiled program's text: an operation's ``op_name`` there is the
    whole name stack, which is what a device trace's ``tf_op`` carries."""
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


def test_the_scope_names_are_what_the_readers_match():
    assert (cca.PROJECT_SCOPE, cca.MIX_SCOPE, cca.STATE_SCOPE,
            moe.SCORE_SCOPE) == ("cca/project", "cca/mix", "cca/state",
                                 "moe/score")
    assert not moe.SCORE_SCOPE.startswith(moe.ROUTE_SCOPE)


def scanned(program, name):
    """The name on an operation INSIDE the layer scan's body."""
    return re.search(r'op_name="[^"]*while/body[^"]*' + re.escape(name),
                     program)


@pytest.mark.parametrize("remat", [False, True])
def test_the_learn_program_carries_the_names_through_the_scan(params, remat):
    cfg = CFG if not remat else preset(
        "tiny-cca-moe", dtype=jnp.float32, remat=True,
        use_flash_attention=False)
    toks = jnp.ones((2, 12), jnp.int32)
    grad = text(jax.grad(lambda lo, p: M.token_logprobs(
        cfg, p, toks, lora=lo).sum()),
        M.init_lora(jax.random.PRNGKey(1), cfg, 2, ("wq", "wv1", "wv2")),
        params)
    for name in NAMES:
        assert scanned(grad, name), name
    # the backward of the mix and of the router MLP keep the names
    assert re.search(r'transpose\(jvp\([^"]*cca/mix', grad)
    assert re.search(r'transpose\(jvp\([^"]*moe/score', grad)
    # the router MLP is not filed under the dispatch
    assert "moe/route/moe/score" not in grad
    assert "moe/score/moe/route" not in grad


def test_prefill_and_decode_programs_carry_the_names(params):
    toks = jnp.ones((1, 12), jnp.int32)
    cache = M.init_caches(CFG, 1, 16)
    prefill = text(lambda p, c: M.forward(CFG, p, toks, cache=c),
                   params, cache)
    slots, bs, mb = 2, 8, 3
    pool = M.init_paged_cache(CFG, 1 + slots * mb, bs, slots=slots,
                              snapshots=slots)
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    decode = text(lambda p, c: M.forward_paged(
        CFG, p, ints(slots, 1), ints(slots), ints(slots), c,
        ints(slots, mb), ints(slots, mb * bs)), params, pool)
    for program in (prefill, decode):
        for name in NAMES:
            assert scanned(program, name), name
    assert "paged/attend" in decode


def test_a_learn_program_without_a_state_still_names_where_it_would_be(params):
    """No cache, no state: ``cca/state`` then holds the zero windows the
    convolutions start from, so the name is in every program of the stack."""
    toks = jnp.ones((1, 12), jnp.int32)
    forward = text(lambda p: M.token_logprobs(CFG, p, toks), params)
    assert "cca/state" in forward and "cca/mix" in forward
