"""The device half of tracing (docs/observability.md, "Device scopes"):
``observability.device_scope`` puts a name on a part of a program twice —
as a ``jax.named_scope``, which a profile's reader finds, and as a frontend
attribute on the operations, which JAX's persistent compilation cache cannot
strip. Per scope: the program that should carry it does, in both forms, and
no block became a function of the module; a persistent cache misses on the
scoped program after it has run the bare one; the three flash-attention
kernels carry their names into the learn program; and with the helper made
a no-op the decode chunk's tokens and log-probabilities, a generation's
fitness and parameters and the paged forward's lowered text are what they
were."""

import contextlib
import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from agilerl_tpu import observability
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.llm.serving import ContinuousGenerator
from agilerl_tpu.parallel import population
from tests.test_llm.test_cca_moe_stack import (DECODE_WITHOUT_SCOPES, STACKS,
                                               lowered)
from tests.test_parallel.test_population import make_evo

# the package exports a function of the same name
G = importlib.import_module("agilerl_tpu.llm.generate")

DENSE = M.GPTConfig(vocab_size=61, n_layer=2, n_head=4, n_kv_head=2,
                    d_model=16, d_ff=32, max_seq_len=64, dtype=jnp.float32)
HYBRID = M.GPTConfig(
    vocab_size=61, n_layer=4, n_head=2, n_kv_head=1, d_model=16, d_ff=32,
    max_seq_len=64, rope=False, attn_layer_period=4, attn_layer_offset=1,
    mamba_d_state=4, mamba_dt_rank=2, dtype=jnp.float32)
CONFIGS = {
    "dense": lambda: DENSE, "hybrid": lambda: HYBRID,
    "mla-moe": lambda: preset("tiny-mla-moe", dtype=jnp.float32),
    "cca-moe": lambda: preset("tiny-cca-moe", dtype=jnp.float32),
}


def no_scopes(monkeypatch):
    """The helper made a no-op at its three call sites."""
    for module in (M, G, population):
        monkeypatch.setattr(module, "device_scope",
                            lambda name: contextlib.nullcontext())


def generator(config):
    return ContinuousGenerator(
        config, max_new_tokens=8, prompt_buckets=(8,), slots=2, block_size=8,
        decode_chunk=4, pad_id=0, eos_id=1, temperature=0.9,
        capture_logprobs=True, metrics=observability.MetricsRegistry())


def decode_chunk_lowered(config):
    """The decode-chunk program as the scheduler compiles it."""
    gen = generator(config)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: M.init_params(key, config))
    slots = ({"slots": gen.slots, "snapshots": 1}
             if config.state_kind is not None else {})
    pool = jax.eval_shape(lambda: M.init_paged_cache(
        config, gen.n_blocks, gen.block_size, **slots))
    s = jax.ShapeDtypeStruct
    n, extent = gen.slots, gen.max_blocks * gen.block_size
    state = (s((n, gen.max_blocks), jnp.int32), s((n, extent), jnp.int32),
             s((n,), jnp.int32), s((n,), jnp.int32), s((n,), jnp.bool_),
             s((n,), jnp.int32), s((n,), jnp.int32), s((n,), jnp.bool_),
             s((n, 2), jnp.uint32))
    return gen._decode.lower(params, None, pool, *state, greedy=False)


def generation_lowered():
    evo = make_evo()
    pop = jax.eval_shape(
        lambda: evo.init_population(jax.random.PRNGKey(0), pop_size=2))
    return evo.make_vmap_generation().lower(pop, jax.random.PRNGKey(1))


PROGRAMS = {"decode": lambda: decode_chunk_lowered(DENSE),
            "generation": generation_lowered}
SCOPES = [("decode", M.PROJ_SCOPE), ("decode", M.FFN_SCOPE),
          ("decode", G.HEAD_SCOPE), ("generation", population.ROLLOUT_SCOPE),
          ("generation", population.SHUFFLE_SCOPE),
          ("generation", population.UPDATE_SCOPE)]


@pytest.fixture(scope="module")
def texts():
    """program -> (text with debug info, text without: what the cache's key
    is made of)."""
    out = {}
    for name, lower in PROGRAMS.items():
        low = lower()
        out[name] = (low.as_text(debug_info=True), low.as_text())
    return out


@pytest.mark.parametrize("program,scope", SCOPES)
def test_the_program_carries_the_scope_for_a_profiles_reader(
        texts, program, scope):
    # in a location, the attribute apart: the compiled program's ``op_name``
    # (a profile's ``tf_op``, where perfbench/layer_metrics/_scopes.py looks)
    # is put together from the locations
    locations = texts[program][0].replace(f'scope = "{scope}"', "")
    assert re.search(rf'loc\("[^"]*{scope}[/)"]', locations)


@pytest.mark.parametrize("program,scope", SCOPES)
def test_the_scope_is_an_attribute_of_the_text_the_cache_is_keyed_on(
        texts, program, scope):
    """What fails if the helper becomes a bare ``named_scope``: a scope
    lives in the debug locations, which the persistent cache strips before
    it hashes a module, so the program would load an executable compiled
    before the scope was put on."""
    assert (f'mhlo.frontend_attributes = {{scope = "{scope}"}}'
            in texts[program][1])


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_no_scope_is_a_function_of_the_module(texts, program):
    """The helper wraps a block in no ``jax.jit``: a block that became
    ``func.func private @evo_rollout`` cost the generation program's first
    call 2-3 s on the chip (PERF.md section 6, PR 35). jax lowers a few of
    its own helpers (``_where``, ``_uniform``) once more per attribute
    context; those are not what this forbids."""
    functions = re.findall(r"func\.func \w* ?@([\w.]+)", texts[program][1])
    assert len(functions) > 3
    named = {scope.replace("/", "_") for _, scope in SCOPES}
    assert not [f for f in functions if any(n in f for n in named)]


@pytest.fixture
def cache_files(tmp_path):
    """JAX's persistent cache in a directory of the test's own, every
    program kept; yields a function that counts the executables in it."""
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path), True, 0.0, -1)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield lambda: len([p for p in tmp_path.iterdir()
                       if p.name.endswith("-cache")])
    for n, v in was.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("how,compiles_anew", [
    ("named_scope", False), ("device_scope", True)])
def test_a_cache_that_holds_the_bare_program_misses_on_the_scoped_one(
        cache_files, how, compiles_anew):
    """The fact the helper is built around, both halves: after a run of the
    bare program a bare ``named_scope`` finds every executable in the cache
    (the stale ones, which have no name), ``device_scope`` compiles the
    scan's program anew."""
    scopes = {"bare": contextlib.nullcontext,
              "named_scope": lambda: jax.named_scope("scan/body"),
              "device_scope": lambda: observability.device_scope("scan/body")}

    def run(scope):
        def body(c, _):
            with scope():
                return jnp.tanh(c @ c) + 1, None

        # a new function each time: jit's own cache is not under test
        out, _ = jax.jit(lambda c: jax.lax.scan(body, c, None, length=3))(
            jnp.eye(4))
        return np.asarray(out)

    bare = run(scopes["bare"])
    held = cache_files()
    assert held > 0
    np.testing.assert_array_equal(run(scopes[how]), bare)
    assert (cache_files() > held) == compiles_anew


@pytest.mark.parametrize("stack", sorted(CONFIGS))
def test_every_stacks_decode_chunk_has_the_three_names(stack):
    """In the compiled program's ``op_name``s, where a profile takes them
    from; the scopes that were there nest inside the new ones, whole."""
    config = CONFIGS[stack]()
    text = decode_chunk_lowered(config).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in (M.PROJ_SCOPE, M.FFN_SCOPE, G.HEAD_SCOPE):
        assert any(f"/{scope}/" in n for n in names), scope
    if config.is_dropless:
        assert any(f"/{M.FFN_SCOPE}/" in n and "moe/experts" in n
                   for n in names)
    if config.is_hybrid:  # the state-space layers' mixer is ssm/step's
        assert any("ssm/step" in n for n in names)
        assert not any("ssm/" in n and M.PROJ_SCOPE in n for n in names)


def test_prefill_and_learn_programs_keep_their_text():
    """The scopes sit at the paged path's call sites: a program that does
    not take ``forward_paged`` has none of them."""
    params = M.init_params(jax.random.PRNGKey(0), DENSE)
    toks = jnp.ones((1, 12), jnp.int32)
    for fn, args in (
            (lambda p: M.token_logprobs(DENSE, p, toks), (params,)),
            (lambda p, c: M.forward(DENSE, p, toks, cache=c)[0],
             (params, M.init_caches(DENSE, 1, 16)))):
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert "decode/" not in text and "frontend_attributes" not in text


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_the_learn_program_names_the_flash_attention_kernels(kernel):
    config = M.GPTConfig(
        vocab_size=61, n_layer=1, n_head=2, n_kv_head=1, d_model=16, d_ff=32,
        max_seq_len=64, dtype=jnp.float32, use_flash_attention=True)
    params = M.init_params(jax.random.PRNGKey(0), config)
    lora = M.init_lora(jax.random.PRNGKey(1), config, 2, ("wq",))
    toks = jnp.ones((1, 16), jnp.int32)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda lo: M.token_logprobs(
        config, params, toks, lora=lo, flash=True).sum()))(lora))
    assert f"name={kernel}" in jaxpr


@pytest.mark.parametrize("stack", ["dense", "hybrid"])
def test_decode_chunks_are_bit_equal_with_the_helper_a_no_op(
        stack, monkeypatch):
    config = CONFIGS[stack]()
    params = M.init_params(jax.random.PRNGKey(0), config)
    prompts = [np.arange(3, 9, dtype=np.int32),
               np.arange(5, 12, dtype=np.int32)]

    def rollout():
        toks, mask, info = generator(config).generate(
            prompts, jax.random.PRNGKey(2), params)
        return toks, mask, info["logprobs"]

    scoped = rollout()
    no_scopes(monkeypatch)
    bare = rollout()
    assert scoped[1].sum() > 8  # more than one chunk of real tokens
    for a, b in zip(scoped, bare):
        np.testing.assert_array_equal(a, b)


def test_a_generations_fitness_is_bit_equal_with_the_helper_a_no_op(
        monkeypatch):
    def generations():
        evo = make_evo(update_epochs=2)
        pop = evo.init_population(jax.random.PRNGKey(0), pop_size=3)
        gen = evo.make_vmap_generation()
        out = []
        for i in range(2):
            pop, fitness = gen(pop, jax.random.PRNGKey(10 + i))
            out.append(np.asarray(fitness))
        return out + [np.asarray(x) for x in jax.tree_util.tree_leaves(
            (pop.actor, pop.critic))]

    scoped = generations()
    no_scopes(monkeypatch)
    for a, b in zip(scoped, generations()):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("program", sorted(DECODE_WITHOUT_SCOPES))
def test_without_the_helper_the_paged_forward_lowers_to_its_old_text(
        program, monkeypatch):
    """The attributes are ALL that PR 36 changed in ``forward_paged``: with
    the helper a no-op the three stacks' paged forwards hash to what they
    hashed to at 13b37f2."""
    no_scopes(monkeypatch)
    text = lowered(STACKS[program.split("/")[0]](), "decode").as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == DECODE_WITHOUT_SCOPES[program]
