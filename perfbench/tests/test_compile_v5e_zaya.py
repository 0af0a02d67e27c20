"""The chip-sized programs of ``zaya1-8b``, compiled by the TPU's own
compiler for a described ``v5e:2x2`` (no chip attached) at the depth and
widths its configuration file states: the learn step and the rollout tier's
decode chunk and prefill. Asserts that each program's planned bytes — the
resident frozen base among them — stay under ``PLAN_LIMIT`` (15.5 GB of the
16.91 GB a chip has: room for the other loaded programs' arguments and the
pool), that the fused loss and flash attention stay kernels at a 262272-wide
tied head and a head size of 128 under ``d_model // n_head``, and that the
scopes the benchmark's readers look for are in the compiled text. Bytes the
compiler plans, not a chip run: nothing runs.

``DEPTH_SWEEP=1`` also compiles the learn step at every candidate depth and
prints the bytes: the sweep that chose n (PERF.md section 4 has its
output). Run with ``-s`` to see the bytes.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the described topology (a fixture: nothing is described at import), the
# byte count and the chip's limit are test_compile_v5e.py's
from perfbench.tests.test_compile_v5e import (  # noqa: F401
    V5E_BYTES_LIMIT, load, planned_bytes, topo)

PLAN_LIMIT = 15.5e9
CANDIDATES = (24, 22, 20, 18, 16)


def build(topo, n_layers=None):
    """Shapes of the base (made as the runner makes it), the agent and the
    generator, on the first described chip."""
    from agilerl_tpu.llm.serving import ContinuousGenerator
    from perfbench import traffic
    from perfbench.runners import _llm, grpo_loop_cca_moe as runner

    config, mix = load("zaya1-8b"), load("reason_256x768_g8", "traffic")
    cfg = runner.gpt_config(config)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layer=n_layers)
    one_chip = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    base = on(jax.eval_shape(lambda: runner.make_base(cfg, 0)))
    rows = int(mix["prompts_per_step"]) * int(mix["group_size"])
    agent = _llm.make_agent(
        cfg, base, 0, config, traffic.IdTokenizer(),
        group_size=int(mix["group_size"]), rows=rows,
        new_tokens=int(mix["new_tokens"]))
    gen = ContinuousGenerator(
        cfg, max_new_tokens=int(mix["new_tokens"]), temperature=0.9,
        capture_logprobs=True, **config["serving"])
    return dict(cfg=cfg, base=base, agent=agent, gen=gen, on=on, rows=rows,
                seq=int(mix["prompt_tokens"][1]) + int(mix["new_tokens"]))


@pytest.fixture(scope="module")
def cell(topo):
    return build(topo)


def report(what, compiled, base):
    total = planned_bytes(compiled)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(base))
    print(f"\nzaya1-8b {what}: {total / 1e9:.2f} GB planned, of which the "
          f"frozen base {weights / 1e9:.2f} GB")
    return total


def compile_update(cell):
    from agilerl_tpu.algorithms.grpo import make_update_fn
    from agilerl_tpu.ops.kernel_mode import native_kernels

    agent, on = cell["agent"], cell["on"]
    rows, seq = cell["rows"], cell["seq"]
    s = lambda shape, dtype: on(jax.ShapeDtypeStruct(shape, dtype))  # noqa: E731
    f32 = jnp.float32
    batch = {"tokens": s((rows, seq), jnp.int32), "mask": s((rows, seq), jnp.int32),
             "loss_mask": s((rows, seq - 1), f32), "old_lp": s((rows, seq - 1), f32),
             "ref_lp": s((rows, seq - 1), f32), "advantage": s((rows,), f32)}
    with native_kernels():
        update = make_update_fn(cell["cfg"], agent.optimizer.tx,
                                agent.lora_scale, use_flash=True)
        return update.lower(
            cell["base"], on(agent.actor.params), on(agent.optimizer.opt_state),
            batch, s((), f32), s((), f32)).compile()


def test_learn_step_fits_a_chip_at_the_stated_depth(cell):
    cfg = cell["cfg"]
    assert cfg.layer_runs() == [("attn", 0, cfg.n_layer)]  # one scan body
    compiled = compile_update(cell)
    assert report("learn step (update)", compiled, cell["base"]) < PLAN_LIMIT
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5  # flash fwd/dQ/dKV, loss fwd/dH
    for scope in ("cca/project", "cca/mix", "cca/state", "moe/score",
                  "moe/route", "moe/experts", "moe/combine"):
        assert scope in text, scope
    assert "ragged-dot" in text or "RaggedDot" in text or "ragged_dot" in text


def test_decode_chunk_and_prefill_fit_a_chip_at_the_stated_depth(cell):
    from agilerl_tpu.llm import model as M

    gen, on, cfg = cell["gen"], cell["on"], cell["cfg"]
    pool = on(jax.eval_shape(lambda: M.init_paged_cache(
        cfg, gen.n_blocks, gen.block_size, slots=gen.slots,
        snapshots=gen.slots)))
    assert pool.k.shape[2:] == pool.v.shape[2:] == (32, 2, 128)
    (p_win, c0_win, v_prev), = pool.state
    assert p_win.shape == (cfg.n_layer, gen.slots, 1, 1280)
    assert v_prev.shape == (cfg.n_layer, gen.slots, 128)
    lora = on(cell["agent"].actor.params)
    a = lambda shape, dtype: on(jax.ShapeDtypeStruct(shape, dtype))  # noqa: E731
    S = gen.max_blocks * gen.block_size
    n = gen.slots
    decode = gen._decode.lower(
        cell["base"], lora, pool, a((n, gen.max_blocks), jnp.int32),
        a((n, S), jnp.int32), a((n,), jnp.int32), a((n,), jnp.int32),
        a((n,), jnp.bool_), a((n,), jnp.int32), a((n,), jnp.int32),
        a((n,), jnp.bool_), a((n, 2), jnp.uint32), greedy=False).compile()
    assert report("decode chunk", decode, cell["base"]) < PLAN_LIMIT
    text = decode.as_text()
    for scope in ("cca/project", "cca/mix", "cca/state", "moe/score",
                  "moe/route", "moe/experts", "paged/attend"):
        assert scope in text, scope
    Pb = 256
    prefill = gen._prefill.lower(
        cell["base"], lora, a((1, Pb), jnp.int32), a((1, Pb), jnp.int32),
        a((2,), jnp.uint32), pool, a((Pb // gen.block_size,), jnp.int32),
        greedy=False, state_ids=a((2,), jnp.int32)).compile()
    assert report("prefill at 256", prefill, cell["base"]) < PLAN_LIMIT
    assert "cca/state" in prefill.as_text()


@pytest.mark.skipif(not os.environ.get("DEPTH_SWEEP"),
                    reason="the sweep that chose the depth: DEPTH_SWEEP=1")
def test_depth_sweep(topo):
    chosen = None
    for n in CANDIDATES:
        cell = build(topo, n)
        total = report(f"learn step at {n} layers", compile_update(cell),
                       cell["base"])
        if chosen is None and total < PLAN_LIMIT:
            chosen = n
    assert chosen == load("zaya1-8b")["num_hidden_layers"]
