"""The chip-sized programs of ``smallthinker-21b-a3b``, compiled by the TPU's
own compiler for a described ``v5e:2x2`` (no chip attached) at the depth and
widths its configuration file states and the cell's sizes (2 rows x 8192
positions; a prompt bucket of 8192; a pool of 8 slots x 260 blocks): the
learn step, the no-grad pass, and the rollout tier's decode chunk and
prefill. Asserts that each program's planned bytes — the resident frozen
base among them — stay under ``PLAN_LIMIT`` (15.5 GB of the 16.91 GB a chip
has), that flash attention stays a kernel in BOTH its kinds (the windowed
executions carry ``flash_*_win``), that the fused loss stays one at an
untied 151936-wide head, and that the scopes the benchmark's readers look
for are in the compiled text. Bytes the compiler plans, not a chip run:
nothing runs.

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
CANDIDATES = (12, 8, 4)  # whole periods of [global, window, window, window]
NAME, MIX = "smallthinker-21b-a3b", "longdoc_8064x128_g2"


def build(topo, n_layers=None):
    """Shapes of the base (made as the runner makes it), the agent and the
    generator (built as ``GRPO`` builds it: no prompt grid passed), on the
    first described chip."""
    from agilerl_tpu.llm.serving import ContinuousGenerator
    from perfbench import traffic
    from perfbench.runners import _llm, grpo_loop_swa_moe as runner

    config, mix = load(NAME), load(MIX, "traffic")
    if n_layers is not None:
        config = {**config, "num_hidden_layers": n_layers}
    cfg = runner.gpt_config(config)
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
    print(f"\n{NAME} {what}: {total / 1e9:.2f} GB planned, of which the "
          f"frozen base {weights / 1e9:.2f} GB")
    return total


def compile_update(cell):
    from agilerl_tpu.algorithms.grpo import make_update_fn
    from agilerl_tpu.ops.kernel_mode import native_kernels

    agent, on = cell["agent"], cell["on"]
    rows, seq = cell["rows"], cell["seq"]
    s = lambda shape, dtype: on(jax.ShapeDtypeStruct(shape, dtype))  # noqa: E731
    f32 = jnp.float32
    # a call of one optimizer step anchors the ratio at its own
    # log-probabilities (PR 34): the batch carries no old_lp
    batch = {"tokens": s((rows, seq), jnp.int32), "mask": s((rows, seq), jnp.int32),
             "loss_mask": s((rows, seq - 1), f32),
             "ref_lp": s((rows, seq - 1), f32), "advantage": s((rows,), f32)}
    with native_kernels():
        update = make_update_fn(cell["cfg"], agent.optimizer.tx,
                                agent.lora_scale, use_flash=True)
        return update.lower(
            cell["base"], on(agent.actor.params), on(agent.optimizer.opt_state),
            batch, s((), f32), s((), f32)).compile()


def test_learn_step_fits_a_chip_at_the_stated_depth(cell):
    cfg = cell["cfg"]
    assert cfg.layer_runs() == [("attn", 0, cfg.n_layer)]
    assert cfg.run_period(0, cfg.n_layer) == 4  # one scan body: one period
    assert cell["seq"] == 8192 and cell["rows"] == 2
    compiled = compile_update(cell)
    assert report("learn step (update)", compiled, cell["base"]) < PLAN_LIMIT
    text = compiled.as_text()
    # flash fwd/dQ/dKV in both kinds, loss fwd/dH
    assert text.count("tpu_custom_call") >= 8
    for name in ("flash_fwd_win", "flash_dq_win", "flash_dkv_win",
                 "attn/win", "attn/full", "moe/score", "moe/route",
                 "moe/experts", "moe/combine", "fused_loss_fwd"):
        assert name in text, name
    assert "ragged-dot" in text or "RaggedDot" in text or "ragged_dot" in text


def test_decode_chunk_and_prefill_fit_a_chip_at_the_stated_depth(cell):
    from agilerl_tpu.llm import model as M

    gen, on, cfg = cell["gen"], cell["on"], cell["cfg"]
    # the grid follows from max_seq_len: a bucket of 8192, 260 blocks a slot
    assert gen.prompt_buckets[-1] == 8192 and gen.max_blocks == 260
    pool = on(jax.eval_shape(lambda: M.init_paged_cache(
        cfg, gen.n_blocks, gen.block_size)))
    assert pool.k.shape == pool.v.shape == (cfg.n_layer, 2081, 32, 4, 128)
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
    for scope in ("paged/attend", "paged/attend_win", "moe/score",
                  "moe/route", "moe/experts", "decode/ffn"):
        assert scope in text, scope
    Pb = 8192
    prefill = gen._prefill.lower(
        cell["base"], lora, a((1, Pb), jnp.int32), a((1, Pb), jnp.int32),
        a((2,), jnp.uint32), pool, a((Pb // gen.block_size,), jnp.int32),
        greedy=False).compile()
    assert report("prefill at 8192", prefill, cell["base"]) < PLAN_LIMIT


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
    assert chosen == load(NAME)["num_hidden_layers"]
