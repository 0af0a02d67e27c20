"""The main path's kernels and serving programs, compiled at qwen2-7b widths
(the selective scan's at jamba2-3b's) by the TPU's own compiler for a
described ``v5e:2x2`` (no chip attached).

Interpret mode accepts tilings and VMEM footprints that Mosaic refuses, and
``jax.default_backend()`` is ``cpu`` here, so each test hands the compiler
the kernel or the jitted step itself with shapes placed on the described
device. Nothing runs: a pass says the chip's compiler takes the program,
not that its result is right (the benchmark's ``correct`` checks that on
the chip: ``python3 perfbench/run.py --workload <cell>``).
Skipped where libtpu cannot describe the topology.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.llm.serving import ContinuousGenerator
from agilerl_tpu.ops.flash_attention_vjp import (
    flash_attention_diff,
    flash_plan,
)
from agilerl_tpu.ops.fused_loss import (
    fused_loss_plan,
    fused_token_logprob_diff,
)
from agilerl_tpu.ops.kernel_mode import native_kernels
from agilerl_tpu.ops.selective_scan import (SCAN_BWD_SCOPE, SCAN_SCOPE,
                                            scan_plan, selective_scan)

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def v5e():
    """Device 0 of a described v5e 2x2, with JAX's persistent compilation
    cache off: an entry written for a described device cannot be read back
    without a chip, and the next compile would warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described topology touches no device: do not take libtpu's lockfile
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "true")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(device, tree):
    s = SingleDeviceSharding(device)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree)


def _compiled_text(fn, *args, **kwargs) -> str:
    with native_kernels():
        return fn.lower(*args, **kwargs).compile().as_text()


@pytest.mark.parametrize("head_grad", [False, True],
                         ids=["frozen_head", "trained_head"])
@pytest.mark.parametrize("N,dtype", [
    (8192, jnp.bfloat16),  # grpo_k4_reason: 8 x 1024 positions, bf16 compute
    (9216, jnp.bfloat16),  # grpo_k4_longprompt: 8 x 1152
    (4096, jnp.float32),   # a float32 configuration; a chip's rows of fsdp-4
], ids=["8192-bf16", "9216-bf16", "4096-f32"])
def test_fused_loss_fwd_and_grad_at_qwen2_7b_head(v5e, N, dtype, head_grad):
    # the learn step's lm head as token_logprobs hands it over: hidden and
    # the untied 152064-wide head in the configuration's compute dtype
    D, V = 3584, 152064
    s = jax.ShapeDtypeStruct
    h, w, t = _on(v5e, (s((N, D), dtype), s((D, V), dtype),
                        s((N,), jnp.int32)))

    def loss(hh, ww, tt):
        return fused_token_logprob_diff(hh, ww, tt, 1.0).sum()

    argnums = (0, 1) if head_grad else 0
    text = _compiled_text(jax.jit(jax.value_and_grad(loss, argnums=argnums)),
                          h, w, t)
    # forward, dH, and dW only for a caller that trains the head: XLA drops
    # the kernel of a cotangent nobody asks for
    kinds = ("fwd", "dh", "dw") if head_grad else ("fwd", "dh")
    assert text.count(KERNEL) == len(kinds)
    for kind in kinds:
        assert f"fused_loss_{kind}" in text
        plan = fused_loss_plan(N, D, V, dtype, dtype, kind)
        print(f"\n  {kind}: {plan.block_n} x {plan.block_v}, head read "
              f"{plan.head_reads}x, {plan.hbm_bytes / 1e9:.2f} GB through HBM,"
              f" {plan.vmem_bytes / 2**20:.1f} MiB of VMEM a grid step "
              f"(limit asked {plan.vmem_limit_bytes / 2**20:.0f} MiB)", end="")
    # the head goes to the kernels as it is: no padded width anywhere, and
    # no operation that pads or copies an array of the head's shape
    assert set(re.findall(r"\[3584,(\d+)\]", text)) == {str(V)}
    moved = [line for line in text.splitlines()
             if re.search(r"= \w+\[3584,152064\]\S* (pad|copy)\(", line)]
    assert not moved, moved


@pytest.mark.parametrize("B,H,T,d,dv,spmd", [
    # the cells' learn batches as their configuration files give them: rows,
    # query heads (GQA repeated before the kernel), positions, head widths
    (8, 28, 1024, 128, 128, True),   # grpo_k4_reason
    (8, 28, 1152, 128, 128, True),   # grpo_k4_longprompt: Tp stays 1152
    (4, 28, 1024, 128, 128, False),  # grpo_learn_fsdp4: a chip, shard_map
    (8, 32, 1024, 192, 128, False),  # grpo_kanana_reason: q, k 192 / v 128
    (8, 8, 1024, 128, 128, True),    # grpo_zaya_reason
    (8, 20, 1024, 128, 128, True),   # grpo_jamba_reason
    (4, 28, 2048, 128, 128, False),  # past one tile: 1024 x 1024, clamped
], ids=["k4_reason", "k4_longprompt", "learn_fsdp4", "kanana_reason",
        "zaya_reason", "jamba_reason", "T2048"])
def test_flash_attention_fwd_and_grad_at_the_cells_heads(v5e, B, H, T, d, dv,
                                                         spmd):
    s = jax.ShapeDtypeStruct
    q, v, m = _on(v5e, (s((B, H, T, d), jnp.bfloat16),
                        s((B, H, T, dv), jnp.bfloat16), s((B, T), jnp.int32)))

    def loss(qq, kk, vv, mm):
        return flash_attention_diff(
            qq, kk, vv, mm, True, spmd=spmd).astype(jnp.float32).sum()

    # Mosaic takes the chosen tiling and its VMEM footprint
    text = _compiled_text(
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))), q, q, v, m)
    assert text.count(KERNEL) == 3  # forward, dQ, dK/dV
    tp = -(-T // 128) * 128
    for kind in ("fwd", "dq", "dkv"):
        assert f"flash_{kind}" in text
        plan = flash_plan(T, d, dv, jnp.bfloat16, kind)
        assert plan.t_pad == tp
        print(f"\n  {kind}: {plan.block_q} x {plan.block_k} of Tp {plan.t_pad},"
              f" {plan.vmem_bytes / 2**20:.1f} MiB of VMEM a grid step "
              f"(limit asked {plan.vmem_limit_bytes / 2**20:.0f} MiB)", end="")
    # the sequence is never padded past the next multiple of 128: every
    # per-head array in the program is Tp long at most
    extents = {int(t) for t in re.findall(rf"\[{B * H},(\d+),\d+\]", text)}
    extents |= {int(t) for t in re.findall(rf"\[{B},{H},(\d+),\d+\]", text)}
    assert tp in extents and max(extents) == tp, extents


@pytest.mark.parametrize("T,learn", [(1024, True), (255, False)],
                         ids=["learn_8x1024", "prefill_state_255"])
def test_selective_scan_kernels_at_jamba2_3b_widths(v5e, T, learn):
    # grpo_jamba_reason's Mamba-1 layers (d_inner 5120, d_state 16): the
    # learn step's 8 rows x 1024 forward and VJP, and the prefill's T - 1
    # positions from a state, forward only
    B, D, N = 8, 5120, 16
    f32 = jnp.float32
    s = jax.ShapeDtypeStruct
    x, dt, A, Bm, Cm, Dk, mask, h0 = _on(v5e, (
        s((B, T, D), jnp.bfloat16), s((B, T, D), f32), s((D, N), f32),
        s((B, T, N), f32), s((B, T, N), f32), s((D,), f32), s((B, T), f32),
        s((B, N, D), f32)))

    def loss(*a):
        y, h = selective_scan(*a)
        return y.astype(f32).sum() + h.sum()

    if learn:
        fn, args = jax.grad(loss, argnums=tuple(range(6))), (
            x, dt, A, Bm, Cm, Dk, mask)
    else:
        fn, args = selective_scan, (x, dt, A, Bm, Cm, Dk, mask, h0)
    # Mosaic takes both kernels and their VMEM footprint
    text = _compiled_text(jax.jit(fn), *args)
    calls = [line for line in text.splitlines() if KERNEL in line]
    kinds = ("fwd", "bwd") if learn else ("fwd",)
    assert len(calls) == len(kinds)
    for kind, scope in zip(kinds, (SCAN_SCOPE, SCAN_BWD_SCOPE)):
        line = next(c for c in calls if f"%ssm_scan_{kind}" in c)
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        # under its scope: jit(f)/ssm/scan/..., or jvp(ssm/scan)/... and
        # transpose(jvp(ssm/scan_bwd))/... inside a gradient
        assert re.search(rf"\b{scope}\)*/ssm_scan_{kind}/", op_name), op_name
    plan = scan_plan(D, N, jnp.bfloat16)
    print(f"\n  {plan.tile} lanes x {plan.block} positions a grid step, "
          f"VMEM limit asked {plan.vmem_limit_bytes / 2**20:.0f} MiB", end="")


@pytest.fixture(scope="module")
def paged_tier(v5e):
    """qwen2-7b widths, 2 layers: the continuous tier as ``GRPO`` builds it,
    with its abstract weights, pool and slot state on the described chip."""
    cfg = preset("qwen2-7b", n_layer=2, max_seq_len=2048)
    gen = ContinuousGenerator(cfg, max_new_tokens=768, pad_id=0, eos_id=1,
                              temperature=0.9, speculate=True)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: M.init_params(k, cfg), key)
    lora = jax.eval_shape(lambda k: M.init_lora(k, cfg, 8), key)
    pool = jax.eval_shape(
        lambda: M.init_paged_cache(cfg, gen.n_blocks, gen.block_size))
    s = jax.ShapeDtypeStruct
    n, extent = gen.slots, gen.max_blocks * gen.block_size
    slot_state = (
        s((n, gen.max_blocks), jnp.int32),  # block tables
        s((n, extent), jnp.int32),          # slot mask
        s((n,), jnp.int32),                 # lengths
        s((n,), jnp.int32),                 # prev_tok
        s((n,), jnp.bool_),                 # prev_ok
        s((n,), jnp.int32),                 # pos
        s((n,), jnp.int32),                 # step_idx
        s((n,), jnp.bool_),                 # done
        s((n, 2), jnp.uint32),              # keys
    )
    drafts = (s((n, gen.speculate.k), jnp.int32), s((n,), jnp.int32))
    return (gen,) + _on(v5e, ((params, lora, pool) + slot_state, drafts))


def _whole_extent_results(gen, text):
    """Compiled operations whose result is K or V of every slot at the
    slot's whole extent (``bf16[8,2816,4,128]`` at these sizes): what the
    paged forward gathered, copied and scattered into a layer a step until
    its attention loop took to reading the pool a live chunk at a time."""
    slab = rf"bf16\[{gen.slots},{gen.max_blocks * gen.block_size},4,128\]"
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= {slab}", line)]


def test_paged_decode_chunk_at_qwen2_7b_widths(paged_tier):
    gen, state, _ = paged_tier
    text = _compiled_text(gen._decode, *state, greedy=False)
    # decode attention is the XLA dynamic-trip-count loop, not a kernel
    assert "while" in text and KERNEL not in text
    assert (gen.slots, gen.max_blocks * gen.block_size) == (8, 2816)
    assert not _whole_extent_results(gen, text)
    # what the loop fetches instead: one chunk of 16 pool blocks a slot
    assert re.search(r"= bf16\[8,512,4,128\]", text)


def test_paged_verify_step_at_qwen2_7b_widths(paged_tier):
    gen, state, drafts = paged_tier
    text = _compiled_text(gen._verify, *state, *drafts, greedy=False)
    assert "while" in text and KERNEL not in text
    assert not _whole_extent_results(gen, text)


def _narrowing_casts(jaxpr, dtype, min_size=1_000_000):
    """(casts, stacks) in the jaxpr and in every jaxpr its equations hold
    (scan bodies, calls): the operand's shape of every
    ``convert_element_type`` to ``dtype`` from a wider type, and the result's
    shape of every ``concatenate`` (what ``jnp.stack`` lowers to), of more
    than ``min_size`` elements."""
    casts, stacks = [], []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            (x,) = eqn.invars
            if (eqn.params["new_dtype"] == dtype and x.aval.size > min_size
                    and x.aval.dtype.itemsize > jnp.dtype(dtype).itemsize):
                casts.append(x.aval.shape)
        if eqn.primitive.name == "concatenate" \
                and eqn.outvars[0].aval.size > min_size:
            stacks.append(eqn.outvars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            c, k = _narrowing_casts(sub, dtype, min_size)
            casts += c
            stacks += k
    return casts, stacks


def test_decode_chunk_from_the_rollout_copy_casts_no_matrix(paged_tier):
    """The decode chunk handed ``model.compute_params``' tree (what
    ``GRPO.get_action`` hands it since ISSUE 39) narrows no matrix to the
    compute type and stacks no layers' weights; handed the f32 masters it
    does both to every layer's matrices, a chunk call (with what XLA:TPU
    adds — the embedding's table cast ahead of its 8-row lookup, the head
    rounded for the default precision's one bf16 pass: neither is a cast in
    the jaxpr — 8 GB read and 4 GB written at four layers). The head is not
    in the copy (``model.COMPUTE_MATRICES`` says why): its rounding stays in
    the chunk, and only the chip's trace sees it."""
    gen, (params, *state), _ = paged_tier
    cfg = gen.config
    chunk = lambda p: jax.make_jaxpr(  # noqa: E731
        lambda *a: gen._decode_chunk_impl(*a, greedy=False))(p, *state).jaxpr
    casts, stacks = _narrowing_casts(chunk(params), cfg.dtype)
    matrices = {(3584, 3584), (3584, 512), (3584, 18944), (18944, 3584)}
    assert set(casts) == matrices, casts  # q / o, k / v, gate / up, down
    assert set(stacks) == {(2, *m) for m in matrices}, stacks
    copy = jax.eval_shape(lambda p: M.compute_params(cfg, p), params)
    (run,) = copy["runs"]
    assert copy["tok_emb"].dtype == run["wk"].dtype == cfg.dtype
    assert run["w_up"].shape == (2, 3584, 18944)
    # the head is read at f32 (logits_fn) and stays as stored, like every
    # vector
    assert copy["lm_head"].dtype == copy["ln_f"].dtype == jnp.float32
    assert run["bq"].dtype == jnp.float32
    assert _narrowing_casts(chunk(copy), cfg.dtype) == ([], [])
