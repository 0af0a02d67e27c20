"""Compile-only TPU AOT regression tier (VERDICT r4 next #1b): the Pallas
kernels must keep compiling natively through the REAL XLA:TPU + Mosaic
pipeline — via libtpu's compile-only PJRT topology, no chip needed.

These are the tiny-dims versions of benchmarking/tpu_aot_compile.py's
targets, compiled for the chip builders have (a described v5e:2x2); the
full-dims run (llama3-8b lm-head/attention shapes, the 7B GSPMD pod step)
is that script's, and test_tpu_compile_v5e.py keeps
the main path's real widths in the fast tier. Skips cleanly when libtpu
cannot build a topology (non-TPU wheels).

History this tier guards against: interpret mode accepted (1, block)
BlockSpecs over 2-D aux arrays and f32-upcast operand blocks that Mosaic
rejects (block-shape rule) or that overflow the 16 MiB scoped VMEM at real
dims — both were invisible to every CPU test and caught only by the TPU
compiler.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def tpu_device():
    import os

    # compile-only use never touches devices; skip libtpu's multi-process
    # lockfile so this tier can run next to another compile (or a real run)
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "true")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc("v5e:2x2", platform="tpu")
    except Exception as e:  # pragma: no cover - non-TPU jaxlib
        pytest.skip(f"no compile-only TPU topology available: {e}")
    return topo.devices[0]


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    assert compiled.as_text()  # optimized HLO exists
    return compiled


def test_fused_loss_fwd_and_grad_compile_for_tpu(tpu_device):
    from jax.sharding import SingleDeviceSharding

    from agilerl_tpu.ops.fused_loss import (
        fused_token_logprob, fused_token_logprob_diff,
    )

    s = SingleDeviceSharding(tpu_device)
    N, D, V = 256, 512, 4096
    h = jax.ShapeDtypeStruct((N, D), jnp.bfloat16, sharding=s)
    w = jax.ShapeDtypeStruct((D, V), jnp.bfloat16, sharding=s)
    t = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=s)
    _compile(jax.jit(functools.partial(fused_token_logprob,
                                       interpret=False)), h, w, t)

    def loss(hh, ww, tt):
        return fused_token_logprob_diff(hh, ww, tt, 1.0).sum()

    _compile(jax.jit(jax.grad(loss, argnums=(0, 1))), h, w, t)


def test_flash_attention_fwd_and_grad_compile_for_tpu(tpu_device):
    from jax.sharding import SingleDeviceSharding

    from agilerl_tpu.ops.flash_attention_vjp import flash_attention_diff

    s = SingleDeviceSharding(tpu_device)
    # B > 1 on purpose: the (1, block) aux BlockSpec regression only
    # manifests with more than one mask row
    B, H, T, d = 2, 4, 256, 128
    q = jax.ShapeDtypeStruct((B, H, T, d), jnp.bfloat16, sharding=s)
    m = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=s)
    _compile(jax.jit(functools.partial(flash_attention_diff, causal=True,
                                       interpret=False)), q, q, q, m)

    def loss(qq, kk, vv, mm):
        return flash_attention_diff(
            qq, kk, vv, mm, interpret=False).astype(jnp.float32).sum()

    _compile(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, q, q, m)


def test_fused_grpo_step_compiles_for_tpu(tpu_device):
    """The production GRPO update with BOTH Pallas kernels on (flash
    attention + fused loss, incl. their custom VJPs) compiles natively for
    one v5e chip from abstract shapes."""
    from jax.sharding import SingleDeviceSharding

    from agilerl_tpu.algorithms.core.optimizer import OptimizerWrapper
    from agilerl_tpu.algorithms.grpo import make_update_fn
    from agilerl_tpu.llm import model as Mod
    from agilerl_tpu.ops.kernel_mode import native_kernels

    s = SingleDeviceSharding(tpu_device)
    cfg = Mod.GPTConfig(vocab_size=1024, n_layer=2, n_head=4, n_kv_head=2,
                        d_model=256, d_ff=512, max_seq_len=256,
                        use_flash_attention=True)
    Bt, Tt = 2, 128
    opt = OptimizerWrapper(optimizer="adamw", lr=5e-6, max_grad_norm=0.1)

    def abstract(shapes):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            shapes)

    base_abs = abstract(jax.eval_shape(
        lambda k: Mod.init_params(k, cfg), jax.random.PRNGKey(0)))
    lora_shapes = jax.eval_shape(
        lambda k: Mod.init_lora(k, cfg, 8), jax.random.PRNGKey(0))
    lora_abs = abstract(lora_shapes)
    opt_abs = abstract(jax.eval_shape(opt.tx.init, lora_shapes))
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32, sharding=s),
        "mask": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32, sharding=s),
        "loss_mask": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32, sharding=s),
        "old_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32, sharding=s),
        "ref_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32, sharding=s),
        "advantage": jax.ShapeDtypeStruct((Bt,), jnp.float32, sharding=s),
    }
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=s)
    update = make_update_fn(cfg, opt.tx, lora_scale=2.0, use_flash=True)
    with native_kernels():
        compiled = _compile(update, base_abs, lora_abs, opt_abs, batch_abs,
                            scalar, scalar)
    # the TPU executable really contains Mosaic kernels, not interpret HLO
    assert "tpu_custom_call" in compiled.as_text()
