import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.ops import flash_attention_vjp as fv
from agilerl_tpu.ops.flash_attention_vjp import (
    flash_attention_diff,
    flash_attention_with_lse,
    flash_plan,
)


def dense_attention(q, k, v, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches(causal):
    key = jax.random.PRNGKey(0)
    B, H, T, d = 2, 2, 32, 16
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
               for i in range(3))
    got = flash_attention_diff(q, k, v, None, causal, 16, 16)
    want = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_dense(causal):
    key = jax.random.PRNGKey(1)
    B, H, T, d = 1, 2, 32, 16
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
               for i in range(3))
    tgt = jax.random.normal(jax.random.fold_in(key, 9), (B, H, T, d))

    def loss_flash(q, k, v):
        return jnp.sum((flash_attention_diff(q, k, v, None, causal, 16, 16) - tgt) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum((dense_attention(q, k, v, causal) - tgt) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=5e-4,
            err_msg=f"grad mismatch for {name}",
        )


def test_gradients_ragged_length():
    key = jax.random.PRNGKey(2)
    B, H, T, d = 1, 1, 24, 16  # T not divisible by blocks
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
               for i in range(3))

    def loss_flash(q):
        return jnp.sum(flash_attention_diff(q, k, v, None, True, 16, 16) ** 2)

    def loss_dense(q):
        return jnp.sum(dense_attention(q, k, v, True) ** 2)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_flash)(q)), np.asarray(jax.grad(loss_dense)(q)),
        atol=5e-4,
    )


def test_gradients_with_padding_mask():
    key = jax.random.PRNGKey(3)
    B, H, T, d = 2, 2, 32, 16
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
               for i in range(3))
    mask = jnp.ones((B, T), jnp.int32).at[0, :8].set(0)

    def dense_masked(q, k, v):
        scale = 1.0 / np.sqrt(d)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        full = jnp.logical_and(causal[None, None], mask[:, None, None, :].astype(bool))
        scores = jnp.where(full, scores, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)

    # compare grads on real (unpadded) rows only: weight the loss by the mask
    w = mask[:, None, :, None].astype(jnp.float32)

    def lf(q, k, v):
        return jnp.sum((flash_attention_diff(q, k, v, mask, True, 16, 16) * w) ** 2)

    def ld(q, k, v):
        return jnp.sum((dense_masked(q, k, v) * w) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   err_msg=name)


# --------------------------------------------------------------------------- #
# Tiles chosen by flash_plan (PR 37)
# --------------------------------------------------------------------------- #

def _dense_with_lse(q, k, v, mask):
    """Causal attention under a [B, T] key mask, and its logsumexp."""
    T = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    keep = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    keep = jnp.logical_and(keep[None, None], mask[:, None, None, :] > 0)
    s = jnp.where(keep, s, -1e30)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return out, jax.nn.logsumexp(s, -1)


# T, d, dv, positions masked at the head of row 0, explicit blocks, the VMEM
# the plan is told of (None: the device's), the tiles each kind must come to
TILE_CASES = {
    "one_tile_of_T": (256, 16, 16, 0, None, None, (256, 256)),
    "explicit_128x256": (256, 16, 16, 0, (128, 256), None, (128, 256)),
    "explicit_256x128": (256, 16, 16, 0, (256, 128), None, (256, 128)),
    "T200_rounds_to_256": (200, 16, 16, 0, None, None, (256, 256)),
    "T384_small_vmem_3x3": (384, 16, 16, 0, None, 3 << 20, (128, 128)),
    "mask_empties_a_block": (384, 16, 16, 130, None, 3 << 20, (128, 128)),
    "mask_one_tile": (256, 16, 16, 130, None, None, (256, 256)),
    "dv_narrower_than_d": (256, 32, 16, 5, None, None, (256, 256)),
}


@pytest.mark.parametrize("lse_cotangent", [False, True],
                         ids=["out", "out_and_lse"])
@pytest.mark.parametrize("case", list(TILE_CASES))
def test_chosen_tiles_match_dense(case, lse_cotangent, monkeypatch):
    T, d, dv, masked, blocks, vmem, tiles = TILE_CASES[case]
    if vmem is not None:
        monkeypatch.setattr(fv, "_vmem_capacity", lambda: vmem)
    bq, bk = blocks or (None, None)
    for kind in ("fwd", "dq", "dkv"):
        plan = flash_plan(T, d, dv, jnp.float32, kind, True, bq, bk)
        assert plan[:2] == tiles, (kind, plan)
        assert plan.t_pad == -(-T // 128) * 128  # the next 128, no further
    B, H = 2, 2
    key = jax.random.PRNGKey(4)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
            for i in range(2))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, T, dv))
    w_out = jax.random.normal(jax.random.fold_in(key, 3), (B, H, T, dv))
    w_lse = jax.random.normal(jax.random.fold_in(key, 5), (B, H, T))
    mask = jnp.ones((B, T), jnp.int32).at[0, :masked].set(0)
    # a query the mask leaves no key to is a pad row: any value, no gradient
    real = mask[:, None, :].astype(jnp.float32)
    w_out, w_lse = w_out * real[..., None], w_lse * real * lse_cotangent

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, mask, True, bq, bk)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return (jnp.sum(out * w_out)
                    + jnp.sum(jnp.where(real > 0, lse, 0.0) * w_lse))
        return f

    out, lse = flash(q, k, v)
    want_out, want_lse = _dense_with_lse(q, k, v, mask)
    assert out.shape == (B, H, T, dv) and lse.shape == (B, H, T)
    np.testing.assert_allclose(np.asarray(out * real[..., None]),
                               np.asarray(want_out * real[..., None]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse * real),
                               np.asarray(want_lse * real), atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: _dense_with_lse(*a, mask)),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   err_msg=f"grad mismatch for {name}")


@pytest.mark.parametrize("d,dv", [(128, 128), (192, 128), (64, 64)])
@pytest.mark.parametrize("T", [64, 975, 1024, 1152, 2048, 4096, 8192])
def test_flash_plan(T, d, dv):
    tp = T if T < 128 else -(-T // 128) * 128
    for kind in ("fwd", "dq", "dkv"):
        for dtype in (jnp.bfloat16, jnp.float32):
            plan = flash_plan(T, d, dv, dtype, kind)
            assert plan.t_pad == tp
            for block in plan[:2]:
                assert tp % block == 0
                assert block == T if T < 128 else block % 128 == 0
            assert plan.vmem_bytes <= plan.vmem_limit_bytes
            # what the cells run: one tile up to 1152, 1024 x 1024 past it
            want = tp if tp <= 1152 else 1024
            assert plan[:2] == (want, want), (kind, plan)
        # a device with little VMEM gets smaller tiles, never a larger Tp
        small = flash_plan(T, d, dv, jnp.bfloat16, kind,
                           vmem_capacity=16 << 20)
        assert small.t_pad == tp and small.vmem_bytes <= 8 << 20
        assert small.block_q * small.block_k <= plan.block_q * plan.block_k
        # explicit blocks win, and Tp is a multiple of both as it always was
        explicit = flash_plan(T, d, dv, jnp.bfloat16, kind, block_q=128,
                              block_k=256)
        assert explicit[:2] == (min(128, T), min(256, T))
        assert explicit.t_pad % explicit.block_q == 0
        assert explicit.t_pad % explicit.block_k == 0
        assert explicit.t_pad >= T
        alone = flash_plan(T, d, dv, jnp.bfloat16, kind, block_k=512)
        assert alone[:2] == (min(128, T), min(512, T))
    with pytest.raises(ValueError):
        flash_plan(T, d, dv, jnp.bfloat16, "dw")
