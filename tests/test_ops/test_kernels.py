import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.ops.flash_attention_vjp import flash_attention_diff
from agilerl_tpu.ops.fused_loss import (
    fused_loss_plan,
    fused_token_logprob,
    fused_token_logprob_diff,
    reference_token_logprob,
)


class TestFusedLoss:
    def test_matches_dense(self):
        key = jax.random.PRNGKey(0)
        N, D, V = 64, 32, 500
        hidden = jax.random.normal(key, (N, D))
        head = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (D, V))
        targets = jax.random.randint(jax.random.fold_in(key, 2), (N,), 0, V)
        got = fused_token_logprob(hidden, head, targets, block_n=16, block_v=128)
        want = reference_token_logprob(hidden, head, targets)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    def test_temperature_and_padding(self):
        key = jax.random.PRNGKey(3)
        N, D, V = 33, 16, 130  # deliberately non-divisible
        hidden = jax.random.normal(key, (N, D))
        head = 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (D, V))
        targets = jax.random.randint(jax.random.fold_in(key, 2), (N,), 0, V)
        got = fused_token_logprob(hidden, head, targets, temperature=1.7,
                                  block_n=16, block_v=64)
        want = reference_token_logprob(hidden, head, targets, temperature=1.7)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    def test_grad_matches_dense(self):
        """VERDICT #7: the fused loss is differentiable (custom VJP recomputes
        per vocab chunk); grads wrt hidden AND head must match the dense path."""
        from agilerl_tpu.ops.fused_loss import fused_token_logprob_diff

        key = jax.random.PRNGKey(7)
        N, D, V = 33, 16, 130  # non-divisible -> exercises padding in bwd too
        hidden = jax.random.normal(key, (N, D))
        head = 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (D, V))
        targets = jax.random.randint(jax.random.fold_in(key, 2), (N,), 0, V)
        wts = jax.random.normal(jax.random.fold_in(key, 3), (N,))

        def fused_loss(h, w):
            return jnp.sum(
                fused_token_logprob_diff(h, w, targets, 1.3, 16, 64, None) * wts
            )

        def dense_loss(h, w):
            return jnp.sum(
                reference_token_logprob(h, w, targets, temperature=1.3) * wts
            )

        v_f, (gh_f, gw_f) = jax.value_and_grad(fused_loss, argnums=(0, 1))(hidden, head)
        v_d, (gh_d, gw_d) = jax.value_and_grad(dense_loss, argnums=(0, 1))(hidden, head)
        np.testing.assert_allclose(float(v_f), float(v_d), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gh_f), np.asarray(gh_d), atol=2e-4)
        np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_d), atol=2e-4)

    def test_grad_under_jit_and_second_use(self):
        from agilerl_tpu.ops.fused_loss import fused_token_logprob_diff

        key = jax.random.PRNGKey(11)
        N, D, V = 32, 8, 64
        hidden = jax.random.normal(key, (N, D))
        head = 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (D, V))
        targets = jax.random.randint(jax.random.fold_in(key, 2), (N,), 0, V)

        @jax.jit
        def loss(h, w):
            return -fused_token_logprob_diff(h, w, targets, 1.0, 16, 64, None).mean()

        g = jax.grad(loss)(hidden, head)
        assert np.isfinite(np.asarray(g)).all()
        # grad step should reduce the NLL
        l0 = float(loss(hidden, head))
        l1 = float(loss(hidden - 0.1 * g, head))
        assert l1 < l0


BF16, F32 = jnp.bfloat16, jnp.float32

# (N, D, V, hidden dtype, head dtype): what the benchmark's cells hand the
# kernels (qwen2-7b's untied head on one chip and a chip of fsdp-4, jamba's
# tied head) and the same shapes at a float32 configuration
CELL_SHAPES = [
    (8192, 3584, 152064, BF16, BF16),
    (9216, 3584, 152064, BF16, BF16),
    (4096, 3584, 152064, BF16, BF16),
    (8192, 3584, 152064, F32, F32),
    (9216, 3584, 152064, F32, F32),
    (4096, 3584, 152064, F32, F32),
    (8192, 2560, 65536, BF16, BF16),
]
V5E_VMEM = 128 << 20


def _shape_id(case):
    N, D, V, hd, wd = case
    return f"{N}x{D}x{V}-{jnp.dtype(hd).name}-{jnp.dtype(wd).name}"


class TestFusedLossPlan:
    @pytest.mark.parametrize("kind", ["fwd", "dh", "dw"])
    @pytest.mark.parametrize("case", CELL_SHAPES, ids=_shape_id)
    def test_plan_at_the_cells_shapes(self, case, kind):
        N, D, V, hd, wd = case
        plan = fused_loss_plan(N, D, V, hd, wd, kind, vmem_capacity=V5E_VMEM)
        assert plan.block_n % 8 == 0 and plan.block_v % 128 == 0
        assert V % plan.block_v == 0  # V % 128 == 0: no ragged block, no pad
        assert plan.vmem_bytes < plan.vmem_limit_bytes <= V5E_VMEM
        limit = {"fwd": 16, "dh": 32, "dw": 1}[kind]
        assert plan.head_reads <= limit
        assert plan.head_reads == (1 if kind == "dw" else -(-N // plan.block_n))
        isz_h, isz_w = jnp.dtype(hd).itemsize, jnp.dtype(wd).itemsize
        assert plan.hbm_bytes >= plan.head_reads * D * V * isz_w + N * D * isz_h
        if kind != "dw" and isz_w == 2:
            # over twice the ridge of a v5e (197 TFLOP/s / 819 GB/s = 240)
            flops = (2 if kind == "fwd" else 4) * N * D * V
            assert flops / plan.hbm_bytes > 480

    @pytest.mark.parametrize("kind", ["fwd", "dh", "dw"])
    @pytest.mark.parametrize("capacity_mib", [16, 64, 128])
    def test_plan_follows_the_vmem_it_is_given(self, capacity_mib, kind):
        cap = capacity_mib << 20
        plan = fused_loss_plan(8192, 3584, 152064, BF16, BF16, kind,
                               vmem_capacity=cap)
        assert plan.vmem_bytes <= cap // 2 < plan.vmem_limit_bytes < cap
        big = fused_loss_plan(8192, 3584, 152064, BF16, BF16, kind,
                              vmem_capacity=V5E_VMEM)
        assert plan.block_n * plan.block_v <= big.block_n * big.block_v
        assert plan.hbm_bytes >= big.hbm_bytes

    @pytest.mark.parametrize("bounds,want", [
        ((None, None), (64, 384)),   # V = 3 x 128: one block
        ((16, 128), (16, 128)),      # upper bounds are kept
        ((16, 64), (16, 128)),       # ... down to the lane width
        ((20, 256), (16, 128)),      # snapped to 8 rows, to a divisor of 384
    ])
    def test_block_arguments_are_upper_bounds(self, bounds, want):
        plan = fused_loss_plan(64, 32, 300, F32, F32, "fwd", *bounds)
        assert (plan.block_n, plan.block_v) == want

    def test_unknown_kind_is_refused(self):
        with pytest.raises(ValueError, match="kind"):
            fused_loss_plan(64, 32, 256, F32, F32, "dq")


def _problem(seed, N, D, V, scale=0.2):
    key = jax.random.PRNGKey(seed)
    hidden = jax.random.normal(key, (N, D))
    head = scale * jax.random.normal(jax.random.fold_in(key, 1), (D, V))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (N,), 0, V)
    wts = jax.random.normal(jax.random.fold_in(key, 3), (N,))
    return hidden, head, targets, wts


def _dense_grads(hidden, head, targets, wts, temperature, coef_dtype):
    """dH and dW of sum(wts * logprob) from dense f32 logits of the operands
    as given, the coefficient g / T * (onehot - p) rounded to ``coef_dtype``
    for its matmul as the kernels round it."""
    h, w = hidden.astype(F32), head.astype(F32)
    p = jax.nn.softmax(h @ w / temperature, axis=-1)
    onehot = jax.nn.one_hot(targets, w.shape[1], dtype=F32)
    coef = ((onehot - p) * (wts / temperature)[:, None])
    coef = coef.astype(coef_dtype).astype(F32)
    return coef @ w.T, h.T @ coef


class TestFusedLossOperandDtypes:
    """bf16 operands (what a bfloat16 configuration hands the kernels):
    tight against a dense reference of the same rounded operands, and inside
    a stated bound of the float32 reference."""

    N, D, V, T = 48, 64, 384, 1.3
    # logits here are ~N(0, 1.6): rounding both operands to 8 bits of
    # mantissa moves a log-probability of ~6 by under 0.05
    BF16_LOGPROB_BOUND = 5e-2
    BF16_GRAD_BOUND = 2e-2  # of the largest gradient entry

    def _both(self):
        hidden, head, targets, wts = _problem(21, self.N, self.D, self.V)
        return (hidden, head, hidden.astype(BF16), head.astype(BF16),
                targets, wts)

    @pytest.mark.parametrize("blocks", [(None, None), (16, 128)],
                             ids=["planned", "16x128"])
    def test_forward(self, blocks):
        h32, w32, h16, w16, targets, _ = self._both()
        got = fused_token_logprob(h16, w16, targets, temperature=self.T,
                                  block_n=blocks[0], block_v=blocks[1])
        assert got.dtype == F32
        same = reference_token_logprob(h16, w16, targets, temperature=self.T)
        full = reference_token_logprob(h32, w32, targets, temperature=self.T)
        np.testing.assert_allclose(np.asarray(got), np.asarray(same), atol=1e-4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   atol=self.BF16_LOGPROB_BOUND)
        # and the rounding is really there: bf16 is not f32 in disguise
        assert np.abs(np.asarray(got) - np.asarray(full)).max() > 1e-4

    @pytest.mark.parametrize("which", ["dh", "dw"])
    @pytest.mark.parametrize("blocks", [(None, None), (16, 128)],
                             ids=["planned", "16x128"])
    def test_gradients(self, blocks, which):
        h32, w32, h16, w16, targets, wts = self._both()
        arg = 0 if which == "dh" else 1

        def fused(h, w):
            return jnp.sum(fused_token_logprob_diff(
                h, w, targets, self.T, blocks[0], blocks[1], None) * wts)

        got = jax.grad(fused, argnums=arg)(h16, w16)
        assert got.dtype == BF16  # the cotangent of a bf16 operand
        got = np.asarray(got.astype(F32))
        same = np.asarray(_dense_grads(h16, w16, targets, wts, self.T,
                                       BF16)[arg])
        full = np.asarray(_dense_grads(h32, w32, targets, wts, self.T,
                                       F32)[arg])
        top = np.abs(full).max()
        # the same rounded operands and coefficient: what is left is the
        # result's own rounding to bf16 (2^-8 of each entry)
        np.testing.assert_allclose(got, same, rtol=2 ** -7, atol=1e-4 * top)
        assert np.abs(got - full).max() < self.BF16_GRAD_BOUND * top

    def test_f32_head_under_bf16_hidden_stays_f32(self):
        """Mixed operands promote (an f32 head is multiplied as f32)."""
        h32, w32, h16, _, targets, wts = self._both()
        got, (gh, gw) = jax.value_and_grad(
            lambda h, w: jnp.sum(fused_token_logprob_diff(
                h, w, targets, self.T) * wts), argnums=(0, 1))(h16, w32)
        want = jnp.sum(reference_token_logprob(
            h16, w32, targets, temperature=self.T) * wts)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        assert gh.dtype == BF16 and gw.dtype == F32


# (N, V, block_n, block_v): rows that do not fill the last row block, a
# vocabulary that is no multiple of 128 (a ragged last block, masked in the
# kernel: the head is never padded), both, and several blocks each way
RAGGED = [(33, 130, 16, 128), (50, 300, 32, 128), (24, 257, 8, 256),
          (40, 384, 16, 128), (19, 1000, None, None), (64, 513, 64, 128)]


class TestFusedLossTails:
    @pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("N,V,bn,bv", RAGGED)
    def test_forward_and_gradients(self, N, V, bn, bv, dtype):
        hidden, head, targets, wts = _problem(N + V, N, 32, V)
        hidden, head = hidden.astype(dtype), head.astype(dtype)
        wts = wts.at[::3].set(0.0)  # rows the loss masks out

        def fused(h, w):
            lp = fused_token_logprob_diff(h, w, targets, 0.9, bn, bv, None)
            return jnp.sum(lp * wts), lp

        (_, lp), (gh, gw) = jax.value_and_grad(
            fused, argnums=(0, 1), has_aux=True)(hidden, head)
        want = reference_token_logprob(hidden, head, targets, temperature=0.9)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(want), atol=1e-4)
        assert gh.shape == hidden.shape and gw.shape == head.shape
        dh, dw = _dense_grads(hidden, head, targets, wts, 0.9, dtype)
        tol = dict(rtol=2 ** -7, atol=2e-4) if dtype == BF16 else dict(atol=2e-4)
        np.testing.assert_allclose(np.asarray(gh.astype(F32)), np.asarray(dh), **tol)
        np.testing.assert_allclose(np.asarray(gw.astype(F32)), np.asarray(dw), **tol)
        assert np.isfinite(np.asarray(gw.astype(F32))).all()
        # a row with no upstream gradient gets exactly none
        assert not np.asarray(gh.astype(F32))[::3].any()

    @pytest.mark.parametrize("N,V,bn,bv", RAGGED[:3])
    def test_padded_rows_add_nothing_to_dw(self, N, V, bn, bv):
        """dW sums over rows: the zero rows that fill the last row block
        must not reach it (their recomputed p is not zero)."""
        hidden, head, targets, wts = _problem(N, N, 32, V)

        def dw(h, t, w8, block_n):
            return jax.grad(lambda w: jnp.sum(fused_token_logprob_diff(
                h, w, t, 1.0, block_n, bv, None) * w8))(head)

        whole = -(-N // bn) * bn  # the same rows with no padding needed
        pad = whole - N
        alone = dw(hidden, targets, wts, bn)
        filled = dw(jnp.pad(hidden, ((0, pad), (0, 0))),
                    jnp.pad(targets, (0, pad)), jnp.pad(wts, (0, pad)), bn)
        np.testing.assert_allclose(np.asarray(alone), np.asarray(filled),
                                   atol=1e-6)


class TestFusedLossHeadGradient:
    """A caller that trains the head (DPO on a full model, a tied embedding)
    gets the dense reference's dW."""

    @pytest.mark.parametrize("temperature", [1.0, 0.7])
    @pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
    def test_dw_matches_dense(self, tied, dtype, temperature):
        N, D, V = 40, 32, 640
        hidden, head, targets, wts = _problem(5, N, D, V)
        hidden = hidden.astype(dtype)
        emb = head.T.astype(dtype) if tied else head.astype(dtype)

        def as_head(e):  # a tied head arrives as tok_emb.T
            return e.T if tied else e

        def fused(e):
            return jnp.sum(fused_token_logprob_diff(
                hidden, as_head(e), targets, temperature, 16, 128, None) * wts)

        def dense(e):
            return jnp.sum(reference_token_logprob(
                hidden, as_head(e), targets, temperature=temperature) * wts)

        got = jax.grad(fused)(emb)
        want = jax.grad(dense)(emb)
        assert got.shape == emb.shape and got.dtype == emb.dtype
        top = float(jnp.abs(want.astype(F32)).max())
        tol = 2e-2 * top if dtype == BF16 else 2e-4
        np.testing.assert_allclose(np.asarray(got.astype(F32)),
                                   np.asarray(want.astype(F32)), atol=tol)


class TestFlashAttention:
    def _dense(self, q, k, v, causal):
        scale = 1.0 / np.sqrt(q.shape[-1])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            T = q.shape[2]
            mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            scores = jnp.where(mask[None, None], scores, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        key = jax.random.PRNGKey(0)
        B, H, T, d = 2, 2, 64, 16
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
            for i in range(3)
        )
        got = flash_attention_diff(q, k, v, causal=causal, block_q=32, block_k=32)
        want = self._dense(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_ragged_length(self):
        key = jax.random.PRNGKey(1)
        B, H, T, d = 1, 2, 48, 16  # T not divisible by block
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
            for i in range(3)
        )
        got = flash_attention_diff(q, k, v, causal=True, block_q=32, block_k=32)
        want = self._dense(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


class TestFlashAttentionMask:
    def test_padding_mask_matches_dense(self):
        key = jax.random.PRNGKey(2)
        B, H, T, d = 2, 2, 32, 16
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (B, H, T, d))
            for i in range(3)
        )
        mask = jnp.ones((B, T), jnp.int32)
        mask = mask.at[0, :8].set(0)  # left padding on row 0
        got = flash_attention_diff(q, k, v, padding_mask=mask, causal=True,
                              block_q=16, block_k=16)
        # dense reference with combined causal+padding mask
        scale = 1.0 / np.sqrt(d)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        full = jnp.logical_and(causal[None, None], mask[:, None, None, :].astype(bool))
        scores = jnp.where(full, scores, -1e30)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
        # padded query rows attend only to pads -> compare real rows only
        np.testing.assert_allclose(
            np.asarray(got[0, :, 8:]), np.asarray(want[0, :, 8:]), atol=2e-5
        )
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=2e-5)


class TestFusedTrainingPath:
    def test_token_logprobs_grad_pallas_vs_xla(self):
        """The use_pallas path must be differentiable end-to-end (LoRA grads
        through the fused head) and match the XLA-chunked path."""
        from agilerl_tpu.llm import model as M

        cfg = M.GPTConfig(vocab_size=96, n_layer=1, n_head=2, d_model=16,
                          max_seq_len=16, dtype=jnp.float32)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        lora = M.init_lora(jax.random.PRNGKey(1), cfg, rank=4)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 2, 95)
        mask = jnp.ones_like(toks)

        def loss(lo, use_pallas):
            lp = M.token_logprobs(cfg, params, toks, attention_mask=mask,
                                  lora=lo, use_pallas=use_pallas)
            return -lp.mean()

        v_x, g_x = jax.value_and_grad(lambda lo: loss(lo, False))(lora)
        v_p, g_p = jax.value_and_grad(lambda lo: loss(lo, True))(lora)
        np.testing.assert_allclose(float(v_p), float(v_x), rtol=1e-5)
        for (pa, gx), (_, gp) in zip(
            jax.tree_util.tree_leaves_with_path(g_x),
            jax.tree_util.tree_leaves_with_path(g_p),
        ):
            np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                                       atol=2e-5, err_msg=str(pa))
