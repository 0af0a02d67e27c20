"""ops/selective_scan.py against the plain scan over positions: values and
``jax.grad`` in all seven inputs, with left padding and a non-zero start
state, at lengths on and off the chunk's boundaries; and against the
benchmark's reference file, whose bf16-state variant must fail. The Pallas
kernels the same way in interpret mode (lengths on and off the block's
boundaries, one lane tile and several, pads inside a row, the prefill's
form), and the gate that decides which form runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.ops.selective_scan import (kernels_run, selective_scan,
                                            selective_scan_pallas,
                                            selective_scan_reference)
from perfbench.reference import jamba_f32 as ref

B, D, N = 2, 24, 4


def inputs(T, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(k[0], (B, T, D))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, D)) - 2.0)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (D, N))
    Bm = jax.random.normal(k[2], (B, T, N))
    Cm = jax.random.normal(k[3], (B, T, N))
    Dk = jax.random.normal(k[4], (D,))
    h0 = jax.random.normal(k[5], (B, N, D))
    mask = jnp.ones((B, T)).at[0, :min(3, T - 1)].set(0).at[1, :T // 2].set(0)
    weights = (jax.random.normal(k[6], (B, T, D)),
               jax.random.normal(k[7], (B, N, D)))
    return (x, dt, A, Bm, Cm, Dk, h0), mask, weights


# 16 = two chunks of 8 exactly; 17 / 15 straddle; 5 is shorter than a chunk
@pytest.mark.parametrize("T,chunk", [(16, 8), (17, 8), (15, 8), (5, 8), (37, 16)])
def test_values_and_all_seven_gradients_match_the_plain_scan(T, chunk):
    args, mask, (wy, wh) = inputs(T)

    def loss(fn, *a):
        y, h = fn(*a[:6], mask, a[6])
        return jnp.sum(y * wy) + jnp.sum(h * wh)

    ours = lambda *a, **kw: selective_scan(*a, chunk=chunk)  # noqa: E731
    y1, h1 = selective_scan(*args[:6], mask, args[6], chunk=chunk)
    y2, h2 = selective_scan_reference(*args[:6], mask, args[6])
    np.testing.assert_allclose(y1, y2, atol=ref.SCAN_TOL)
    np.testing.assert_allclose(h1, h2, atol=ref.SCAN_TOL)
    g1 = jax.grad(lambda *a: loss(ours, *a), argnums=tuple(range(7)))(*args)
    g2 = jax.grad(lambda *a: loss(selective_scan_reference, *a),
                  argnums=tuple(range(7)))(*args)
    for name, a, b in zip("x dt A B C D h0".split(), g1, g2):
        assert float(jnp.abs(b).max()) > 0, name
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= ref.SCAN_GRAD_TOL * scale, name


def test_a_pad_position_leaves_the_state_unchanged():
    args, _, _ = inputs(12)
    mask = jnp.ones((B, 12)).at[:, 4:9].set(0)
    _, h_all = selective_scan(*args[:6], mask, args[6], chunk=4)
    keep = np.r_[0:4, 9:12]
    short = [a[:, keep] if a.ndim == 3 and a.shape[1] == 12 else a
             for a in args[:6]]
    _, h_short = selective_scan(*short, None, args[6], chunk=4)
    np.testing.assert_allclose(h_all, h_short, atol=1e-6)


def test_the_benchmarks_reference_agrees_and_its_bf16_state_does_not():
    """One unpadded sequence through perfbench/reference/jamba_f32.py's
    position-by-position scan: the op agrees within SCAN_TOL; the same
    reference with its state and discretisation rounded to bfloat16 — what
    a program that kept them in the compute dtype would compute — does not."""
    (x, dt, A, Bm, Cm, Dk, _), _, _ = inputs(64, seed=3)
    y, h = selective_scan(x[:1], dt[:1], A, Bm[:1], Cm[:1], Dk, chunk=16)
    want_y, want_h = ref.selective_scan(x[0], dt[0], A, Bm[0], Cm[0], Dk)
    assert float(jnp.abs(y[0] - want_y).max()) < ref.SCAN_TOL
    assert float(jnp.abs(h[0].T - want_h).max()) < ref.SCAN_TOL
    lossy_y, _ = ref.selective_scan(x[0], dt[0], A, Bm[0], Cm[0], Dk,
                                    bf16_state=True)
    assert float(jnp.abs(lossy_y - want_y).max()) > 10 * ref.SCAN_TOL


# --------------------------------------------------------------------------- #
# The Pallas kernels (interpret mode here): the same function, both ways
# --------------------------------------------------------------------------- #

KN = 16  # d_state as the kernels take it: a multiple of 8 sublanes


def kernel_inputs(T, d, seed=0, pads=True):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(k[0], (B, T, d)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, d)) - 2.0)
    A = -jnp.broadcast_to(jnp.arange(1, KN + 1, dtype=jnp.float32), (d, KN))
    Bm = jax.random.normal(k[2], (B, T, KN))
    Cm = jax.random.normal(k[3], (B, T, KN))
    Dk = jax.random.normal(k[4], (d,))
    h0 = jax.random.normal(k[5], (B, KN, d))
    mask = jnp.ones((B, T))
    if pads:  # left padding, and a run of pads inside row 1
        mask = mask.at[0, :3].set(0).at[1, :T // 3].set(0)
        mask = mask.at[1, T // 2:T // 2 + 5].set(0)
    weights = (jax.random.normal(k[6], (B, T, d)),
               jax.random.normal(k[7], (B, KN, d)))
    return (x, dt, A, Bm, Cm, Dk, h0), mask, weights


def kernels(*a):
    return selective_scan_pallas(*a, interpret=True)


def reference_in_x_dtype(*a):
    """The plain scan with ``y`` handed out in ``x``'s dtype, as both forms
    hand it out: the cotangent of ``y`` then comes in rounded alike."""
    y, h = selective_scan_reference(*a)
    return y.astype(a[0].dtype), h


# 512: two whole blocks of KERNEL_BLOCK (256); 300: a block and a ragged
# tail; 256 one block, 200 one ragged. 128 lanes: one tile; 384: three
@pytest.mark.parametrize("T,d,with_h0,pads", [
    (512, 128, True, True), (300, 384, True, True), (256, 384, False, False),
    (200, 128, False, True)],
    ids=["blocks-one_tile-h0-pads", "ragged-three_tiles-h0-pads",
         "blocks-three_tiles", "ragged-one_tile-pads"])
def test_kernels_values_and_all_seven_gradients_match_the_plain_scan(
        T, d, with_h0, pads):
    args, mask, (wy, wh) = kernel_inputs(T, d, pads=pads)
    if not with_h0:
        args = args[:6] + (jnp.zeros_like(args[6]),)

    def loss(fn, *a):
        y, h = fn(*a[:6], mask, a[6])
        return jnp.sum(y.astype(jnp.float32) * wy) + jnp.sum(h * wh)

    y1, h1 = kernels(*args[:6], mask, args[6])
    y2, h2 = selective_scan_reference(*args[:6], mask, args[6])
    assert y1.dtype == jnp.bfloat16 and h1.dtype == jnp.float32
    # y is stored in x's bf16: one rounding, half a unit of its last place
    np.testing.assert_allclose(y1.astype(jnp.float32), y2,
                               atol=2 ** -8 * float(jnp.abs(y2).max()))
    np.testing.assert_allclose(h1, h2, atol=ref.SCAN_TOL)
    g1 = jax.grad(lambda *a: loss(kernels, *a), argnums=tuple(range(7)))(*args)
    g2 = jax.grad(lambda *a: loss(reference_in_x_dtype, *a),
                  argnums=tuple(range(7)))(*args)
    for name, a, b in zip("x dt A B C D h0".split(), g1, g2):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        # dx is the cotangent of a bf16 input: rounded on both sides, so a
        # unit of its last place apart at most
        tol = 2 ** -7 if name == "x" else ref.SCAN_GRAD_TOL
        err = float(jnp.abs(a - b).max())
        assert err <= tol * scale, (name, err / scale)


def test_kernels_prefill_form_a_state_in_and_t_minus_one_positions():
    # the mixer's prefill: the first T - 1 positions from the slot's state
    (x, dt, A, Bm, Cm, Dk, h0), mask, _ = kernel_inputs(256, 256, seed=1)
    head = lambda a: a[:, :-1]  # noqa: E731
    y1, h1 = kernels(head(x), head(dt), A, head(Bm), head(Cm), Dk,
                     head(mask), h0)
    y2, h2 = selective_scan_reference(head(x), head(dt), A, head(Bm),
                                      head(Cm), Dk, head(mask), h0)
    assert y1.shape == (B, 255, 256)
    np.testing.assert_allclose(y1.astype(jnp.float32), y2,
                               atol=2 ** -8 * float(jnp.abs(y2).max()))
    np.testing.assert_allclose(h1, h2, atol=ref.SCAN_TOL)


def test_a_pad_position_leaves_the_kernels_state_bit_unchanged():
    (x, dt, A, Bm, Cm, Dk, h0), _, _ = kernel_inputs(144, 128, seed=2,
                                                     pads=False)
    # a row of pads only: the state comes out as it went in
    _, h_pads = kernels(x, dt, A, Bm, Cm, Dk, jnp.zeros((B, 144)), h0)
    np.testing.assert_array_equal(h_pads, h0)
    # pads inside a row: the state of the row with them cut out. Two whole
    # groups of KERNEL_GROUP positions are cut, so every real position keeps
    # its place inside the unrolled group (the interpreter may round a
    # position's update by its place there, in the last bit)
    mask = jnp.ones((B, 144)).at[:, 16:48].set(0)
    keep = np.r_[0:16, 48:144]
    _, h_all = kernels(x, dt, A, Bm, Cm, Dk, mask, h0)
    _, h_short = kernels(x[:, keep], dt[:, keep], A, Bm[:, keep],
                         Cm[:, keep], Dk, None, h0)
    np.testing.assert_array_equal(h_all, h_short)


def test_the_kernels_run_where_the_other_kernels_run_natively(monkeypatch):
    """Off the TPU the XLA form runs; under ``native_kernels()`` the two
    kernels, unless the kill switch is set or the widths do not fit."""
    from agilerl_tpu.ops.kernel_mode import native_kernels

    def lowered(d, n=KN):
        (x, dt, _, Bm, Cm, Dk, _), mask, _ = kernel_inputs(16, d)
        A = -jnp.ones((d, n))
        loss = lambda *a: selective_scan(*a)[0].astype(jnp.float32).sum()  # noqa: E731
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
            x, dt, A, Bm[..., :n], Cm[..., :n], Dk, mask))

    assert kernels_run(128, KN) is False and "pallas_call" not in lowered(128)
    with native_kernels():
        assert "pallas_call" in lowered(128)
        for d, n in ((24, KN), (128, 4)):  # widths that do not fit
            assert "pallas_call" not in lowered(d, n)
        monkeypatch.setenv("AGILERL_TPU_DISABLE_PALLAS", "1")
        assert "pallas_call" not in lowered(128)
