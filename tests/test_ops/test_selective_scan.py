"""ops/selective_scan.py against the plain scan over positions: values and
``jax.grad`` in all seven inputs, with left padding and a non-zero start
state, at lengths on and off the chunk's boundaries; and against the
benchmark's reference file, whose bf16-state variant must fail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.ops.selective_scan import (selective_scan,
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
