"""Selective scan: the Mamba-1 recurrence over a whole sequence as ONE op.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t        (state, float32)
    y_t = sum_n h_t[n] * C_t[n] + D * x_t

with ``dt_t`` multiplied by ``mask_t`` first, so a pad position (``mask ==
0``; the system left-pads) decays nothing and adds nothing: ``h`` passes it
unchanged.

Plain XLA, chunked over ``T``:

- the state is laid out ``[B, d_state, d_inner]`` (``d_inner`` on the
  lanes, ``d_state`` = 16 on the sublanes): the published ``[d_inner,
  d_state]`` order would pad the 16 to 128 lanes on a TPU, eight times the
  bytes of every step;
- a chunk of ``chunk`` positions runs as a sequential ``lax.scan`` whose
  step is one fused update of ``h``; the chunk's states ``[chunk, B,
  d_state, d_inner]`` are the largest array that ever exists (``chunk`` /
  ``T`` of the ``[B, T, d_inner, d_state]`` the naive form holds), and
  ``y`` is one batched multiply-reduce over them;
- the custom VJP stores ONE state a chunk (the state a chunk starts from).
  The backward walks the chunks in reverse: it recomputes the chunk's
  states from that one, runs the adjoint recurrence ``g_t = C_t (x) dy_t +
  exp(dt_{t+1} A) g_{t+1}`` as the second sequential scan, and forms all
  seven gradients as batched reductions over the chunk.

Both directions sit under one name each in a device trace: the scopes
``ssm/scan`` and ``ssm/scan_bwd`` (docs/observability.md).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: positions a chunk holds; 8 slots x 32 x 16 x 5120 f32 states = 84 MB
DEFAULT_CHUNK = 32

SCAN_SCOPE = "ssm/scan"
SCAN_BWD_SCOPE = "ssm/scan_bwd"


def _chunks(a, n: int, chunk: int):
    """[B, T, ...] -> time-major chunks [n, chunk, B, ...]."""
    a = jnp.moveaxis(a, 1, 0)
    return a.reshape((n, chunk) + a.shape[1:])


def _unchunk(a):
    """[n, chunk, B, ...] -> [B, T, ...]."""
    return jnp.moveaxis(a.reshape((-1,) + a.shape[2:]), 0, 1)


def _chunk_states(h, dtm, dtx, bm, a_t):
    """The sequential part of a chunk: every state it passes through.
    h [B, N, D]; dtm, dtx [L, B, D]; bm [L, B, N]; a_t [N, D]. Returns
    (h_last, hs [L, B, N, D])."""
    def step(h, s):
        dtm_t, dtx_t, b_t = s
        h = (jnp.exp(dtm_t[:, None, :] * a_t) * h
             + b_t[:, :, None] * dtx_t[:, None, :])
        return h, h

    return jax.lax.scan(step, h, (dtm, dtx, bm))


def _forward(x, dt, A, Bm, Cm, D, mask, h0, chunk):
    """Returns (y [B, T, D] float32, h_last, boundaries [n, B, N, D]: the
    state each chunk starts from)."""
    T = x.shape[1]
    n = T // chunk
    a_t = A.T  # [N, D]
    dtm = dt * mask[..., None]
    xs = (_chunks(dtm, n, chunk), _chunks(dtm * x, n, chunk),
          _chunks(Bm, n, chunk), _chunks(Cm, n, chunk))

    def one_chunk(h, c):
        dtm_c, dtx_c, b_c, c_c = c
        h_last, hs = _chunk_states(h, dtm_c, dtx_c, b_c, a_t)
        y_c = jnp.sum(hs * c_c[..., None], axis=2)  # [L, B, D]
        return h_last, (y_c, h)

    h_last, (y, starts) = jax.lax.scan(one_chunk, h0, xs)
    return _unchunk(y) + D * x, h_last, starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _selective_scan(x, dt, A, Bm, Cm, D, mask, h0, chunk):
    with jax.named_scope(SCAN_SCOPE):
        y, h_last, _ = _forward(x, dt, A, Bm, Cm, D, mask, h0, chunk)
    return y, h_last


def _vjp_fwd(x, dt, A, Bm, Cm, D, mask, h0, chunk):
    with jax.named_scope(SCAN_SCOPE):
        y, h_last, starts = _forward(x, dt, A, Bm, Cm, D, mask, h0, chunk)
    return (y, h_last), (x, dt, A, Bm, Cm, D, mask, starts)


def _vjp_bwd(chunk, res, cts):
    x, dt, A, Bm, Cm, D, mask, starts = res
    dy, dh_last = cts
    T = x.shape[1]
    n = T // chunk
    a_t = A.T
    with jax.named_scope(SCAN_BWD_SCOPE):
        dtm = dt * mask[..., None]
        xs = (_chunks(dtm, n, chunk), _chunks(x, n, chunk),
              _chunks(Bm, n, chunk), _chunks(Cm, n, chunk),
              _chunks(dy, n, chunk), starts)

        def one_chunk(carry, c):
            g_in, da_acc = carry
            dtm_c, x_c, b_c, c_c, dy_c, h_start = c
            _, hs = _chunk_states(h_start, dtm_c, dtm_c * x_c, b_c, a_t)
            h_prev = jnp.concatenate([h_start[None], hs[:-1]], axis=0)

            def adjoint(g_in, s):
                dtm_t, c_t, dy_t = s
                g = c_t[:, :, None] * dy_t[:, None, :] + g_in
                return jnp.exp(dtm_t[:, None, :] * a_t) * g, g

            g_in, gs = jax.lax.scan(adjoint, g_in, (dtm_c, c_c, dy_c),
                                    reverse=True)
            # gh = d(loss)/d(exp(dtm A)) * exp(dtm A)
            gh = gs * h_prev * jnp.exp(dtm_c[:, :, None, :] * a_t)
            gs_b = jnp.sum(gs * b_c[..., None], axis=2)  # [L, B, D]
            ddtm = jnp.sum(gh * a_t, axis=2) + gs_b * x_c
            da_acc = da_acc + jnp.sum(gh * dtm_c[:, :, None, :], axis=(0, 1))
            db = jnp.sum(gs * (dtm_c * x_c)[:, :, None, :], axis=3)
            dc = jnp.sum(hs * dy_c[:, :, None, :], axis=3)
            dx = gs_b * dtm_c
            return (g_in, da_acc), (ddtm, db, dc, dx)

        (dh0, da_t), (ddtm, db, dc, dx) = jax.lax.scan(
            one_chunk, (dh_last, jnp.zeros_like(a_t)), xs, reverse=True)
        ddt = _unchunk(ddtm) * mask[..., None]
        dx = _unchunk(dx) + D * dy
        dD = jnp.sum(dy * x, axis=(0, 1))
    return (dx, ddt, da_t.T, _unchunk(db), _unchunk(dc), dD,
            jnp.zeros_like(mask), dh0)


_selective_scan.defvjp(_vjp_fwd, _vjp_bwd)


def selective_scan(
    x: jax.Array,      # [B, T, D] the conv's activated output
    dt: jax.Array,     # [B, T, D] step sizes, after softplus
    A: jax.Array,      # [D, N] negative reals (-exp(A_log))
    Bm: jax.Array,     # [B, T, N]
    Cm: jax.Array,     # [B, T, N]
    D: jax.Array,      # [D] skip
    mask: Optional[jax.Array] = None,  # [B, T] 1 = real position
    h0: Optional[jax.Array] = None,    # [B, N, D] float32 start state
    chunk: int = DEFAULT_CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B, T, D] in ``x``'s dtype, h_last [B, N, D] float32).
    Everything inside is float32 whatever the inputs' types. Any ``T``: the
    tail of the last chunk is filled with masked positions, which leave the
    state as it is. Differentiable in all seven of x, dt, A, Bm, Cm, D, h0."""
    B, T, Dn = x.shape
    N = A.shape[1]
    f32 = jnp.float32
    if mask is None:
        mask = jnp.ones((B, T), f32)
    if h0 is None:
        h0 = jnp.zeros((B, N, Dn), f32)
    chunk = max(1, min(int(chunk), T))
    pad = (-T) % chunk

    def prep(a):
        a = a.astype(f32)
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) \
            if pad else a

    y, h_last = _selective_scan(
        prep(x), prep(dt), A.astype(f32), prep(Bm), prep(Cm), D.astype(f32),
        prep(mask), h0.astype(f32), chunk)
    return y[:, :T].astype(x.dtype), h_last


def selective_scan_reference(x, dt, A, Bm, Cm, D, mask=None, h0=None):
    """The same function as one plain ``lax.scan`` over positions in the
    published ``[d_inner, d_state]`` order: what the tests (and autodiff
    through it) hold the chunked op and its VJP to. Holds all T states when
    differentiated: tests only."""
    B, T, Dn = x.shape
    f32 = jnp.float32
    mask = jnp.ones((B, T), f32) if mask is None else mask.astype(f32)
    h = (jnp.zeros((B, Dn, A.shape[1]), f32) if h0 is None
         else jnp.swapaxes(h0.astype(f32), 1, 2))

    def step(h, s):
        x_t, dt_t, b_t, c_t, m_t = s
        dtm = dt_t * m_t[:, None]
        h = (jnp.exp(dtm[..., None] * A) * h
             + (dtm * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t

    tm = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)  # noqa: E731
    h, y = jax.lax.scan(step, h, (tm(x), tm(dt), tm(Bm), tm(Cm), tm(mask)))
    return jnp.moveaxis(y, 0, 1), jnp.swapaxes(h, 1, 2)
