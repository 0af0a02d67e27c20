"""Selective scan: the Mamba-1 recurrence over a whole sequence as ONE op.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t        (state, float32)
    y_t = sum_n h_t[n] * C_t[n] + D * x_t

with ``dt_t`` multiplied by ``mask_t`` first, so a pad position (``mask ==
0``; the system left-pads) decays nothing and adds nothing: ``h`` passes it
unchanged.

The state is laid out ``[B, d_state, d_inner]`` (``d_inner`` on the lanes,
``d_state`` = 16 on the sublanes): the published ``[d_inner, d_state]``
order would pad the 16 to 128 lanes on a TPU, eight times the bytes and the
vector work of every step. Two forms compute the same function:

**Pallas kernels** (``ssm_scan_fwd``, ``ssm_scan_bwd``), wherever the
package's other kernels run natively: on the TPU backend, or under
``kernel_mode.native_kernels()`` for a compile against a described chip —
and never under ``AGILERL_TPU_DISABLE_PALLAS``, nor where ``d_inner`` is no
multiple of 128 or ``d_state`` no multiple of 8 (``kernels_run``). The
recurrent state never leaves the chip:

- the grid is (row, tile of ``d_inner`` lanes, block of ``KERNEL_BLOCK``
  positions), the blocks walked in order; a row's state ``[d_state, tile]``
  float32 lives in VMEM across them and in registers inside one;
- a grid step reads its positions' ``x`` (in its own dtype) and ``dt``,
  forms ``dt * mask`` and ``dt * x`` itself, and writes ``y`` in ``x``'s
  dtype; ``B``, ``C`` and the mask come in ONE ``[2 d_state + 8, T]``
  float32 array per row, positions on the lanes (about 1 % of ``x``'s
  bytes), and reach the sublanes as one column a position (a lane roll a
  group of positions and a lane broadcast): nothing ``[.., d_state,
  d_inner]``-sized is ever written to HBM but the state a block starts
  from, and that only for the backward;
- the backward walks the blocks in reverse and carries the adjoint
  ``g [d_state, tile]`` in VMEM. A block recomputes its states from the one
  stored state into VMEM scratch, then runs the adjoint recurrence ``g_t =
  C_t dy_t + exp(dt_{t+1} A) g_{t+1}`` position by position, forming
  ``dx``, ``ddt``, ``dB``, ``dC`` and ``dA`` as it goes (``dA`` accumulates
  a row's tile in VMEM; ``dB`` / ``dC``, sums over ``d_inner``, leave as
  one partial sum a lane tile, added outside: ``[B, tiles, 2 d_state,
  T]``). ``dD = sum(dy * x)`` stays one XLA reduction; ``dh0`` is the last
  adjoint.

The state, ``exp(dt * A)`` and every accumulation are float32 in both
kernels, as in the XLA form.

**Plain XLA**, chunked over ``T`` — every other backend, the tests'
reference path and the kill switch's:

- a chunk of ``chunk`` positions runs as a sequential ``lax.scan`` whose
  step is one fused update of ``h``; the chunk's states ``[chunk, B,
  d_state, d_inner]`` are the largest array that ever exists (``chunk`` /
  ``T`` of the ``[B, T, d_inner, d_state]`` the naive form holds), and
  ``y`` is one batched multiply-reduce over them;
- the custom VJP stores ONE state a chunk (the state a chunk starts from).
  The backward walks the chunks in reverse: it recomputes the chunk's
  states from that one, runs the adjoint recurrence as the second
  sequential scan, and forms all seven gradients as batched reductions
  over the chunk.

Both forms sit under one name each in a device trace: the scopes
``ssm/scan`` and ``ssm/scan_bwd`` (docs/observability.md); the kernels'
executions carry their own names besides, which ``ssm_scan_roofline``
reads.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from agilerl_tpu.ops import kernel_mode
from agilerl_tpu.ops.fused_loss import _vmem_capacity

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

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


# --------------------------------------------------------------------------- #
# The Pallas kernels: the state stays on the chip
# --------------------------------------------------------------------------- #

LANES = 128
#: d_inner lanes a grid step carries: the widest of these dividing d_inner.
#: At jamba2-3b's 5120 x 16, 8 rows x 1024 on a v5e, a layer's forward /
#: backward took 2.9 / 8.6 ms at 1024 lanes, 3.2 / 11.9 at 512, 4.7 / 20.7
#: at 256 (PERF.md section 6): a wider tile spreads the per-position
#: lane reductions of dB / dC and the columns' broadcasts over more lanes
KERNEL_TILES = (1024, 512, 256, 128)
#: positions a grid step walks: a multiple of 128, because the block of B,
#: C and the mask carries its positions on the lanes; a row is padded to a
#: multiple of it with masked positions. 256 against 128: ~5 % off the
#: backward (fewer grid steps); 512 gave nothing more
KERNEL_BLOCK = 256
#: positions one pass of a kernel's inner loop unrolls: 16 rows of a bf16
#: x, dy or dx block make one whole (16, 128) tile
KERNEL_GROUP = 16
#: rows of the mask in the small per-row array (one row, padded to the 8
#: sublanes of a float32 tile)
_MASK_ROWS = 8


class ScanPlan(NamedTuple):
    """What the two kernels walk for one problem."""

    tile: int  # d_inner lanes a grid step
    block: int  # positions a grid step
    group: int  # positions an unrolled pass of the inner loop
    interpret: bool
    vmem_limit_bytes: int


def kernels_run(d_inner: int, d_state: int) -> bool:
    """The one gate of the kernels: where the package's Pallas kernels run
    natively (the TPU backend, or ``native_kernels()``), the kill switch is
    not set, and the widths fit the lanes and sublanes."""
    return (d_inner % LANES == 0 and d_state % 8 == 0
            and not kernel_mode.active_kill_switches()
            and not kernel_mode.resolve_interpret(None))


def _vmem_bytes(tile: int, block: int, n: int, isz: int, kind: str) -> int:
    """VMEM a grid step holds: double-buffered blocks, and the state (the
    backward: a block's states) in scratch."""
    small = 2 * (2 * n + _MASK_ROWS) * block * 4
    state = n * tile * 4
    if kind == "fwd":  # x, dt, y; a, h0, h_last, starts
        return 2 * block * tile * (2 * isz + 4) + small + 9 * state
    # x, dy, dx, dt, ddt; a, start, dh_last, dA, dh0; dB/dC out
    return (2 * block * tile * (3 * isz + 8) + small
            + 2 * (2 * n) * block * 4 + 11 * state + (block + 1) * state)


def scan_plan(d_inner: int, d_state: int, dtype,
              interpret: Optional[bool] = None) -> ScanPlan:
    tile = next(t for t in KERNEL_TILES if d_inner % t == 0)
    isz = jnp.dtype(dtype).itemsize
    need = max(_vmem_bytes(tile, KERNEL_BLOCK, d_state, isz, k)
               for k in ("fwd", "bwd"))
    limit = min(_vmem_capacity() * 3 // 4, max(2 * need, 32 << 20))
    return ScanPlan(tile, KERNEL_BLOCK, KERNEL_GROUP,
                    kernel_mode.resolve_interpret(interpret), limit)


def _small_rows(Bm, Cm, mask):
    """[B, T, N] x 2 and [B, T] -> [B, 2N + 8, T] float32: B and C
    transposed, positions on the lanes, and the mask below them."""
    bsz, T = mask.shape
    return jnp.concatenate(
        [jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2),
         jnp.broadcast_to(mask[:, None, :], (bsz, _MASK_ROWS, T))], axis=1)


def _columns(small, t0, block: int):
    """The block of B, C and the mask rolled so that lane j holds position
    ``t0 + j``: a group's columns are then static lane slices."""
    return pltpu.roll(small, (block - t0) % block, 1)


def _column(cols, j: int, n: int):
    """Position j of a group: B_t and C_t as [N, 1] columns, mask_t [1, 1]."""
    c = cols[:, j:j + 1]
    return c[:n], c[n:2 * n], c[2 * n:2 * n + 1]


def _fwd_kernel(n: int, plan: ScanPlan, with_starts: bool):
    G, L = plan.group, plan.block
    f32 = jnp.float32

    def kernel(x_ref, dt_ref, small_ref, a_ref, d_ref, h0_ref, y_ref, hl_ref,
               *rest):
        h_scr = rest[-1]

        @pl.when(pl.program_id(2) == 0)
        def _():
            h_scr[...] = h0_ref[...]

        if with_starts:
            rest[0][...] = h_scr[...]
        a, d_skip, small = a_ref[...], d_ref[...], small_ref[...]

        def group(i, h):
            t0 = pl.multiple_of(i * G, G)
            dt = dt_ref[pl.ds(t0, G), :]
            x = x_ref[pl.ds(t0, G), :].astype(f32)
            cols = _columns(small, t0, L)
            ys = []
            for j in range(G):
                b, c, m = _column(cols, j, n)
                dtm = dt[j:j + 1] * m
                h = jnp.exp(dtm * a) * h + b * (dtm * x[j:j + 1])
                ys.append(jnp.sum(h * c, axis=0, keepdims=True))
            y_ref[pl.ds(t0, G), :] = (
                jnp.concatenate(ys, axis=0) + d_skip * x).astype(y_ref.dtype)
            return h

        h = lax.fori_loop(0, L // G, group, h_scr[...])
        h_scr[...] = h

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _():
            hl_ref[...] = h

    return kernel


def _bwd_kernel(n: int, plan: ScanPlan):
    G, L = plan.group, plan.block
    f32 = jnp.float32

    def kernel(x_ref, dt_ref, small_ref, a_ref, d_ref, st_ref, dy_ref,
               dhl_ref, dx_ref, ddt_ref, dbc_ref, da_ref, dh0_ref, g_scr,
               hs_scr):
        @pl.when(pl.program_id(2) == 0)
        def _():
            g_scr[...] = dhl_ref[...]
            da_ref[...] = jnp.zeros_like(da_ref)

        a, d_skip, small = a_ref[...], d_ref[...], small_ref[...]
        # hs[t] = the state before position t of the block, hs[L] its last
        hs_scr[0] = st_ref[...]

        def recompute(i, h):
            t0 = pl.multiple_of(i * G, G)
            dt = dt_ref[pl.ds(t0, G), :]
            x = x_ref[pl.ds(t0, G), :].astype(f32)
            cols = _columns(small, t0, L)
            for j in range(G):
                b, _, m = _column(cols, j, n)
                dtm = dt[j:j + 1] * m
                h = jnp.exp(dtm * a) * h + b * (dtm * x[j:j + 1])
                hs_scr[t0 + j + 1] = h
            return h

        lax.fori_loop(0, L // G, recompute, hs_scr[0])
        lane = lax.broadcasted_iota(jnp.int32, (2 * n, L), 1)

        def adjoint(r, carry):
            g, da, dbc = carry
            t0 = pl.multiple_of((L // G - 1 - r) * G, G)
            dt = dt_ref[pl.ds(t0, G), :]
            x = x_ref[pl.ds(t0, G), :].astype(f32)
            dy = dy_ref[pl.ds(t0, G), :].astype(f32)
            cols = _columns(small, t0, L)
            ddts, dxs = [None] * G, [None] * G
            for j in reversed(range(G)):
                b, c, m = _column(cols, j, n)
                dtm, x_t, dy_t = dt[j:j + 1] * m, x[j:j + 1], dy[j:j + 1]
                decay = jnp.exp(dtm * a)
                g = c * dy_t + g
                gb = jnp.sum(g * b, axis=0, keepdims=True)
                gh = g * hs_scr[t0 + j] * decay
                ddts[j] = (jnp.sum(gh * a, axis=0, keepdims=True)
                           + gb * x_t) * m
                dxs[j] = gb * dtm + d_skip * dy_t
                da = da + gh * dtm
                # dB_t and dC_t over this tile's lanes, into lane t0 + j
                both = jnp.concatenate(
                    [g * (dtm * x_t), hs_scr[t0 + j + 1] * dy_t], axis=0)
                dbc = jnp.where(lane == t0 + j,
                                jnp.sum(both, axis=1, keepdims=True), dbc)
                g = decay * g
            ddt_ref[pl.ds(t0, G), :] = jnp.concatenate(ddts, axis=0)
            dx_ref[pl.ds(t0, G), :] = jnp.concatenate(
                dxs, axis=0).astype(dx_ref.dtype)
            return g, da, dbc

        g, da, dbc = lax.fori_loop(
            0, L // G, adjoint,
            (g_scr[...], jnp.zeros(a.shape, f32), jnp.zeros((2 * n, L), f32)))
        g_scr[...] = g
        da_ref[...] += da
        dbc_ref[...] = dbc

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _():
            dh0_ref[...] = g

    return kernel


def _params(plan: ScanPlan):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit_bytes)


def _fwd_call(x, dt, A, Bm, Cm, D, mask, h0, plan: ScanPlan,
              with_starts: bool):
    """(y, h_last) and, ``with_starts``, the state each block starts from
    [B, blocks, N, D]."""
    bsz, T, d = x.shape
    n = A.shape[1]
    f32 = jnp.float32
    L, tile = plan.block, plan.tile
    nt = T // L
    rows = pl.BlockSpec((None, L, tile), lambda b, j, k: (b, k, j))
    state = pl.BlockSpec((None, n, tile), lambda b, j, k: (b, 0, j))
    out_specs = [rows, state]
    out_shape = [jax.ShapeDtypeStruct((bsz, T, d), x.dtype),
                 jax.ShapeDtypeStruct((bsz, n, d), f32)]
    if with_starts:
        out_specs.append(pl.BlockSpec((None, None, n, tile),
                                      lambda b, j, k: (b, k, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bsz, nt, n, d), f32))
    return pl.pallas_call(
        _fwd_kernel(n, plan, with_starts),
        grid=(bsz, d // tile, nt),
        in_specs=[
            rows, rows,
            pl.BlockSpec((None, 2 * n + _MASK_ROWS, L),
                         lambda b, j, k: (b, 0, k)),
            pl.BlockSpec((n, tile), lambda b, j, k: (0, j)),
            pl.BlockSpec((1, tile), lambda b, j, k: (0, j)),
            state,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, tile), f32)],
        compiler_params=_params(plan),
        interpret=plan.interpret,
        name="ssm_scan_fwd",
    )(x, dt, _small_rows(Bm, Cm, mask), A.T, D[None], h0)


def _bwd_call(x, dt, A, Bm, Cm, D, mask, starts, dy, dh_last,
              plan: ScanPlan):
    """(dx with D dy in, ddt, dA, dB, dC, dh0)."""
    bsz, T, d = x.shape
    n = A.shape[1]
    f32 = jnp.float32
    L, tile = plan.block, plan.tile
    nt, nd = T // L, d // tile
    rows = pl.BlockSpec((None, L, tile),
                        lambda b, j, k: (b, nt - 1 - k, j))
    state = pl.BlockSpec((None, n, tile), lambda b, j, k: (b, 0, j))
    dx, ddt, dbc, da, dh0 = pl.pallas_call(
        _bwd_kernel(n, plan),
        grid=(bsz, nd, nt),
        in_specs=[
            rows, rows,
            pl.BlockSpec((None, 2 * n + _MASK_ROWS, L),
                         lambda b, j, k: (b, 0, nt - 1 - k)),
            pl.BlockSpec((n, tile), lambda b, j, k: (0, j)),
            pl.BlockSpec((1, tile), lambda b, j, k: (0, j)),
            pl.BlockSpec((None, None, n, tile),
                         lambda b, j, k: (b, nt - 1 - k, 0, j)),
            rows, state,
        ],
        out_specs=[
            rows, rows,
            pl.BlockSpec((None, None, 2 * n, L),
                         lambda b, j, k: (b, j, 0, nt - 1 - k)),
            state, state,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, T, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, T, d), f32),
            jax.ShapeDtypeStruct((bsz, nd, 2 * n, T), f32),
            jax.ShapeDtypeStruct((bsz, n, d), f32),
            jax.ShapeDtypeStruct((bsz, n, d), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, tile), f32),
                        pltpu.VMEM((L + 1, n, tile), f32)],
        compiler_params=_params(plan),
        interpret=plan.interpret,
        name="ssm_scan_bwd",
    )(x, dt, _small_rows(Bm, Cm, mask), A.T, D[None], starts, dy, dh_last)
    dbc = jnp.swapaxes(dbc.sum(1), 1, 2)  # [B, T, 2N]
    return dx, ddt, da.sum(0).T, dbc[..., :n], dbc[..., n:], dh0


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _scan_kernels(x, dt, A, Bm, Cm, D, mask, h0, plan):
    with jax.named_scope(SCAN_SCOPE):
        return tuple(_fwd_call(x, dt, A, Bm, Cm, D, mask, h0, plan, False))


def _kernels_fwd(x, dt, A, Bm, Cm, D, mask, h0, plan):
    with jax.named_scope(SCAN_SCOPE):
        y, h_last, starts = _fwd_call(x, dt, A, Bm, Cm, D, mask, h0, plan,
                                      True)
    return (y, h_last), (x, dt, A, Bm, Cm, D, mask, starts)


def _kernels_bwd(plan, res, cts):
    x, dt, A, Bm, Cm, D, mask, starts = res
    dy, dh_last = cts
    with jax.named_scope(SCAN_BWD_SCOPE):
        dx, ddt, dA, dB, dC, dh0 = _bwd_call(x, dt, A, Bm, Cm, D, mask,
                                             starts, dy, dh_last, plan)
        dD = jnp.sum(dy.astype(jnp.float32) * x.astype(jnp.float32),
                     axis=(0, 1))
    return dx, ddt, dA, dB, dC, dD, jnp.zeros_like(mask), dh0


_scan_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def selective_scan_pallas(x, dt, A, Bm, Cm, D, mask=None, h0=None,
                          interpret: Optional[bool] = None):
    """``selective_scan`` through the two kernels, whatever the backend
    (``interpret`` as the package's other kernels take it: None = native on
    a TPU or under ``native_kernels()``). ``d_inner`` a multiple of 128,
    ``d_state`` of 8; any ``T``: a row is padded to whole blocks with masked
    positions."""
    bsz, T, d = x.shape
    n = A.shape[1]
    f32 = jnp.float32
    plan = scan_plan(d, n, x.dtype, interpret)
    if mask is None:
        mask = jnp.ones((bsz, T), f32)
    if h0 is None:
        h0 = jnp.zeros((bsz, n, d), f32)
    pad = (-T) % plan.block

    def prep(a, dtype):
        a = a.astype(dtype)
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) \
            if pad else a

    y, h_last = _scan_kernels(
        prep(x, x.dtype), prep(dt, f32), A.astype(f32), prep(Bm, f32),
        prep(Cm, f32), D.astype(f32), prep(mask, f32), h0.astype(f32), plan)
    return y[:, :T], h_last


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
    tail of the last chunk (block) is filled with masked positions, which
    leave the state as it is. Differentiable in all seven of x, dt, A, Bm,
    Cm, D, h0. The kernels where ``kernels_run`` says so (``chunk`` is the
    XLA form's), else the XLA form."""
    B, T, Dn = x.shape
    N = A.shape[1]
    if kernels_run(Dn, N):
        return selective_scan_pallas(x, dt, A, Bm, Cm, D, mask, h0)
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
    through it) hold both forms and their VJPs to. Holds all T states when
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
