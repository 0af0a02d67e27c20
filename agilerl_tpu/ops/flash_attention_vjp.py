"""Differentiable Pallas flash attention (custom VJP, FlashAttention-2 style
backward) — lets the fused kernel serve the TRAINING losses (GRPO/DPO forward-
backward), not just the no-grad passes.

Forward saves per-row logsumexp L; backward recomputes probabilities blockwise:
  D_i  = rowsum(dO_i * O_i)
  P_ij = exp(q_i k_j^T * scale - L_i)
  dV_j = sum_i P_ij^T dO_i
  dS   = P * (dO V^T - D)
  dQ_i = dS_ij K_j * scale        (grid: kv innermost, accumulate in VMEM)
  dK_j = dS_ij^T Q_i * scale      (grid: q innermost, accumulate in VMEM)

Causal masking mirrors the forward. Interpret mode on CPU for tests; native on
TPU (``flash_fwd`` / ``flash_dq`` / ``flash_dkv``). Supports an optional
[B, T] padding mask. bf16 (or whatever arrives) operands on the MXU; scores,
statistics and accumulators in float32.

Layout inside the kernels. All three hold a tile of scores TRANSPOSED,
``[block_k, block_q]`` = K Q^T: queries ride the 128 lanes, keys the
sublanes. The per-query statistics (running max and sum, L, D) are then
lane-dense ``[1, block_q]`` rows — L and D live in HBM as ``[BH, 1, Tp]``,
where a ``[BH, Tp, 1]`` array is stored 128 lanes wide — the softmax's
reductions run over sublanes, and every matmul but two contracts over a
leading or a trailing dimension as the MXU takes it (``lax.dot_general``, no
explicit transpose of a score-sized tile; the forward's P V and dQ's dS^T K
contract over dimension 0 of both operands). Timed alone on a v5e, this
layout took 20-27 % off each kernel at equal tiles (PERF.md section 6,
PR 37).

Tiles. ``flash_plan`` is the one place that decides them, from (T, d, dv),
the operands' itemsize, the kernel kind and the device's VMEM: the pair of
multiples of 128 dividing ``Tp = round_up(T, 128)`` that costs least by a
model of two measured terms — the live tiles' score elements, and a grid
step's fixed cost of ``_STEP_ELEMS`` elements — under an estimate of VMEM.
At the cells' T (1024, 1152; any Tp to ~1400) that is one tile of the whole
sequence, a grid of ``(BH, 1, 1)``; at 2048 to 8192 it is 1024 x 1024. **Tp never passes the next multiple of 128**: a tile
that does not divide it is not a candidate (padding 1152 to 1536 for 512-
wide tiles would run a third more work), so 1152 runs as 1152 or 384.
``block_q`` / ``block_k`` given explicitly win over the plan (a test, a
caller that knows better); one given alone leaves the other at 128, and Tp
is then a multiple of both, as it always was. Under ``causal`` a tile wholly
above the diagonal computes nothing and its index maps are clamped onto the
last live tile, so it fetches nothing either.

Window. With ``window=W`` > 0 (and ``causal``) query t sees keys
``t - W < j <= t``: a sliding-window layer. The mask gains that edge, a tile
wholly BEHIND the window is skipped as one wholly above the diagonal is, the
index maps stay on a live tile on that side too, ``flash_plan`` counts the
live tiles of the band, and the three kernels carry names of their own
(``flash_fwd_win`` / ``flash_dq_win`` / ``flash_dkv_win``) so that a reader
can credit an execution with the pairs it computes. ``W >= T`` is no window.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from agilerl_tpu.ops.fused_loss import _round_up, _vmem_capacity
from agilerl_tpu.ops.kernel_mode import resolve_interpret

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


# --------------------------------------------------------------------------- #
# The plan: which tile each kernel walks
# --------------------------------------------------------------------------- #

# A grid step's fixed cost in score elements, from the kernels timed alone on
# a v5e (PERF.md section 6, PR 37): 0.8-0.9 us a step against ~3.1 us a
# million elements of a tile (matmuls, mask, exp, reductions).
_STEP_ELEMS = 250_000

# float32 score-sized tiles a grid step is taken to hold at once (scores,
# mask, probabilities, dO V^T, dS and their casts). Mosaic streams part of
# them through registers and needs less (compiles for a described v5e pass
# with a limit of 1-3.4 tiles); the estimate is an upper bound, not a
# reading, and it keeps a tile under ~2048 x 1024, past which the sweep
# found nothing to gain.
_SCORE_TILES = {"fwd": 4, "dq": 5, "dkv": 6}


class FlashPlan(NamedTuple):
    """The tile one kernel of this module walks over a sequence of T."""

    block_q: int
    block_k: int
    t_pad: int  # Tp: the sequence extent the kernel sees
    vmem_bytes: int  # estimated footprint of one grid step, double buffers in
    vmem_limit_bytes: int  # what the pallas_call asks the compiler for


def _vmem_estimate(bq: int, bk: int, d: int, dv: int, isz: int,
                   kind: str) -> int:
    """Bytes of VMEM one grid step holds: double-buffered operand and result
    blocks in their own dtype, the float32 accumulators, the statistics rows
    (a [1, bq] row pads to 8 sublanes), the padding mask's [bk, 1] column
    (pads to 128 lanes) and the score-sized float32 intermediates."""
    lanes = max(d, 128), max(dv, 128)
    q_side, k_side = bq * isz, bk * isz
    col, row = 2 * bk * 128 * 4, 2 * 8 * bq * 4
    tiles = _SCORE_TILES[kind] * bq * bk * 4
    if kind == "fwd":  # q, k, v in; o, lse out; m, l, acc^T
        blocks = 2 * (q_side * lanes[0] + k_side * (lanes[0] + lanes[1])
                      + q_side * lanes[1])
        return blocks + 4 * row + bq * lanes[1] * 4 + col + tiles
    blocks = 2 * (q_side * (lanes[0] + lanes[1])
                  + k_side * (lanes[0] + lanes[1]))  # q, do, k, v in
    if kind == "dq":
        out = 2 * q_side * lanes[0] + bq * lanes[0] * 4
    else:
        out = 2 * k_side * (lanes[0] + lanes[1]) + bk * (lanes[0] + lanes[1]) * 4
    return blocks + out + 2 * row + col + tiles


def _live_tiles(nq: int, nk: int, bq: int, bk: int, causal: bool,
                window: int = 0) -> int:
    """Tiles of the (nq, nk) grid that compute: all of them, or under
    ``causal`` those with a key at or before their last query — and, with a
    ``window``, a key inside the window of their first query."""
    if not causal:
        return nq * nk
    if not window:
        return sum(min(nk, (i * bq + bq - 1) // bk + 1) for i in range(nq))
    return sum(min(nk, (i * bq + bq - 1) // bk + 1)
               - max(i * bq - window + 1, 0) // bk for i in range(nq))


def flash_plan(T: int, d: int, dv: int, dtype, kind: str, causal: bool = True,
               block_q: Optional[int] = None, block_k: Optional[int] = None,
               vmem_capacity: Optional[int] = None,
               window: int = 0) -> FlashPlan:
    """The tile one of the three kernels (``kind``: "fwd", "dq", "dkv") walks
    over queries and keys of length T with heads ``d`` (q, k) and ``dv`` (v)
    wide, chosen from those, the operands' itemsize and the device's VMEM
    alone.

    With neither ``block_q`` nor ``block_k`` given: T under 128 is one tile
    of T; otherwise ``Tp = round_up(T, 128)`` and the tiles are multiples of
    128 that divide it — Tp never grows to fit a tile. Of the pairs whose
    estimate fits half the device's VMEM the plan takes the one that costs
    least: live tiles x (block_q x block_k + ``_STEP_ELEMS``), i.e. the score
    elements computed (a tile on the diagonal computes its upper half for
    nothing, a tile above it is skipped) plus a fixed cost a grid step; the
    larger block on the inner grid axis of two that cost the same. Under a
    ``window`` the live tiles are the band's. The
    compiler is asked for three quarters of VMEM (16 MiB is only Mosaic's
    default scoped limit).

    An explicit ``block_q`` / ``block_k`` wins: the blocks are those (128
    for one left out, T for one larger than T) and Tp a multiple of both.
    """
    if kind not in _SCORE_TILES:
        raise ValueError(
            f"kind must be one of {tuple(_SCORE_TILES)}, got {kind!r}")
    isz = jnp.dtype(dtype).itemsize
    capacity = vmem_capacity or _vmem_capacity()

    def plan(bq, bk, t_pad):
        return FlashPlan(bq, bk, t_pad, _vmem_estimate(bq, bk, d, dv, isz, kind),
                         capacity * 3 // 4)

    if block_q is not None or block_k is not None:
        bq, bk = min(block_q or 128, T), min(block_k or 128, T)
        # a multiple of BOTH block sizes, else the grid's floor division
        # drops trailing rows
        return plan(bq, bk, _round_up(T, math.lcm(bq, bk)))
    if T < 128:
        return plan(T, T, T)
    t_pad = _round_up(T, 128)
    n = t_pad // 128
    sides = [s * 128 for s in range(n, 0, -1) if n % s == 0]
    inner = (lambda bq, bk: bq) if kind == "dkv" else (lambda bq, bk: bk)
    fits = [(bq, bk) for bq in sides for bk in sides
            if _vmem_estimate(bq, bk, d, dv, isz, kind) <= capacity // 2]
    bq, bk = min(fits or [(128, 128)], key=lambda t: (
        _live_tiles(t_pad // t[0], t_pad // t[1], *t, causal, window)
        * (t[0] * t[1] + _STEP_ELEMS), -inner(*t)))
    return plan(bq, bk, t_pad)


# --------------------------------------------------------------------------- #
# Kernels. A tile of scores is [block_k, block_q] (see the module docstring).
# --------------------------------------------------------------------------- #

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _tile_mask(shape, q0, k0, causal, seq_len, pm_ref, window=0):
    """Which [block_k, block_q] scores count, for the tile whose first query
    is q0 and first key k0: keys at or before the query (``causal``) and
    less than ``window`` behind it, keys short of ``seq_len`` (None where
    T == Tp or a padding mask says so already) and keys the padding mask
    keeps (its [block_k, 1] column)."""
    keys = lax.broadcasted_iota(jnp.int32, shape, 0)
    masks = []
    if causal:
        behind = keys - lax.broadcasted_iota(jnp.int32, shape, 1)
        masks.append(behind <= q0 - k0)
        if window:
            masks.append(behind > q0 - k0 - window)
    if seq_len is not None:
        masks.append(keys < seq_len - k0)
    if pm_ref is not None:
        masks.append(pm_ref[0] > 0)
    return functools.reduce(jnp.logical_and, masks) if masks else None


def _in_window(live, q0, k0, block_k, window):
    """``live`` and the tile's last key inside the window of its first
    query: a tile wholly behind the window is skipped as one wholly above
    the diagonal is."""
    return jnp.logical_and(live, k0 + block_k - 1 > q0 - window)


def _run_live(body, live, q0, k0, block_q, block_k, window):
    """Run ``body(edged)`` on a live tile of a WINDOWED kernel: with the
    causal and the window edge in its mask where the tile straddles either
    (a key above a query, or a pair the window parts), without them on an
    interior tile — most tiles of a long band, and the two compares and the
    ``and`` a score are vector work the MXU waits for."""
    live = _in_window(live, q0, k0, block_k, window)
    edged = jnp.logical_or(k0 + block_k - 1 > q0,
                           q0 + block_q - 1 - k0 >= window)
    pl.when(jnp.logical_and(live, edged))(lambda: body(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(edged)))(
        lambda: body(False))


def _probs(q, k, lse_ref, scale, mask):
    """P^T = exp(K Q^T * scale - L) on the tile, 0 where masked."""
    p = jnp.exp(_dot(k, q, _NT) * scale - lse_ref[0])
    return p if mask is None else jnp.where(mask, p, 0.0)


def _fwd_kernel(scale, causal, block_q, block_k, seq_len, with_mask,
                window=0):
    def kernel(*refs):
        if with_mask:
            (q_ref, k_ref, v_ref, pm_ref, out_ref, lse_ref,
             m_ref, l_ref, acc_ref) = refs
        else:
            q_ref, k_ref, v_ref, out_ref, lse_ref, m_ref, l_ref, acc_ref = refs
            pm_ref = None
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(kj == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, -1e30)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        def body(edged=True):
            q, k, v = q_ref[0], k_ref[0], v_ref[0]
            s = _dot(k, q, _NT) * scale  # [BK, BQ]
            mask = _tile_mask(s.shape, qi * block_q, kj * block_k,
                              causal and edged, seq_len, pm_ref, window)
            if mask is not None:
                s = jnp.where(mask, s, -1e30)
            m_old = m_ref[:]  # [1, BQ]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=0, keepdims=True)
            # the accumulator is O^T [dv, BQ], so alpha scales it by lanes
            acc_ref[:] = acc_ref[:] * alpha + _dot(v, p.astype(v.dtype), _TN)
            m_ref[:] = m_new

        if causal:
            live = kj * block_k <= qi * block_q + block_q - 1
            if window:
                _run_live(body, live, qi * block_q, kj * block_k, block_q,
                          block_k, window)
            else:
                pl.when(live)(body)
        else:
            body()

        @pl.when(kj == nk - 1)
        def _done():
            l = jnp.maximum(l_ref[:], 1e-30)
            out_ref[0] = (acc_ref[:] / l).T.astype(out_ref.dtype)
            lse_ref[0] = m_ref[:] + jnp.log(l)

    return kernel


def _dq_kernel(scale, causal, block_q, block_k, seq_len, with_mask,
               window=0):
    def kernel(*refs):
        if with_mask:
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, pm_ref,
             dq_ref, acc_ref) = refs
        else:
            q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, acc_ref = refs
            pm_ref = None
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(kj == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        def body(edged=True):
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            mask = _tile_mask((block_k, block_q), qi * block_q, kj * block_k,
                              causal and edged, seq_len, pm_ref, window)
            p = _probs(q, k, lse_ref, scale, mask)
            ds = p * (_dot(v, do, _NT) - dd_ref[0])  # [BK, BQ]
            acc_ref[:] = acc_ref[:] + _dot(ds.astype(k.dtype), k, _TN)

        if causal:
            live = kj * block_k <= qi * block_q + block_q - 1
            if window:
                _run_live(body, live, qi * block_q, kj * block_k, block_q,
                          block_k, window)
            else:
                pl.when(live)(body)
        else:
            body()

        @pl.when(kj == nk - 1)
        def _done():
            dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)

    return kernel


def _dkv_kernel(scale, causal, block_q, block_k, seq_len, with_mask,
                window=0):
    def kernel(*refs):
        if with_mask:
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, pm_ref,
             dk_ref, dv_ref, dk_acc, dv_acc) = refs
        else:
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
             dk_ref, dv_ref, dk_acc, dv_acc) = refs
            pm_ref = None
        kj = pl.program_id(1)
        qi = pl.program_id(2)
        nq = pl.num_programs(2)

        @pl.when(qi == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        def body(edged=True):
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            mask = _tile_mask((block_k, block_q), qi * block_q, kj * block_k,
                              causal and edged, seq_len, pm_ref, window)
            p = _probs(q, k, lse_ref, scale, mask)
            dv_acc[:] = dv_acc[:] + _dot(p.astype(do.dtype), do)
            ds = p * (_dot(v, do, _NT) - dd_ref[0])
            dk_acc[:] = dk_acc[:] + _dot(ds.astype(q.dtype), q)

        if causal:
            # q blocks strictly before this kv block contribute nothing
            live = qi * block_q + block_q - 1 >= kj * block_k
            if window:
                _run_live(body, live, qi * block_q, kj * block_k, block_q,
                          block_k, window)
            else:
                pl.when(live)(body)
        else:
            body()

        @pl.when(qi == nq - 1)
        def _done():
            dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    return kernel


# --------------------------------------------------------------------------- #
# custom_vjp wrapper
# --------------------------------------------------------------------------- #


def _pad_t(x, pad):
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def flash_attention_diff(
    q: jax.Array,  # [B, H, T, d]
    k: jax.Array,
    v: jax.Array,
    padding_mask: Optional[jax.Array] = None,  # [B, T] 1=real
    causal: bool = True,
    block_q: Optional[int] = None,  # None, both: ``flash_plan`` chooses
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    spmd: bool = True,
    window: int = 0,  # > 0: query t sees keys t - window < j <= t
) -> jax.Array:
    """``spmd=True`` (default) routes through the custom_partitioning
    wrappers so plain-GSPMD callers shard over (batch, heads) at runtime;
    pass ``spmd=False`` when calling from inside an explicit shard_map
    (e.g. model.py's ``flash_shard_axes`` path — the AOT-compatible route:
    custom_partitioning needs a runtime python callback that compile-only
    PJRT clients don't host, 'Custom emitter for CustomSPMDPartitioning
    not found')."""
    out, _ = _fwd_rule(q, k, v, padding_mask, causal, block_q, block_k,
                       interpret, spmd, window)
    return out


def _compiler_params(plan: FlashPlan):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit_bytes)


def _kv_block(causal, block_q, block_k, window=0):
    """(q block i, kv block j) -> the K/V block to fetch: j, but under
    ``causal`` a block above the diagonal stays on the last live one of its
    row (and with a ``window`` a block behind it on the first), so the step
    that computes nothing fetches nothing either."""
    if not causal:
        return lambda i, j: j
    if not window:
        return lambda i, j: jnp.minimum(
            j, (i * block_q + block_q - 1) // block_k)
    return lambda i, j: jnp.clip(
        j, jnp.maximum(i * block_q - window + 1, 0) // block_k,
        (i * block_q + block_q - 1) // block_k)


def _effective_window(window: int, T: int, causal: bool) -> int:
    """0 where the window masks nothing (none stated, or as long as the
    sequence): the kernels are then the plain ones, by name too."""
    if window and not causal:
        raise ValueError("a sliding window is a causal window: causal=True")
    return window if 0 < window < T else 0


def _fwd(q, k, v, padding_mask, causal, block_q, block_k, interpret,
         window=0):
    interpret = resolve_interpret(interpret)
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas tpu module unavailable")
    B, H, T, d = q.shape
    dv = v.shape[-1]  # values may be narrower than queries and keys
    scale = 1.0 / math.sqrt(d)
    window = _effective_window(window, T, causal)
    plan = flash_plan(T, d, dv, q.dtype, "fwd", causal, block_q, block_k,
                      window=window)
    block_q, block_k, Tp = plan.block_q, plan.block_k, plan.t_pad
    pad = Tp - T
    qf = _pad_t(q, pad).reshape(B * H, Tp, d)
    kf = _pad_t(k, pad).reshape(B * H, Tp, d)
    vf = _pad_t(v, pad).reshape(B * H, Tp, dv)
    with_mask = padding_mask is not None

    kv = _kv_block(causal, block_q, block_k, window)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kv(i, j), 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, kv(i, j), 0)),
    ]
    args = [qf, kf, vf]
    if with_mask:
        # keys ride sublanes inside the kernels, so the mask comes in as a
        # [B, Tp, 1] column (a (rows, Tp) array with (1, block) blocks fails
        # the TPU lowering whenever rows > 1: benchmarking/tpu_aot_compile.py)
        in_specs.append(pl.BlockSpec(
            (1, block_k, 1), lambda b, i, j, H=H: (b // H, kv(i, j), 0)))
        args.append(_mask_column(padding_mask, pad))
    out, lse = pl.pallas_call(
        _fwd_kernel(scale, causal, block_q, block_k,
                    None if with_mask or not pad else T, with_mask, window),
        grid=(B * H, Tp // block_q, Tp // block_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Tp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((dv, block_q), jnp.float32),
        ],
        compiler_params=_compiler_params(plan),
        interpret=interpret,
        name="flash_fwd_win" if window else "flash_fwd",
    )(*args)
    out4 = out.reshape(B, H, Tp, dv)[:, :, :T, :]
    # lse rides as [B, H, 1, Tp] so the GSPMD partitioning rule can map its
    # leading dims 1:1 onto q's (batch, heads) axes
    return out4, lse.reshape(B, H, 1, Tp)


def _mask_column(padding_mask, pad):
    B, T = padding_mask.shape
    return jnp.pad(padding_mask.astype(jnp.int32),
                   ((0, 0), (0, pad))).reshape(B, T + pad, 1)


# --------------------------------------------------------------------------- #
# GSPMD partitioning (custom_partitioning + Shardy sharding rules)
#
# Mosaic kernels cannot be auto-partitioned ("wrap the call in a shard_map" —
# surfaced by benchmarking/tpu_aot_compile.py's grpo_7b_flash target). The
# TPU-native answer for the production fsdp x tp mesh: attention is
# embarrassingly parallel over (batch, heads) once GQA heads are repeated, so
# we declare exactly that — b and h shard freely, sequence and head_dim are
# need_replication factors (Shardy inserts the all-gathers if a caller hands
# in sp-sharded operands) — and lower the SAME pallas kernels per shard.
# --------------------------------------------------------------------------- #


def _keep_dims(mesh, info, keep):
    """NamedSharding that keeps only `keep` dims of an operand's sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ndim = len(info.shape)
    spec = getattr(info.sharding, "spec", None)
    parts = list(spec) if spec is not None else []
    parts = parts + [None] * (ndim - len(parts))
    parts = [p if i in keep else None for i, p in enumerate(parts)]
    return NamedSharding(mesh, P(*parts))


@functools.lru_cache(maxsize=None)
def _partitioned_fwd(causal, block_q, block_k, interpret, with_mask,
                     window=0):
    from jax.experimental.custom_partitioning import custom_partitioning

    def impl(*args):
        q, k, v = args[:3]
        mask = args[3] if with_mask else None
        return _fwd(q, k, v, mask, causal, block_q, block_k, interpret,
                    window)

    fn = custom_partitioning(impl)
    arg_keep = [(0, 1), (0, 1), (0, 1)] + ([(0,)] if with_mask else [])
    res_keep = [(0, 1), (0, 1)]

    def partition(mesh, arg_infos, result_infos):
        arg_sh = tuple(_keep_dims(mesh, a, k)
                       for a, k in zip(arg_infos, arg_keep))
        res_sh = tuple(_keep_dims(mesh, r, k)
                       for r, k in zip(result_infos, res_keep))
        return mesh, impl, res_sh, arg_sh

    rule = ("b h t d, b h t d, b h t d" + (", b t" if with_mask else "")
            + " -> b h t d, b h p u")
    fn.def_partition(partition=partition, sharding_rule=rule,
                     need_replication_factors=("t", "d", "p", "u"))
    return fn


@functools.lru_cache(maxsize=None)
def _partitioned_bwd(causal, block_q, block_k, interpret, with_mask,
                     window=0):
    from jax.experimental.custom_partitioning import custom_partitioning

    def impl(*args):
        q, k, v, do, out, lse = args[:6]
        mask = args[6] if with_mask else None
        return _bwd_arrays(q, k, v, do, out, lse, mask, causal, block_q,
                           block_k, interpret, window=window)

    fn = custom_partitioning(impl)
    arg_keep = [(0, 1)] * 6 + ([(0,)] if with_mask else [])
    res_keep = [(0, 1)] * 3

    def partition(mesh, arg_infos, result_infos):
        arg_sh = tuple(_keep_dims(mesh, a, k)
                       for a, k in zip(arg_infos, arg_keep))
        res_sh = tuple(_keep_dims(mesh, r, k)
                       for r, k in zip(result_infos, res_keep))
        return mesh, impl, res_sh, arg_sh

    rule = ("b h t d, b h t d, b h t d, b h t d, b h t d, b h p u"
            + (", b t" if with_mask else "")
            + " -> b h t d, b h t d, b h t d")
    fn.def_partition(partition=partition, sharding_rule=rule,
                     need_replication_factors=("t", "d", "p", "u"))
    return fn


def _fwd_rule(q, k, v, padding_mask, causal, block_q, block_k, interpret,
              spmd=True, window=0):
    concrete = resolve_interpret(interpret)
    with_mask = padding_mask is not None
    if spmd:
        args = (q, k, v) + ((padding_mask,) if with_mask else ())
        out, lse = _partitioned_fwd(causal, block_q, block_k, concrete,
                                    with_mask, window)(*args)
    else:
        out, lse = _fwd(q, k, v, padding_mask, causal, block_q, block_k,
                        concrete, window)
    return out, (q, k, v, padding_mask, out, lse)


def _bwd_rule(causal, block_q, block_k, interpret, spmd, window, res, do):
    q, k, v, padding_mask, out, lse = res
    concrete = resolve_interpret(interpret)
    with_mask = padding_mask is not None
    if spmd:
        args = (q, k, v, do, out, lse) + ((padding_mask,) if with_mask else ())
        dq, dk, dv = _partitioned_bwd(causal, block_q, block_k, concrete,
                                      with_mask, window)(*args)
    else:
        dq, dk, dv = _bwd_arrays(q, k, v, do, out, lse, padding_mask,
                                 causal, block_q, block_k, concrete,
                                 window=window)
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_with_lse(
    q: jax.Array,  # [B, H, T, d]
    k: jax.Array,
    v: jax.Array,
    padding_mask: Optional[jax.Array] = None,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Flash attention returning (out [B,H,T,d], lse [B,H,T]) — BOTH
    differentiable. The lse output is what lets callers merge partial
    attentions online (ring attention's per-block path, ops/ring_attention
    .py): o = sum_b o_b * exp(lse_b - lse_total). The backward folds the
    lse cotangent into the FlashAttention-2 dd term: dS gains p * dlse,
    and since dS = p * (dOV^T - dd), that is exactly dd -> dd - dlse.
    Direct (non-custom_partitioning) kernels: built for use INSIDE
    shard_map."""
    out, lse4 = _fwd(q, k, v, padding_mask, causal, block_q, block_k,
                     resolve_interpret(interpret))
    T = q.shape[2]
    return out, lse4[:, :, 0, :T]


def _with_lse_fwd(q, k, v, padding_mask, causal, block_q, block_k, interpret):
    out, lse4 = _fwd(q, k, v, padding_mask, causal, block_q, block_k,
                     resolve_interpret(interpret))
    T = q.shape[2]
    return (out, lse4[:, :, 0, :T]), (q, k, v, padding_mask, out, lse4)


def _with_lse_bwd(causal, block_q, block_k, interpret, res, cts):
    q, k, v, padding_mask, out, lse4 = res
    do, dlse = cts
    dq, dk, dv = _bwd_arrays(q, k, v, do, out, lse4, padding_mask, causal,
                             block_q, block_k, resolve_interpret(interpret),
                             dlse=dlse)
    return dq, dk, dv, None


flash_attention_with_lse.defvjp(_with_lse_fwd, _with_lse_bwd)


def _bwd_arrays(q, k, v, do, out, lse, padding_mask, causal, block_q,
                block_k, interpret, dlse=None, window=0):
    interpret = resolve_interpret(interpret)
    B, H, T, d = q.shape
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(d)
    window = _effective_window(window, T, causal)
    plans = {kind: flash_plan(T, d, dv, q.dtype, kind, causal, block_q, block_k,
                              window=window)
             for kind in ("dq", "dkv")}
    Tp = plans["dq"].t_pad  # the forward's and both kernels': T decides it
    pad = Tp - T
    bh = B * H
    qf = _pad_t(q, pad).reshape(bh, Tp, d)
    kf = _pad_t(k, pad).reshape(bh, Tp, d)
    vf = _pad_t(v, pad).reshape(bh, Tp, dv)
    dof = _pad_t(do, pad).reshape(bh, Tp, dv)
    lse = lse.reshape(bh, 1, Tp)  # arrives [B, H, 1, Tp] (partition layout)
    # D_i = rowsum(dO * O), a lane-dense row like lse. An lse cotangent
    # (flash_attention_with_lse) enters as dS += p * dlse == dd -= dlse.
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        dd = dd - dlse.astype(jnp.float32)
    dd = jnp.pad(dd, ((0, 0), (0, 0), (0, pad))).reshape(bh, 1, Tp)
    with_mask = padding_mask is not None
    mask_args = [_mask_column(padding_mask, pad)] if with_mask else []
    seq_len = None if with_mask or not pad else T

    block_q, block_k = plans["dq"][:2]
    kv = _kv_block(causal, block_q, block_k, window)
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # q by qi
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kv(i, j), 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, kv(i, j), 0)),
        pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),  # do by qi
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),  # lse by qi
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),  # dd by qi
    ]
    if with_mask:
        dq_specs.append(pl.BlockSpec(
            (1, block_k, 1), lambda b, i, j, H=H: (b // H, kv(i, j), 0)))
    dq = pl.pallas_call(
        _dq_kernel(scale, causal, block_q, block_k, seq_len, with_mask,
                   window),
        grid=(bh, Tp // block_q, Tp // block_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, Tp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(plans["dq"]),
        interpret=interpret,
        name="flash_dq_win" if window else "flash_dq",
    )(qf, kf, vf, dof, lse, dd, *mask_args)

    bq, bk = plans["dkv"][:2]

    def qb(j, i):  # a q block before kv block j: stay on the first live one
        if window:  # and one past the window of its last key on the last
            return jnp.clip(i, (j * bk) // bq,
                            (j * bk + bk + window - 2) // bq)
        return jnp.maximum(i, (j * bk) // bq) if causal else i

    dkv_specs = [
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, qb(j, i), 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bq, dv), lambda b, j, i: (b, qb(j, i), 0)),
        pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, qb(j, i))),
        pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, qb(j, i))),
    ]
    if with_mask:
        dkv_specs.append(
            pl.BlockSpec((1, bk, 1), lambda b, j, i, H=H: (b // H, j, 0)))
    dk, dvv = pl.pallas_call(
        _dkv_kernel(scale, causal, bq, bk, seq_len, with_mask, window),
        grid=(bh, Tp // bk, Tp // bq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Tp, d), k.dtype),
            jax.ShapeDtypeStruct((bh, Tp, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(plans["dkv"]),
        interpret=interpret,
        name="flash_dkv_win" if window else "flash_dkv",
    )(qf, kf, vf, dof, lse, dd, *mask_args)

    unpad = lambda x: x.reshape(B, H, Tp, -1)[:, :, :T, :]  # noqa: E731
    return unpad(dq), unpad(dk), unpad(dvv)


flash_attention_diff.defvjp(_fwd_rule, _bwd_rule)
