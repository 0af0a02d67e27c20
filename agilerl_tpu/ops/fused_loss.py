"""Pallas fused chunked lm-head + log-softmax kernel — the Liger-kernel
replacement (parity: liger Triton fused GRPO/DPO/CE losses used at
agilerl/algorithms/grpo.py:558, dpo.py:409, and the chunked logprob path
_memory_efficient_logits, core/base.py:2937).

Computes per-token log p(target) WITHOUT materialising the [N, V] logits: the
grid walks vocab chunks innermost, keeping an online (max, sum-exp,
chosen-logit) accumulator in VMEM scratch; each chunk is one [BN, D] x [D, BV]
matmul on the MXU.

``fused_token_logprob`` is the forward kernel; ``fused_token_logprob_diff``
wraps it in a custom VJP (the Liger parity point: liger's losses are
differentiable) whose backward pass RECOMPUTES logits per vocab chunk from the
saved (hidden, head, lse) residuals — two more Pallas kernels (dH accumulates
over vocab blocks, dW over row blocks), so the [N, V] logits never materialise
in either direction. XLA drops the dW kernel of a caller that does not train
the head. On CPU the kernels run in pallas interpret mode (how the tests
exercise them); on TPU they compile natively, under the names
``fused_loss_fwd`` / ``fused_loss_dh`` / ``fused_loss_dw``.

Operand dtypes. The kernels multiply the operands in the dtypes they are
handed and keep everything else in float32: the logits
(``preferred_element_type``), the running max / sum-exp / chosen logit, lse,
and the backward's coefficient g / T * (onehot - p), which is cast to the
operand dtype only for its one matmul (what flash_attention_vjp.py does with
p). Mixed operands promote. ``model.token_logprobs`` hands over both in the
configuration's compute dtype. bf16 operands are half an f32 pair's bytes,
in HBM and in VMEM (so twice the rows fit a tile); they are not fewer MXU
passes and not another rounding, because Mosaic's default precision already
multiplies f32 operands in one bf16 pass (timed on a v5e: the same 51 / 92
ms for the forward / dH either way, PERF.md section 6, PR 28). The
cotangents come back in the operands' dtypes.

Tiles. ``fused_loss_plan`` is the one place that decides them, from (N, D,
V), the two itemsizes, the kernel kind and the device's VMEM, and says what
they cost: the forward and dH re-read the whole head once per row block, so
the row block is what sets their arithmetic intensity (2 * block_n /
itemsize FLOP a byte of head for the forward, twice that for dH, against a
v5e's ridge of 240). A v5e core has 128 MiB of VMEM
(``pltpu.get_tpu_info()``); 16 MiB is only the limit Mosaic scopes a kernel
to by default, and every ``pallas_call`` here raises it
(``vmem_limit_bytes``). The head is never padded or copied: vocab blocks
divide V rounded up to 128, and a ragged last block is masked in the kernel.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from agilerl_tpu.ops.kernel_mode import resolve_interpret

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


# --------------------------------------------------------------------------- #
# The plan: which tile each kernel walks, and what that costs
# --------------------------------------------------------------------------- #

# Preferred tile per kind, from the three kernels timed alone on a v5e at
# the cells' shapes (PERF.md section 6, PR 28). 1024 rows of a bf16 head are
# 1024 FLOP a byte of head for the forward and 2048 for dH, against the
# chip's ridge of 240: past that the head's traffic hides behind the MXU
# and larger tiles only cost VMEM.
_PREFERRED = {"fwd": (1024, 512), "dh": (1024, 512), "dw": (512, 768)}

# A v5e core's VMEM; the figure for a device jax cannot ask (a compile for a
# described topology on a CPU host, interpret mode).
_VMEM_FALLBACK = 128 << 20


class FusedLossPlan(NamedTuple):
    """What one kernel of this module does with a problem of a given shape."""

    block_n: int  # rows a grid step
    block_v: int  # vocab columns a grid step
    head_reads: int  # times the whole [D, V] head comes in from HBM
    hbm_bytes: int  # operands read + results written, head re-reads included
    vmem_bytes: int  # estimated footprint of one grid step, double buffers in
    vmem_limit_bytes: int  # what the pallas_call asks the compiler for


def _vmem_capacity() -> int:
    """VMEM of one core of the device this trace is for."""
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except Exception:  # noqa: BLE001 - not a TPU jax knows: plan for a v5e
        return _VMEM_FALLBACK


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _halvings(top: int, align: int):
    """top, then halves of it snapped down to the alignment, to the floor."""
    out, v = [top], top
    while v > align:
        v = max(align, (v // 2) // align * align)
        out.append(v)
    return out


def _vmem_estimate(bn, bv, D, isz_h, isz_w, kind) -> int:
    """Bytes of VMEM one grid step holds: double-buffered operand blocks in
    their own dtypes, the result block (double-buffered too) with its f32
    accumulator where the result is narrower than f32, the [bn, 1] column
    vectors (each pads to a full 128-lane tile) and the [bn, bv] f32
    softmax intermediates."""
    col, tile = bn * 128 * 4, bn * bv * 4
    ins = 2 * (bn * D * isz_h + D * bv * isz_w)
    if kind == "fwd":  # targets, out, lse double-buffered + (m, s, c)
        return ins + 9 * col + 4 * tile
    cols = 8 * col  # targets, lse, g double-buffered + slack
    if kind == "dh":
        acc = bn * D * 4 if isz_h != 4 else 0
        return ins + cols + acc + 2 * bn * D * isz_h + 5 * tile
    acc = D * bv * 4 if isz_w != 4 else 0
    return ins + cols + acc + 2 * D * bv * isz_w + 5 * tile + bn * D * isz_h


def fused_loss_plan(N: int, D: int, V: int, h_dtype, w_dtype, kind: str,
                    block_n: Optional[int] = None,
                    block_v: Optional[int] = None,
                    vmem_capacity: Optional[int] = None) -> FusedLossPlan:
    """The tile one of the three kernels (``kind``: "fwd", "dh", "dw") walks
    over hidden [N, D] x head [D, V], chosen from the shapes, the operands'
    itemsizes and the device's VMEM alone — and what it costs: how often the
    head is read from HBM, the bytes moved, the VMEM a grid step holds.

    ``block_n`` / ``block_v`` are upper bounds (None: the kind's preferred
    tile). Row blocks are multiples of 8; vocab blocks are multiples of 128
    that divide V rounded up to 128, so the head is never padded or copied
    (a V that is no multiple of 128 leaves a ragged last block, masked in
    the kernel). The plan takes half the device's VMEM and asks the compiler
    for three quarters: 16 MiB is only Mosaic's default scoped limit.

    fwd and dH keep a row block resident and stream the head past it, so
    the head is read ceil(N / block_n) times: the most rows that fit, then
    the most columns. dW keeps a head block resident and streams the rows,
    so the hidden state is read V / block_v times, but a few rows a step
    starve the MXU (16 x 1536 ran five times slower than 512 x 768 on a
    v5e): the largest tile that fits, the wider of two equal ones."""
    if kind not in _PREFERRED:
        raise ValueError(f"kind must be one of {tuple(_PREFERRED)}, got {kind!r}")
    isz_h, isz_w = jnp.dtype(h_dtype).itemsize, jnp.dtype(w_dtype).itemsize
    capacity = vmem_capacity or _vmem_capacity()
    want_n, want_v = _PREFERRED[kind]
    top_n = max(8, min(block_n or want_n, _round_up(N, 8)) // 8 * 8)
    v128 = _round_up(V, 128)
    top_v = max(128, min(block_v or want_v, v128))
    divisors = [d * 128 for d in range(v128 // 128, 0, -1)
                if (v128 // 128) % d == 0 and d * 128 <= top_v]
    tiles = [(bn, bv) for bn in _halvings(top_n, 8) for bv in divisors]
    if kind == "dw":  # the largest tile, the wider of two equal ones
        tiles.sort(key=lambda t: (-t[0] * t[1], -t[1]))
    fits = [t for t in tiles
            if _vmem_estimate(*t, D, isz_h, isz_w, kind) <= capacity // 2]
    bn, bv = fits[0] if fits else (8, 128)  # floor blocks: the best effort
    n_pad = _round_up(N, bn)
    row_blocks, col_blocks = n_pad // bn, v128 // bv
    head_reads = 1 if kind == "dw" else row_blocks
    hidden_reads = col_blocks if kind == "dw" else 1
    hbm = (head_reads * D * V * isz_w + hidden_reads * n_pad * D * isz_h
           + 3 * 4 * n_pad)
    if kind == "dh":
        hbm += n_pad * D * isz_h
    elif kind == "dw":
        hbm += D * V * isz_w
    return FusedLossPlan(
        bn, bv, head_reads, hbm,
        _vmem_estimate(bn, bv, D, isz_h, isz_w, kind), capacity * 3 // 4)


# --------------------------------------------------------------------------- #
# Kernels
# --------------------------------------------------------------------------- #


def _logits_tile(h, w, j, inv_temp, vocab_size, ragged):
    """One [BN, BV] tile of logits in f32, and its column numbers relative
    to the tile. ``ragged``: V is no multiple of BV, so the last tile's
    columns past V hold whatever the pipeline left there."""
    logits = jnp.dot(h, w, preferred_element_type=jnp.float32) * inv_temp
    lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = lane < vocab_size - j * logits.shape[1] if ragged else None
    return logits, lane, valid


def _make_kernel(vocab_size: int, inv_temp: float, ragged: bool):
    def kernel(hidden_ref, head_ref, target_ref, out_ref, lse_ref, m_ref, s_ref, c_ref):
        j = pl.program_id(1)
        nv = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, -1e30)
            s_ref[:] = jnp.zeros_like(s_ref)
            c_ref[:] = jnp.zeros_like(c_ref)

        logits, lane, valid = _logits_tile(
            hidden_ref[:], head_ref[:], j, inv_temp, vocab_size, ragged)
        if ragged:
            logits = jnp.where(valid, logits, -1e30)
        bv = logits.shape[1]

        hit = lane == target_ref[:] - j * bv  # targets: [BN, 1]
        c_ref[:] = c_ref[:] + jnp.sum(
            jnp.where(hit, logits, 0.0), axis=1, keepdims=True
        )

        m_old = m_ref[:]
        m_new = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
        s_ref[:] = s_ref[:] * jnp.exp(m_old - m_new) + jnp.sum(
            jnp.exp(logits - m_new), axis=1, keepdims=True
        )
        m_ref[:] = m_new

        @pl.when(j == nv - 1)
        def _finish():
            lse = m_ref[:] + jnp.log(s_ref[:])
            out_ref[:] = c_ref[:] - lse
            lse_ref[:] = lse

    return kernel


def _bwd_coef(h, w, target_ref, lse_ref, g_ref, j, inv_temp, vocab_size,
              ragged):
    """Recompute softmax probs for one (row-block, vocab-block) tile and
    return the shared bwd coefficient g / T * (onehot(target) - p), in f32:
    the caller casts it to its operand dtype for its one matmul."""
    logits, lane, valid = _logits_tile(h, w, j, inv_temp, vocab_size, ragged)
    p = jnp.exp(logits - lse_ref[:])
    if ragged:
        p = jnp.where(valid, p, 0.0)
    hit = lane == target_ref[:] - j * logits.shape[1]
    return (hit.astype(jnp.float32) - p) * (g_ref[:] * inv_temp)  # [BN, BV]


def _make_dh_kernel(vocab_size: int, inv_temp: float, ragged: bool):
    """grid (i, j), j innermost: accumulate dH_i over vocab blocks, straight
    into the resident f32 output block, or into an f32 scratch where the
    output is narrower."""

    def kernel(hidden_ref, head_ref, target_ref, lse_ref, g_ref, dh_ref, *scratch):
        acc_ref = scratch[0] if scratch else dh_ref
        j = pl.program_id(1)
        nv = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        w = head_ref[:]
        if ragged:  # 0 * (whatever lies past V) must stay 0 in coef @ w.T
            lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
            w = jnp.where(lane < vocab_size - j * w.shape[1], w,
                          jnp.zeros_like(w))
        coef = _bwd_coef(hidden_ref[:], w, target_ref, lse_ref, g_ref, j,
                         inv_temp, vocab_size, ragged)
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            coef.astype(w.dtype), w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        if scratch:
            @pl.when(j == nv - 1)
            def _finish():
                dh_ref[:] = acc_ref[:].astype(dh_ref.dtype)

    return kernel


def _make_dw_kernel(vocab_size: int, inv_temp: float, ragged: bool):
    """grid (j, i), i innermost: accumulate dW_j over row blocks (resident
    f32 output block, or f32 scratch where the output is narrower)."""

    def kernel(hidden_ref, head_ref, target_ref, lse_ref, g_ref, dw_ref, *scratch):
        acc_ref = scratch[0] if scratch else dw_ref
        i = pl.program_id(1)
        ni = pl.num_programs(1)
        j = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        h = hidden_ref[:]
        coef = _bwd_coef(h, head_ref[:], target_ref, lse_ref, g_ref, j,
                         inv_temp, vocab_size, ragged)
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            h, coef.astype(h.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        if scratch:
            @pl.when(i == ni - 1)
            def _finish():
                dw_ref[:] = acc_ref[:].astype(dw_ref.dtype)

    return kernel


# --------------------------------------------------------------------------- #
# Calls
# --------------------------------------------------------------------------- #


def _pad_rows(x, rows):
    """Zero rows up to a whole number of row blocks (the hidden state and the
    per-row vectors; the head is never padded)."""
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _column(x, rows, dtype):
    return _pad_rows(x.astype(dtype), rows)[:, None]


def _call(kernel, name, plan, grid, in_specs, out_specs, out_shape, scratch,
          flops, interpret, operands):
    if pltpu is None:  # pragma: no cover - CPU wheels without pltpu
        raise RuntimeError("pallas tpu module unavailable")
    rows, vocab = operands[0].shape[0], operands[1].shape[1]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=rows * vocab,
            bytes_accessed=plan.hbm_bytes),
        interpret=interpret,
        name=name,
    )(*operands)


def _fwd_call(hidden, head, targets, temperature, block_n, block_v, interpret):
    interpret = resolve_interpret(interpret)
    N, D = hidden.shape
    V = head.shape[1]
    plan = fused_loss_plan(N, D, V, hidden.dtype, head.dtype, "fwd",
                           block_n, block_v)
    bn, bv = plan.block_n, plan.block_v
    rows = _round_up(N, bn)
    col = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    out, lse = _call(
        _make_kernel(V, 1.0 / temperature, V % bv != 0), "fused_loss_fwd",
        plan, (rows // bn, pl.cdiv(V, bv)),
        [pl.BlockSpec((bn, D), lambda i, j: (i, 0)),
         pl.BlockSpec((D, bv), lambda i, j: (0, j)), col],
        [col, col],
        [jax.ShapeDtypeStruct((rows, 1), jnp.float32)] * 2,
        [pltpu.VMEM((bn, 1), jnp.float32) for _ in range(3)],
        2 * rows * D * V, interpret,
        (_pad_rows(hidden, rows), head, _column(targets, rows, jnp.int32)))
    return out[:N, 0], lse[:N, 0]


@functools.partial(
    jax.jit, static_argnames=("temperature", "block_n", "block_v", "interpret")
)
def fused_token_logprob(
    hidden: jax.Array,  # [N, D]
    head: jax.Array,  # [D, V]
    targets: jax.Array,  # [N] int
    temperature: float = 1.0,
    block_n: Optional[int] = None,
    block_v: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-row log softmax(hidden @ head / T)[target]. Returns [N] float32.
    ``block_n`` / ``block_v`` bound the tile ``fused_loss_plan`` picks.
    Forward-only entry point; use ``fused_token_logprob_diff`` inside losses."""
    return _fwd_call(hidden, head, targets, temperature, block_n, block_v,
                     interpret)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_token_logprob_diff(
    hidden: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    temperature: float = 1.0,
    block_n: Optional[int] = None,
    block_v: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Differentiable fused per-token logprob (the Liger parity point: liger's
    fused GRPO/DPO/CE losses are differentiable, ref grpo.py:558, dpo.py:409).
    Backward recomputes logits per vocab chunk from (hidden, head, lse) — the
    [N, V] logits never materialise in either pass."""
    return _fwd_call(hidden, head, targets, temperature, block_n, block_v,
                     interpret)[0]


def _diff_fwd(hidden, head, targets, temperature, block_n, block_v, interpret):
    out, lse = _fwd_call(hidden, head, targets, temperature, block_n, block_v,
                         interpret)
    return out, (hidden, head, targets, lse)


def _diff_bwd(temperature, block_n, block_v, interpret, res, g):
    hidden, head, targets, lse = res
    interpret = resolve_interpret(interpret)
    N, D = hidden.shape
    V = head.shape[1]
    inv_temp = 1.0 / temperature

    def bwd_call(kind, make_kernel, out_dtype):
        # the two kernels hold different result blocks (dh: [BN, D], dw:
        # [D, BV]), so each has its own plan. Padded rows get g = 0 and so
        # contribute nothing to either.
        plan = fused_loss_plan(N, D, V, hidden.dtype, head.dtype, kind,
                               block_n, block_v)
        bn, bv = plan.block_n, plan.block_v
        rows = _round_up(N, bn)
        blocks = (rows // bn, pl.cdiv(V, bv))
        if kind == "dh":  # vocab innermost; the result follows the rows
            grid, row, vocab = blocks, (lambda i, j: (i, 0)), (lambda i, j: (0, j))
            out_shape, out_spec = (rows, D), pl.BlockSpec((bn, D), row)
        else:  # rows innermost; the result follows the vocab
            grid, row, vocab = blocks[::-1], (lambda j, i: (i, 0)), (lambda j, i: (0, j))
            out_shape, out_spec = (D, V), pl.BlockSpec((D, bv), vocab)
        col = pl.BlockSpec((bn, 1), row)
        narrow = jnp.dtype(out_dtype) != jnp.float32
        return _call(
            make_kernel(V, inv_temp, V % bv != 0), f"fused_loss_{kind}", plan,
            grid,
            [pl.BlockSpec((bn, D), row), pl.BlockSpec((D, bv), vocab),
             col, col, col],
            out_spec, jax.ShapeDtypeStruct(out_shape, out_dtype),
            [pltpu.VMEM(out_spec.block_shape, jnp.float32)] if narrow else [],
            4 * rows * D * V, interpret,
            (_pad_rows(hidden, rows), head,
             _column(targets, rows, jnp.int32),
             _column(lse, rows, jnp.float32), _column(g, rows, jnp.float32)))

    dh = bwd_call("dh", _make_dh_kernel, hidden.dtype)
    dw = bwd_call("dw", _make_dw_kernel, head.dtype)
    dtargets = np.zeros(targets.shape, jax.dtypes.float0)
    return dh[:N], dw, dtargets


fused_token_logprob_diff.defvjp(_diff_fwd, _diff_bwd)


def reference_token_logprob(hidden, head, targets, temperature: float = 1.0):
    """Dense reference for tests."""
    logits = (hidden.astype(jnp.float32) @ head.astype(jnp.float32)) / temperature
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, targets[:, None].astype(jnp.int32), axis=1)[:, 0]
