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
TPU. Supports an optional [B, T] padding mask like the forward kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from agilerl_tpu.ops.kernel_mode import resolve_interpret

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


# --------------------------------------------------------------------------- #
# Forward kernel that also emits L = m + log(l)
# --------------------------------------------------------------------------- #


def _fwd_kernel(scale, causal, block_q, block_k, seq_len, with_mask):
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

        def body():
            q, k, v = q_ref[0], k_ref[0], v_ref[0]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = k_ids < seq_len
            if causal:
                mask = jnp.logical_and(mask, k_ids <= q_ids)
            if pm_ref is not None:
                mask = jnp.logical_and(mask, pm_ref[0] > 0)
            s = jnp.where(mask, s, -1e30)
            m_old = m_ref[:]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            m_ref[:] = m_new

        if causal:
            @pl.when(kj * block_k <= qi * block_q + block_q - 1)
            def _run():
                body()
        else:
            body()

        @pl.when(kj == nk - 1)
        def _done():
            out_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(out_ref.dtype)
            lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))

    return kernel


def _dq_kernel(scale, causal, block_q, block_k, seq_len, with_mask):
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

        def body():
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = k_ids < seq_len
            if causal:
                mask = jnp.logical_and(mask, k_ids <= q_ids)
            if pm_ref is not None:
                mask = jnp.logical_and(mask, pm_ref[0] > 0)
            p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
            dov = jnp.dot(do, v.T, preferred_element_type=jnp.float32)  # [BQ, BK]
            ds = p * (dov - dd_ref[0])
            acc_ref[:] = acc_ref[:] + jnp.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32
            ) * scale

        if causal:
            @pl.when(kj * block_k <= qi * block_q + block_q - 1)
            def _run():
                body()
        else:
            body()

        @pl.when(kj == nk - 1)
        def _done():
            dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)

    return kernel


def _dkv_kernel(scale, causal, block_q, block_k, seq_len, with_mask):
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

        def body():
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = k_ids < seq_len
            if causal:
                mask = jnp.logical_and(mask, k_ids <= q_ids)
            if pm_ref is not None:
                mask = jnp.logical_and(mask, pm_ref[0] > 0)
            p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
            dv_acc[:] = dv_acc[:] + jnp.dot(
                p.T.astype(do.dtype), do, preferred_element_type=jnp.float32
            )
            dov = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dov - dd_ref[0])
            dk_acc[:] = dk_acc[:] + jnp.dot(
                ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32
            ) * scale

        if causal:
            # q blocks strictly before this kv block contribute nothing
            @pl.when(qi * block_q + block_q - 1 >= kj * block_k)
            def _run():
                body()
        else:
            body()

        @pl.when(qi == nq - 1)
        def _done():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    return kernel


# --------------------------------------------------------------------------- #
# custom_vjp wrapper
# --------------------------------------------------------------------------- #


def _pad_t(x, pad):
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_diff(
    q: jax.Array,  # [B, H, T, d]
    k: jax.Array,
    v: jax.Array,
    padding_mask: Optional[jax.Array] = None,  # [B, T] 1=real
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    spmd: bool = True,
) -> jax.Array:
    """``spmd=True`` (default) routes through the custom_partitioning
    wrappers so plain-GSPMD callers shard over (batch, heads) at runtime;
    pass ``spmd=False`` when calling from inside an explicit shard_map
    (e.g. model.py's ``flash_shard_axes`` path — the AOT-compatible route:
    custom_partitioning needs a runtime python callback that compile-only
    PJRT clients don't host, 'Custom emitter for CustomSPMDPartitioning
    not found')."""
    out, _ = _fwd_rule(q, k, v, padding_mask, causal, block_q, block_k,
                       interpret, spmd)
    return out


def _prep(q, T, block_q, block_k):
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    # pad to a multiple of BOTH block sizes, else the grid floor-division
    # silently drops trailing rows (review finding)
    pad = (-T) % math.lcm(block_q, block_k)
    return block_q, block_k, pad


def _fwd(q, k, v, padding_mask, causal, block_q, block_k, interpret):
    interpret = resolve_interpret(interpret)
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas tpu module unavailable")
    B, H, T, d = q.shape
    dv = v.shape[-1]  # values may be narrower than queries and keys
    scale = 1.0 / math.sqrt(d)
    block_q, block_k, pad = _prep(q, T, block_q, block_k)
    Tp = T + pad
    qf = _pad_t(q, pad).reshape(B * H, Tp, d)
    kf = _pad_t(k, pad).reshape(B * H, Tp, d)
    vf = _pad_t(v, pad).reshape(B * H, Tp, dv)
    with_mask = padding_mask is not None
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
    ]
    args = [qf, kf, vf]
    if with_mask:
        # mask rides lanes as [B, 1, Tp] / lse rides sublanes as
        # [bh, Tp, 1]: both satisfy Mosaic's last-two-dims block rule in
        # their natural broadcast orientation (no in-kernel transposes).
        # 2-D (rows, Tp) aux arrays with (1, block) blocks fail the TPU
        # lowering whenever rows > 1 — caught by the AOT harness
        # (benchmarking/tpu_aot_compile.py), invisible to interpret mode.
        mp = jnp.pad(padding_mask.astype(jnp.int32), ((0, 0), (0, pad)))
        mp = mp.reshape(B, 1, Tp)
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j, H=H: (b // H, 0, j)))
        args.append(mp)
    grid = (B * H, Tp // block_q, Tp // block_k)
    out, lse = pl.pallas_call(
        _fwd_kernel(scale, causal, block_q, block_k, T, with_mask),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    out4 = out.reshape(B, H, Tp, dv)[:, :, :T, :]
    # lse rides as [B, H, Tp, 1] so the GSPMD partitioning rule can map its
    # leading dims 1:1 onto q's (batch, heads) axes
    return out4, lse.reshape(B, H, Tp, 1)


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
def _partitioned_fwd(causal, block_q, block_k, interpret, with_mask):
    from jax.experimental.custom_partitioning import custom_partitioning

    def impl(*args):
        q, k, v = args[:3]
        mask = args[3] if with_mask else None
        return _fwd(q, k, v, mask, causal, block_q, block_k, interpret)

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
def _partitioned_bwd(causal, block_q, block_k, interpret, with_mask):
    from jax.experimental.custom_partitioning import custom_partitioning

    def impl(*args):
        q, k, v, do, out, lse = args[:6]
        mask = args[6] if with_mask else None
        return _bwd_arrays(q, k, v, do, out, lse, mask, causal, block_q,
                           block_k, interpret)

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
              spmd=True):
    concrete = resolve_interpret(interpret)
    with_mask = padding_mask is not None
    if spmd:
        args = (q, k, v) + ((padding_mask,) if with_mask else ())
        out, lse = _partitioned_fwd(causal, block_q, block_k, concrete,
                                    with_mask)(*args)
    else:
        out, lse = _fwd(q, k, v, padding_mask, causal, block_q, block_k,
                        concrete)
    return out, (q, k, v, padding_mask, out, lse)


def _bwd_rule(causal, block_q, block_k, interpret, spmd, res, do):
    q, k, v, padding_mask, out, lse = res
    concrete = resolve_interpret(interpret)
    with_mask = padding_mask is not None
    if spmd:
        args = (q, k, v, do, out, lse) + ((padding_mask,) if with_mask else ())
        dq, dk, dv = _partitioned_bwd(causal, block_q, block_k, concrete,
                                      with_mask)(*args)
    else:
        dq, dk, dv = _bwd_arrays(q, k, v, do, out, lse, padding_mask,
                                 causal, block_q, block_k, concrete)
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_with_lse(
    q: jax.Array,  # [B, H, T, d]
    k: jax.Array,
    v: jax.Array,
    padding_mask: Optional[jax.Array] = None,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
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
    return out, lse4[:, :, :T, 0]


def _with_lse_fwd(q, k, v, padding_mask, causal, block_q, block_k, interpret):
    out, lse4 = _fwd(q, k, v, padding_mask, causal, block_q, block_k,
                     resolve_interpret(interpret))
    T = q.shape[2]
    return (out, lse4[:, :, :T, 0]), (q, k, v, padding_mask, out, lse4)


def _with_lse_bwd(causal, block_q, block_k, interpret, res, cts):
    q, k, v, padding_mask, out, lse4 = res
    do, dlse = cts
    dq, dk, dv = _bwd_arrays(q, k, v, do, out, lse4, padding_mask, causal,
                             block_q, block_k, resolve_interpret(interpret),
                             dlse=dlse)
    return dq, dk, dv, None


flash_attention_with_lse.defvjp(_with_lse_fwd, _with_lse_bwd)


def _bwd_arrays(q, k, v, do, out, lse, padding_mask, causal, block_q,
                block_k, interpret, dlse=None):
    interpret = resolve_interpret(interpret)
    B, H, T, d = q.shape
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(d)
    block_q, block_k, pad = _prep(q, T, block_q, block_k)
    Tp = T + pad
    bh = B * H
    qf = _pad_t(q, pad).reshape(bh, Tp, d)
    kf = _pad_t(k, pad).reshape(bh, Tp, d)
    vf = _pad_t(v, pad).reshape(bh, Tp, dv)
    dof = _pad_t(do, pad).reshape(bh, Tp, dv)
    lse = lse.reshape(bh, Tp, 1)  # arrives [B, H, Tp, 1] (partition layout)
    # D_i = rowsum(dO * O); dd sublane-oriented like lse. An lse cotangent
    # (flash_attention_with_lse) enters as dS += p * dlse == dd -= dlse.
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        dd = dd - dlse.astype(jnp.float32)
    dd = jnp.pad(dd, ((0, 0), (0, 0), (0, pad))).reshape(bh, Tp, 1)
    with_mask = padding_mask is not None
    mask_args = []
    if with_mask:
        mask_args = [jnp.pad(
            padding_mask.astype(jnp.int32), ((0, 0), (0, pad))
        ).reshape(B, 1, Tp)]

    common_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # q by qi
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),  # k by kj
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),  # v by kj
        pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),  # do by qi
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),  # lse by qi
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),  # dd by qi
    ]
    if with_mask:
        common_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j, H=H: (b // H, 0, j))
        )
    dq = pl.pallas_call(
        _dq_kernel(scale, causal, block_q, block_k, T, with_mask),
        grid=(bh, Tp // block_q, Tp // block_k),
        in_specs=common_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, Tp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(qf, kf, vf, dof, lse, dd, *mask_args)

    dkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, dv), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
    ]
    if with_mask:
        dkv_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, j, i, H=H: (b // H, 0, j))
        )
    dk, dvv = pl.pallas_call(
        _dkv_kernel(scale, causal, block_q, block_k, T, with_mask),
        grid=(bh, Tp // block_k, Tp // block_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Tp, d), k.dtype),
            jax.ShapeDtypeStruct((bh, Tp, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(qf, kf, vf, dof, lse, dd, *mask_args)

    unpad = lambda x: x.reshape(B, H, Tp, -1)[:, :, :T, :]  # noqa: E731
    return unpad(dq), unpad(dk), unpad(dvv)


flash_attention_diff.defvjp(_fwd_rule, _bwd_rule)
