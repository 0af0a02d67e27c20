"""Pipeline parallelism (GPipe-style microbatching over a "pp" mesh axis).

Beyond reference parity: the reference has no pipeline parallelism
(SURVEY.md §2.8 row "Pipeline parallelism: absent"); this completes the
dp/fsdp/tp/sp/ep/pp strategy menu.

TPU-first design: the transformer blocks are stacked into one [L, ...] pytree
and split into S contiguous stages sharded ``P("pp", ...)``. A ``shard_map``
program runs the classic GPipe schedule as a ``lax.scan`` over M + S - 1
ticks: every tick each stage applies its local layers (a ``lax.scan`` over
the stage's slice) and hands its activation to the next stage with a single
``lax.ppermute`` hop over ICI. Because the schedule is a scan of pure ops
(ppermute included), reverse-mode AD through the whole pipeline works out of
the box — XLA replays the ticks backwards, giving the standard GPipe
backward schedule without hand-written send/recv code (contrast: torch PP
frameworks hand-schedule NCCL p2p ops).

Embedding and the LM head stay replicated outside the shard_map (they are
cheap and XLA dedupes the computation); only the block stack is staged.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from agilerl_tpu.llm.model import GPTConfig, _rms, block_apply_dense

Params = Any


def stack_blocks(params: Params, config: GPTConfig) -> Params:
    """Per-layer dicts -> one stacked [L, ...] tree. Requires homogeneous
    blocks (dense everywhere, or MoE with moe_every == 1)."""
    blocks = [params["blocks"][str(i)] for i in range(config.n_layer)]
    keys0 = set(blocks[0])
    assert all(set(b) == keys0 for b in blocks), (
        "pipeline stages need homogeneous blocks (interleaved MoE unsupported)"
    )
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)


def unstack_blocks(stacked: Params, config: GPTConfig) -> Dict[str, Params]:
    return {
        str(i): jax.tree_util.tree_map(lambda x: x[i], stacked)
        for i in range(config.n_layer)
    }


def pipeline_hidden_fn(
    config: GPTConfig,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    fsdp_axis: Optional[str] = None,
):
    """Build ``fn(stacked_blocks, h0, mask, positions) -> hidden`` running the
    block stack as a GPipe pipeline over ``mesh[axis]``.

    - ``stacked_blocks``: [L, ...] tree (shard with ``P(axis)`` on dim 0, and
      ``P(axis, fsdp_axis)`` when composing with FSDP)
    - ``h0``: [B, T, d] embedded inputs; B % num_microbatches == 0
    - returns final hidden [B, T, d]

    With ``fsdp_axis`` set (pp x fsdp composition), each stage's weights are
    additionally sharded on their first non-stage dim at rest and all-gathered
    per-layer inside a rematerialised scan body — forward gathers one layer at
    a time, backward re-gathers and reverse-mode AD transposes the gather into
    a reduce-scatter, i.e. the ZeRO grad/memory flow — and the batch is
    sharded over the same axis (each fsdp group pipelines its own rows; B
    must divide by mesh.shape[fsdp_axis] * num_microbatches).
    """
    S = mesh.shape[axis]
    assert config.n_layer % S == 0, "n_layer must divide into pipeline stages"
    if fsdp_axis is not None:
        F = mesh.shape[fsdp_axis]
        hd = config.head_dim
        for dim, what in (
            (config.d_model, "d_model"),
            (config.ff_dim, "ff_dim"),
            (config.n_head * hd, "n_head*head_dim"),
            (config.kv_heads * hd, "kv_heads*head_dim"),
        ):
            assert dim % F == 0, (
                f"pp x fsdp: {what}={dim} must divide by the fsdp axis size {F}"
            )
    M = num_microbatches

    def staged(local_blocks, h0, mask, positions):
        # local_blocks leaves: [L/S, ...] (shard_map strips the stage dim)
        sid = jax.lax.axis_index(axis)
        B = h0.shape[0]
        mb = B // M
        h_mb = h0.reshape(M, mb, *h0.shape[1:])
        mask_mb = mask.reshape(M, mb, *mask.shape[1:])
        pos_mb = positions.reshape(M, mb, *positions.shape[1:])

        def apply_stage(h, m, p):
            def one_layer(carry, blk):
                if fsdp_axis is not None:
                    # ZeRO: this layer's weights live sharded (dim 0 here —
                    # scan consumed the stage dim); gather just-in-time.
                    # Inside jax.checkpoint the residual is the SHARDED blk:
                    # backward re-gathers, and AD transposes the gather into
                    # a reduce-scatter of the weight cotangent.
                    blk = jax.tree_util.tree_map(
                        lambda x: jax.lax.all_gather(
                            x, fsdp_axis, axis=0, tiled=True
                        ),
                        blk,
                    )
                return block_apply_dense(config, blk, carry, m, p), None

            if fsdp_axis is not None:
                one_layer = jax.checkpoint(one_layer)
            out, _ = jax.lax.scan(one_layer, h, local_blocks)
            return out

        zeros = jnp.zeros((mb,) + h0.shape[1:], h0.dtype)
        out_buf = jnp.zeros((M, mb) + h0.shape[1:], h0.dtype)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            h_in, out_buf = carry
            mb_idx = t - sid  # microbatch this stage handles at tick t
            safe = jnp.clip(mb_idx, 0, M - 1)
            # stage 0 ingests a fresh microbatch; others use the received act
            h_cur = jnp.where(sid == 0, h_mb[jnp.clip(t, 0, M - 1)], h_in)
            h_out = apply_stage(h_cur, mask_mb[safe], pos_mb[safe])
            active = (mb_idx >= 0) & (mb_idx < M)
            written = jax.lax.dynamic_update_index_in_dim(
                out_buf, h_out, safe, axis=0
            )
            out_buf = jnp.where((sid == S - 1) & active, written, out_buf)
            h_next = jax.lax.ppermute(h_out, axis, fwd_perm)
            return (h_next, out_buf), None

        (_, out_buf), _ = jax.lax.scan(
            tick, (zeros, out_buf), jnp.arange(M + S - 1)
        )
        # broadcast the last stage's outputs to every stage
        out_buf = jax.lax.psum(
            jnp.where(sid == S - 1, out_buf, jnp.zeros_like(out_buf)), axis
        )
        return out_buf.reshape(B, *h0.shape[1:])

    # stacked blocks shard on the stage (layer-group) dim (+ fsdp on dim 1);
    # data replicated, or batch-sharded over the fsdp axis when composing
    block_spec = P(axis) if fsdp_axis is None else P(axis, fsdp_axis)
    data_spec = P() if fsdp_axis is None else P(fsdp_axis)
    return shard_map(
        staged,
        mesh=mesh,
        in_specs=(block_spec, data_spec, data_spec, data_spec),
        out_specs=data_spec,
        check_vma=False,
    )


def pipeline_apply(
    config: GPTConfig,
    params: Params,
    tokens: jax.Array,
    mesh: Mesh,
    num_microbatches: int = 2,
    attention_mask: Optional[jax.Array] = None,
    axis: str = "pp",
    stacked: Optional[Params] = None,
    fsdp_axis: Optional[str] = None,
) -> jax.Array:
    """Full forward to logits with the block stack pipelined over ``axis``
    (optionally composed with ZeRO sharding + batch sharding over
    ``fsdp_axis`` — see pipeline_hidden_fn).

    Pass ``stacked=stack_blocks(params, config)`` (placed via
    ``shard_stacked_blocks(..., fsdp_axis=...)`` with the same axes used
    here) to avoid re-stacking per call inside jit."""
    assert config.n_experts == 0, (
        "pipeline_apply stages the dense block program; pp x MoE composition "
        "is not supported yet (shard experts on ep instead)"
    )
    if fsdp_axis is not None:
        F = mesh.shape[fsdp_axis]
        assert tokens.shape[0] % (F * num_microbatches) == 0, (
            f"pp x fsdp: batch {tokens.shape[0]} must divide by fsdp size {F} "
            f"x num_microbatches {num_microbatches}"
        )
    if attention_mask is None:
        attention_mask = jnp.ones(tokens.shape, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(attention_mask, axis=-1) - 1, 0)
    h0 = jnp.take(params["tok_emb"], tokens, axis=0).astype(config.dtype)
    if stacked is None:
        stacked = stack_blocks(params, config)
    fn = pipeline_hidden_fn(config, mesh, num_microbatches, axis, fsdp_axis)
    hidden = fn(stacked, h0, attention_mask, positions)
    hidden = _rms(hidden, params["ln_f"], config.rms_eps).astype(jnp.float32)
    head = params["tok_emb"].T if config.tie_embeddings else params["lm_head"]
    return hidden @ head.astype(jnp.float32)


def shard_stacked_blocks(
    stacked: Params,
    mesh: Mesh,
    axis: str = "pp",
    fsdp_axis: Optional[str] = None,
) -> Params:
    """Place a stacked block tree for the pipeline: stage dim on ``axis``,
    and (for the pp x fsdp composition) weight dim 1 on ``fsdp_axis`` so the
    at-rest copy is genuinely ZeRO-sharded, matching pipeline_hidden_fn's
    in_specs — any mismatch would just be resharded on every call."""
    spec = P(axis) if fsdp_axis is None else P(axis, fsdp_axis)
    sh = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), stacked)
