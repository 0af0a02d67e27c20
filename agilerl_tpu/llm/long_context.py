"""Sequence-parallel transformer forward: long-context training over an "sp"
mesh axis with ring attention (ICI ppermute), differentiable end-to-end.

This is the long-context capability the reference lacks entirely (SURVEY.md
§5.7: no ring attention / Ulysses / blockwise CP anywhere; it caps context via
max_model_len + chunking). Here the sequence dimension shards across devices:
activations per chip are O(T/P), attention runs blockwise with online softmax
(ops/ring_attention.py), K/V blocks rotate over ICI, and because shard_map is
differentiable the SAME path serves GRPO/DPO training on sequences that do not
fit one chip.

Constraints: right-padded batches (global positions = shard_offset + local
index), T divisible by the sp axis size.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from agilerl_tpu.llm.model import (
    GPTConfig, _maybe_lora, _rms, _rope, _run_layers, logits_fn,
)
from agilerl_tpu.ops.ring_attention import ring_attention


def _block_sp(config: GPTConfig, blk, lora_layer, h, positions, axis_name, lora_scale):
    """One transformer block with ring attention over the sp axis.
    h: [B, T_local, D]; positions: [B, T_local] global positions."""
    B, T, _ = h.shape
    dtype = config.dtype
    x = _rms(h, blk["ln1"], config.rms_eps)
    q = _maybe_lora(x, blk["wq"], lora_layer, "wq", lora_scale, dtype)
    k = _maybe_lora(x, blk["wk"], lora_layer, "wk", lora_scale, dtype)
    v = _maybe_lora(x, blk["wv"], lora_layer, "wv", lora_scale, dtype)
    if config.qkv_bias:
        q = q + blk["bq"].astype(dtype)
        k = k + blk["bk"].astype(dtype)
        v = v + blk["bv"].astype(dtype)
    q = q.reshape(B, T, config.n_head, config.head_dim)
    k = k.reshape(B, T, config.kv_heads, config.head_dim)
    v = v.reshape(B, T, config.kv_heads, config.head_dim)
    q = _rope(q, positions, config.rope_theta)
    k = _rope(k, positions, config.rope_theta)
    rep = config.n_head // config.kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # use_flash_attention routes the per-block engine through the Pallas
    # flash kernel (flash_attention_with_lse + logsumexp merge): the
    # [T_local, T_local] scores never hit HBM, which is the memory ceiling
    # for long-context sp training
    attn = ring_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        axis_name=axis_name, causal=True,
        use_flash=config.use_flash_attention,
    ).astype(dtype)
    attn = attn.reshape(B, T, config.n_head * config.head_dim)
    h = h + _maybe_lora(attn, blk["wo"], lora_layer, "wo", lora_scale, dtype)

    x = _rms(h, blk["ln2"], config.rms_eps)
    gate = _maybe_lora(x, blk["w_gate"], lora_layer, "w_gate", lora_scale, dtype)
    up = _maybe_lora(x, blk["w_up"], lora_layer, "w_up", lora_scale, dtype)
    down = _maybe_lora(
        jax.nn.silu(gate) * up, blk["w_down"], lora_layer, "w_down", lora_scale, dtype
    )
    return h + down


def _forward_local(config: GPTConfig, params, tokens, lora, lora_scale, axis_name):
    """Per-device forward over the local sequence shard."""
    B, T = tokens.shape
    sp_idx = lax.axis_index(axis_name)
    positions = sp_idx * T + jnp.arange(T)[None, :] * jnp.ones((B, 1), jnp.int32)
    h = jnp.take(params["tok_emb"], tokens, axis=0).astype(config.dtype)

    # model.forward's layer loop (one scan a run of equal layers); ring
    # attention's ppermute collectives are legal inside a scan body under
    # shard_map
    def block_fn(h, blk, _, lora_layer):
        return _block_sp(config, blk, lora_layer, h, positions, axis_name,
                         lora_scale), None, 0.0

    h, _, _ = _run_layers(config, params, lora, h, {"attn": block_fn}, {})
    return _rms(h, params["ln_f"], config.rms_eps).astype(jnp.float32)


def make_sp_logprob_fn(config: GPTConfig, mesh: Mesh, axis_name: str = "sp",
                       lora_scale: float = 2.0):
    """Build a jitted fn(params, lora, tokens [B, T]) -> per-token logprobs
    [B, T-1] with the sequence sharded over `axis_name`. Differentiable —
    usable directly inside GRPO/DPO losses for long sequences."""

    def local_fn(params, lora, tokens):
        # tokens: local shard [B, T_local]
        hidden = _forward_local(config, params, tokens, lora, lora_scale, axis_name)
        head = params["tok_emb"].T if config.tie_embeddings else params["lm_head"]
        logits = hidden @ head.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        # target for local position t is tokens[t+1]; the last local target
        # lives on the next shard — fetch its first token via ppermute
        p_size = lax.axis_size(axis_name)
        first_next = lax.ppermute(
            tokens[:, :1], axis_name,
            [(j, (j - 1) % p_size) for j in range(p_size)],
        )
        targets = jnp.concatenate([tokens[:, 1:], first_next], axis=1)  # [B, T_local]
        lp = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return lp  # [B, T_local] — entry t predicts global position off+t+1

    spec_tok = P(None, axis_name)
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(), spec_tok),
        out_specs=spec_tok,
        check_vma=False,
    )

    @jax.jit
    def sp_logprobs(params, lora, tokens):
        lp = fn(params, lora, tokens)  # [B, T]
        return lp[:, :-1]  # last entry predicts beyond the sequence

    return sp_logprobs
