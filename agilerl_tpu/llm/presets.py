"""Named model presets for the flagship GRPO stack.

The reference reads arbitrary HF checkpoints (its 7B headline workload is a
Llama-class model served through vLLM + DeepSpeed,
/root/reference/agilerl/algorithms/core/base.py:3101); here the equivalent
"flagship" sizes are first-class GPTConfig presets so benchmarks, the 7B
dress rehearsal (benchmarking/grpo_7b_plan.py) and tests all agree on dims.

Dims match the public architectures exactly (so an HF checkpoint of the same
family loads straight into the preset via llm/hf.load_hf_model).
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from agilerl_tpu.llm.model import GPTConfig

# dims: (vocab, n_layer, n_head, n_kv_head, d_model, d_ff, max_seq_len)
_PRESETS: Dict[str, Dict[str, Any]] = {
    # GPT-2 small — a model that fits any single chip
    "gpt2-small": dict(
        vocab_size=50_257, n_layer=12, n_head=12, n_kv_head=12, d_model=768,
        d_ff=3_072, max_seq_len=1_024, rope_theta=10_000.0,
    ),
    # Llama-2-7B: MHA (no GQA), 4k context
    "llama2-7b": dict(
        vocab_size=32_000, n_layer=32, n_head=32, n_kv_head=32, d_model=4_096,
        d_ff=11_008, max_seq_len=4_096, rope_theta=10_000.0,
        tie_embeddings=False,
    ),
    # Llama-3-8B: GQA 8 kv-heads, 128k vocab — the BASELINE.md 7B-class
    # target model for the >=35% MFU goal
    "llama3-8b": dict(
        vocab_size=128_256, n_layer=32, n_head=32, n_kv_head=8, d_model=4_096,
        d_ff=14_336, max_seq_len=8_192, rope_theta=500_000.0,
        tie_embeddings=False,
    ),
    # Qwen2-7B: GQA 4 kv-heads, attention biases
    "qwen2-7b": dict(
        vocab_size=152_064, n_layer=28, n_head=28, n_kv_head=4, d_model=3_584,
        d_ff=18_944, max_seq_len=32_768, rope_theta=1_000_000.0,
        tie_embeddings=False, qkv_bias=True,
    ),
    # AI21-Jamba2-3B: a hybrid stack — 26 Mamba-1 layers (d_inner 5120,
    # state 16, conv 4, dt rank 160) beside 2 attention layers (layers 7 and
    # 21: i % 14 == 7; 20 query heads on ONE KV head of 128, no positional
    # encoding), dense SwiGLU everywhere, head tied to the embedding. The
    # one source of these sizes for tests, the benchmark's runner and docs
    # (perfbench/configs/jamba2-3b.json repeats the published keys).
    "jamba2-3b": dict(
        vocab_size=65_536, n_layer=28, n_head=20, n_kv_head=1, d_model=2_560,
        d_ff=8_192, max_seq_len=262_144, tie_embeddings=True, rope=False,
        attn_layer_period=14, attn_layer_offset=7, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160,
    ),
    # A DeepSeek-V3-class stack at toy sizes, for the CPU tests: latent
    # attention (no query latent), 1 leading dense layer, then 2 expert
    # layers of 8 sigmoid-routed experts, 2 a token and none dropped, a
    # drawn selection bias, one shared expert. Nothing of it is a model.
    "tiny-mla-moe": dict(
        vocab_size=256, n_layer=3, n_head=4, d_model=64, d_ff=128,
        max_seq_len=256, rope_theta=10_000.0, tie_embeddings=False,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        n_dense_layers=1, n_experts=8, expert_top_k=2, capacity_factor=None,
        d_ff_expert=32, d_ff_shared=64, router_score="sigmoid",
        router_bias=True, norm_topk=True, routed_scale=2.5,
    ),
    # A ZAYA1-class stack at toy sizes, for the CPU tests: compressed
    # convolutional attention (4 query heads on 2 key heads of 16, in a
    # latent 64 / 32 wide under d_model 48; kernels 2 and 2; rotary on half
    # a head) with its rolling state, then 8 experts of which ONE a token,
    # chosen by a router MLP 16 wide over a state carried from layer to
    # layer (softmax, a drawn selection bias, the probability itself as the
    # weight), scale and bias on both arms of every merge, a tied head.
    # Nothing of it is a model.
    "tiny-cca-moe": dict(
        vocab_size=256, n_layer=3, n_head=4, n_kv_head=2, head_size=16,
        d_model=48, max_seq_len=256, rope_theta=5_000_000.0, rms_eps=1e-5,
        tie_embeddings=True, cca_time0=2, cca_time1=2, rotary_share=0.5,
        n_experts=8, expert_top_k=1, capacity_factor=None, d_ff_expert=32,
        router_score="softmax", router_bias=True, norm_topk=False,
        router_hidden=16, scaled_merge=True,
    ),
}


def preset_names():
    return sorted(_PRESETS)


def preset(name: str, **overrides: Any) -> GPTConfig:
    """Build a GPTConfig for a named architecture. Overrides win — e.g.
    ``preset("llama3-8b", max_seq_len=1024, remat=True)`` for a training
    config with a shorter context and per-block rematerialisation.

    Defaults bf16 + remat + flash attention: the TPU training recipe."""
    if name not in _PRESETS:
        raise KeyError(f"unknown preset {name!r}; available: {preset_names()}")
    kw: Dict[str, Any] = dict(_PRESETS[name])
    kw.setdefault("dtype", jnp.bfloat16)
    kw.setdefault("remat", True)
    kw.setdefault("use_flash_attention", True)
    kw.update(overrides)
    return GPTConfig(**kw)
