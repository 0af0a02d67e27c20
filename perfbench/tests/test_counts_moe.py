"""``counts_moe`` held to hand arithmetic: on a model small enough to count
in one's head, and at the published widths of ``kanana2-30b-a3b``."""

import json
from pathlib import Path

from perfbench import counts_moe as C

# hidden 8, 2 heads of nope 4 | rope 2 | v 4 on a latent of 6; dense SwiGLU
# 16; 4 experts 3 wide, 2 a token, 2 shared; vocab 32; 1 dense + 2 expert
TINY = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 6,
        "intermediate_size": 16, "moe_intermediate_size": 3,
        "n_shared_experts": 2, "n_routed_experts": 4, "num_experts_per_tok": 2,
        "vocab_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1}
REAL = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "kanana2-30b-a3b.json").read_text())


def test_layers_by_kind():
    assert C.layer_counts(TINY) == {"dense": 1, "expert": 2}
    assert C.layer_counts(REAL) == {"dense": 1, "expert": 7}


def test_attention_projections():
    # wq 8x12 + wkv_a 8x8 + wkv_b 6x16 + wo 8x8 = 96 + 64 + 96 + 64
    assert C.mla_params(TINY) == 320
    # q 12.58 M, kv_a 1.18 M, kv_b 4.19 M, o 8.39 M
    assert C.mla_params(REAL) == (2048 * 32 * 192 + 2048 * 576
                                  + 512 * 32 * 256 + 32 * 128 * 2048) \
        == 26_345_472


def test_a_token_uses_its_k_experts_and_the_shared_one():
    # dense: 320 + 3 x 8 x 16; expert: 320 + router 8x4 + 2 x (3 x 8 x 3)
    # + shared 3 x 8 x 6
    assert C.layer_active_params(TINY) == {"dense": 704, "expert": 640}
    assert C.active_matmul_params(TINY) == 704 + 2 * 640 + 8 * 32 == 2240
    per = C.layer_active_params(REAL)
    assert per["dense"] == 26_345_472 + 3 * 2048 * 6144 == 64_094_208
    assert C.expert_params(REAL) == 3 * 2048 * 768 == 4_718_592
    assert per["expert"] == (26_345_472 + 2048 * 128 + 6 * 4_718_592
                             + 3 * 2048 * 1536) == 64_356_352
    # 6 of 128 experts: 64.4 M of an expert layer's 640.0 M are active
    assert C.active_matmul_params(REAL) == (
        64_094_208 + 7 * 64_356_352 + 2048 * 128256) == 777_256_960


def test_attention_counts_the_published_widths():
    # 2 heads x (6 + 4) x t^2 in 3 layers
    assert C.attention_forward_flops(TINY, [4, 6]) == 3 * 20 * (16 + 36)
    # keys 192 wide, values 128: 32 x 320 x 1024^2 a layer
    assert C.attention_forward_flops(REAL, [1024]) == 8 * 32 * 320 * 1024 ** 2


def test_adapters():
    # wq 8x12 -> 2 (16 + 24) = 80; wkv_b 6x16 -> 2 (12 + 32) = 88; 3 layers
    assert C.lora_forward_flops_per_token(TINY, 2, ["wq", "wkv_b"]) == 504
    # rank 8: wq 2 (2048 + 6144) 8, wkv_b 2 (512 + 8192) 8, 8 layers
    assert C.lora_forward_flops_per_token(REAL, 8, ["wq", "wkv_b"]) == \
        8 * 16 * (8192 + 8704)


def test_learn_call():
    extra = 3120 + 504 * 10
    assert C.grpo_learn_flops(TINY, [4, 6], 2, ["wq", "wkv_b"]) == \
        2 * (2 * 2240 * 10 + extra) + 4 * 2240 * 10 + 3 * extra == 220000
    # the cell's learn batch, 8 rows x 1024: 8 N_active a token is 50.9
    # TFLOP, five forwards of attention 3.4 TFLOP, of the adapters 0.09
    real = C.grpo_learn_flops(REAL, [1024] * 8, 8, ["wq", "wkv_b"])
    attention = 8 * 8 * 32 * 320 * 1024 ** 2
    adapters = 8 * 16 * (8192 + 8704) * 8192
    assert real == 8 * 777_256_960 * 8192 + 5 * (attention + adapters)
    assert 54.4e12 < real < 54.6e12


def test_grouped_matmuls_of_a_learn_call():
    # 2 layers x 10 rows x 2 experts x 2 x 72
    assert C.grouped_matmul_forward_flops(TINY, 10) == 5760
    # two no-grad forwards, the update's, remat's, and the backward w.r.t.
    # the rows
    assert C.learn_grouped_matmul_flops(TINY, 10) == 5 * 5760
    assert C.learn_grouped_matmul_flops(TINY, 10, remat=False) == 4 * 5760
    # 7 layers x 8192 rows x 6 x 2 x 4.72 M = 3.25 TFLOP a forward
    assert C.grouped_matmul_forward_flops(REAL, 8192) == \
        7 * 8192 * 6 * 2 * 4_718_592


def test_decode_step_bytes():
    assert C.latent_bytes_per_token(TINY) == 3 * 8 * 2
    assert C.latent_bytes_per_token(REAL) == 8 * 576 * 2
    # outside the experts: 3 x 320 + 384 + 2 x 144 + 256 weights in bf16,
    # 2 routers 8x4 in f32; 5 experts of 72 hit; 100 live tokens
    assert C.decode_step_bytes(TINY, 100, 5) == \
        (960 + 384 + 288 + 256) * 2 + 2 * 32 * 4 + 5 * 72 * 2 + 100 * 48
    # published widths: 1.16 GB outside the experts (8 x 26.3 M of
    # attention, 37.7 M dense SwiGLU, 7 x 9.44 M shared, 262.7 M head, in
    # bf16; 7 routers in f32); 41 experts a layer (8 rows x 6 choices,
    # uniform) are 2.71 GB; 8 x 640 tokens of latent cache 47 MB
    fixed = C.decode_step_bytes(REAL, 0, 0)
    assert fixed == (8 * 26_345_472 + 37_748_736 + 7 * 9_437_184
                     + 262_668_288) * 2 + 7 * 2048 * 128 * 4 == 1_161_822_208
    assert C.decode_step_bytes(REAL, 0, 7 * 41) - fixed == 287 * 9_437_184
    assert C.decode_step_bytes(REAL, 5120, 0) - fixed == 5120 * 9216
