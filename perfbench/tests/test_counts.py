"""``perfbench/counts.py`` against counts made by hand at a toy shape."""

from perfbench import counts

# hidden 8, 2 query heads / 1 KV head of 4, SwiGLU 16, vocab 32, 2 layers
TOY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 16, "vocab_size": 32, "num_hidden_layers": 2}


def test_matmul_params():
    # a block: wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + three 8x16 = 576
    assert counts.layer_matmul_params(TOY) == 64 + 32 + 32 + 64 + 384 == 576
    # two blocks and the 8x32 head; the embedding lookup is no matmul
    assert counts.matmul_params(TOY) == 2 * 576 + 256 == 1408


def test_attention_flops_use_the_rows_real_lengths():
    # per layer and row: QK^T and PV, 2 FLOPs a MAC, half the T x T square:
    # 2 * 2 * (2 heads * 4) * T*T / 2
    assert counts.attention_forward_flops(TOY, [4]) == 2 * 256
    assert counts.attention_forward_flops(TOY, [4, 2]) == 2 * (256 + 64)


def test_grpo_learn_flops_of_a_frozen_base_with_lora():
    # adapters of rank 2 on wq (8 -> 8) and wv (8 -> 4), a token, a layer:
    # 2*(8*2 + 2*8) + 2*(8*2 + 2*4) = 64 + 48 = 112; two layers = 224
    assert counts.lora_forward_flops_per_token(TOY, 2, ["wq", "wv"]) == 224
    # one row of 4 tokens. A no-grad pass: 2*1408*4 + 512 + 224*4 = 12672.
    # The update: forward + activation backward 4*1408*4 = 22528 (no weight
    # gradient of the frozen base), attention 3 * 512, adapters 3 * 896.
    assert counts.grpo_learn_flops(TOY, [4], 2, ["wq", "wv"]) \
        == 2 * 12672 + 22528 + 1536 + 2688 == 52096
    # what 6N a token would have claimed: 3 passes worth 2+2+6 = 10N
    assert counts.grpo_learn_flops(TOY, [4], 2, ["wq", "wv"]) < 10 * 1408 * 4 + 5 * 512 + 5 * 896


def test_decode_step_bytes():
    # weights once in bf16: (2 * 576 + 256) * 2 = 2816; live KV of 10 tokens:
    # 2 layers * (K and V) * 1 head * 4 * 10 * 2 bytes = 320
    assert counts.decode_step_bytes(TOY, live_kv_tokens=10) == 2816 + 320
    assert counts.stored_weight_bytes(TOY) == 1408 * 4
