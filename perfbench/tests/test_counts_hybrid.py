"""``counts_hybrid`` on a model small enough to count by hand: hidden 8, 2
query heads on 1 KV head of 4, SwiGLU 16, vocab 32, four layers of which
layers 1 and 3 are attention (period 2, offset 1); Mamba: d_inner 16, state
4, conv 4, dt rank 2."""

import json
from pathlib import Path

from perfbench import counts_hybrid as C

TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 16, "vocab_size": 32, "num_hidden_layers": 4,
        "attn_layer_period": 2, "attn_layer_offset": 1, "mamba_expand": 2,
        "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_dt_rank": 2}


def test_layers_by_kind():
    assert C.layer_counts(TINY) == {"attn": 2, "mamba": 2}


def test_matmul_weights_by_layer_kind():
    # attention: wq 8x8 + wk, wv 2 x 8x4 + wo 8x8 = 192; SwiGLU 3 x 8x16 = 384
    # mamba: in_proj 8x32 + x_proj 16x(2+4+4) + dt_proj 2x16 + out_proj 16x8
    #        = 256 + 160 + 32 + 128 = 576
    assert C.layer_matmul_params(TINY) == {"attn": 576, "mamba": 960}
    # two of each and the tied head 8 x 32
    assert C.matmul_params(TINY) == 2 * 576 + 2 * 960 + 256 == 3328


def test_attention_counts_the_attention_layers_alone():
    # 2 * 2 * (2 heads * 4) * t^2 / 2 = 16 t^2: 256 and 576, in 2 layers
    assert C.attention_forward_flops(TINY, [4, 6]) == 2 * (256 + 576)


def test_selective_scan_work_and_bytes():
    # a position and layer: 7 x 16 x 4 state operations + 3 x 16 = 496
    assert C.selective_scan_flops(TINY, 10) == 2 * 10 * 496
    # x and y in bf16 and dt in f32 over 16 channels, B and C in f32 over 4
    assert C.selective_scan_bytes(TINY, 10) == 2 * 10 * (16 * 8 + 32)


def test_adapters_count_where_their_projection_exists():
    # wq 8x8 -> 2 (8x2 + 2x8) = 64 in the attention layers only; in_proj
    # 8x32 -> 2 (16 + 64) = 160 in the Mamba layers only; w_up 8x16 -> 96
    # in all four
    assert C.lora_forward_flops_per_token(
        TINY, 2, ["wq", "in_proj", "w_up"]) == 2 * (64 + 96) + 2 * (160 + 96)


def test_learn_call():
    extra = 1664 + 9920 + 832 * 10  # attention, scan, adapters over 10 tokens
    assert C.grpo_learn_flops(TINY, [4, 6], 2, ["wq", "in_proj", "w_up"]) == \
        2 * (2 * 3328 * 10 + extra) + 4 * 3328 * 10 + 3 * extra == 365760


def test_decode_step_bytes():
    # a slot's state: 2 layers x (16 x 4 f32 + 3 x 16 bf16) = 704 bytes
    assert C.state_bytes_per_slot(TINY) == 704
    # weights 3328 x 2; KV of 2 layers x (K and V) x 4 x 100 tokens x 2;
    # 3 slots' state read and written
    assert C.decode_step_bytes(TINY, 100, 3) == 6656 + 3200 + 2 * 3 * 704


def test_the_published_configuration():
    model = json.loads((Path(__file__).parents[1] / "configs"
                        / "jamba2-3b.json").read_text())
    assert C.layer_counts(model) == {"attn": 2, "mamba": 26}
    per = C.layer_matmul_params(model)
    assert per["attn"] == 13_762_560 + 62_914_560
    assert per["mamba"] == 41_123_840 + 62_914_560
    assert C.matmul_params(model) == 3_026_124_800
    assert round(C.state_bytes_per_slot(model) / 1e6, 2) == 9.32
