"""``counts_smallthinker`` against figures worked out by hand: the parameter
counts ISSUE 38 states at published widths, the live pairs of a window, what
a tiny stack allocates, and the FLOPs of a learn call, of the grouped
matmuls and of a flash execution by its kind."""

import json
from pathlib import Path

import jax

from perfbench import counts_smallthinker as C

ROOT = Path(__file__).resolve().parents[2]
REAL = json.loads((ROOT / "perfbench" / "configs"
                   / "smallthinker-21b-a3b.json").read_text())
#: d 8, 2 query heads on 1 key head of 4, 4 experts 6 wide of which 2 a
#: token, a window of 3 in layers 1 and 2 of 3, vocabulary 32
TINY = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "moe_ffn_hidden_size": 6,
        "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 2,
        "vocab_size": 32, "num_hidden_layers": 3, "sliding_window_size": 3,
        "sliding_window_layout": [0, 1, 1, 1]}


def test_a_layer_and_the_embeddings_are_what_the_issue_states():
    assert C.attention_params(REAL) == 20_971_520
    assert C.expert_params(REAL) == 5_898_240
    assert C.router_params(REAL) == 163_840
    assert round(C.layer_params(REAL) / 1e6, 1) == 398.6
    assert round(C.embedding_and_head_params(REAL) / 1e6, 1) == 777.9
    n = int(REAL["num_hidden_layers"])
    bf16_gb = 2 * (n * C.layer_params(REAL)
                   + C.embedding_and_head_params(REAL)) / 1e9
    assert round(bf16_gb, 2) == {12: 11.12, 8: 7.93, 4: 4.74}[n]
    assert C.layer_active_params(REAL) == 20_971_520 + 163_840 + 6 * 5_898_240
    assert C.active_matmul_params(REAL) == \
        n * C.layer_active_params(REAL) + 2560 * 151936


def test_live_pairs_of_a_window():
    assert C.live_pairs(8192) == 33_558_528
    assert C.live_pairs(8192, 4096) == 25_167_872  # 75 % of the causal half
    assert C.live_pairs(16384, 4096) / C.live_pairs(16384) < 0.44
    assert C.live_pairs(4096, 4096) == C.live_pairs(4096)  # no bite yet
    assert C.live_pairs(5, 3) == 1 + 2 + 3 + 3 + 3
    assert C.live_pairs(8064 + 128, 4096) - C.live_pairs(8064, 4096) \
        == 128 * 4096  # every sampled position sees a whole window


def test_the_program_allocates_what_is_counted():
    from agilerl_tpu.llm import model as M
    from perfbench.runners import grpo_loop_swa_moe as runner

    tiny = json.loads((ROOT / "perfbench" / "tests" / "configs"
                       / "tiny-swa-moe.json").read_text())
    cfg = runner.gpt_config(tiny)
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    (run,) = shapes["runs"]
    norms = 2 * cfg.d_model
    per_layer = sum(x.size for x in jax.tree_util.tree_leaves(run)) \
        // cfg.n_layer
    assert per_layer == C.layer_params(tiny) + norms
    assert shapes["tok_emb"].size + shapes["lm_head"].size \
        == C.embedding_and_head_params(tiny)
    pool = jax.eval_shape(lambda: M.init_paged_cache(cfg, 4, 32))
    assert M.paged_block_bytes(pool) \
        == 32 * cfg.n_layer * 2 * C._dims(tiny)["dk"] * 2  # K and V, bf16
    # the stack is the layouts' first num_hidden_layers entries
    assert C._dims(tiny)["window_layers"] == cfg.n_window_layers == 6


def test_attention_by_live_pairs_and_adapters():
    # 2 heads x 4 dims x 4 FLOPs a pair; one global layer, two window layers
    assert C.attention_forward_flops(TINY, [5, 2]) == 32 * (
        1 * (15 + 3) + 2 * (12 + 3))
    n, w = int(REAL["num_hidden_layers"]), 4096
    windowed = sum(REAL["sliding_window_layout"][:n])
    assert windowed == n * 3 // 4
    assert C.attention_forward_flops(REAL, [8192]) == 4 * 28 * 128 * (
        (n - windowed) * 33_558_528 + windowed * C.live_pairs(8192, w))
    # wq 8x8 -> 2 (16 + 16); wv 8x4 -> 2 (16 + 8); 3 layers
    assert C.lora_forward_flops_per_token(TINY, 2, ["wq", "wv"]) \
        == 3 * (64 + 48)


def test_learn_flops_count_one_nograd_pass():
    lengths, tokens = [5, 2], 7
    n = C.active_matmul_params(TINY)
    assert n == 3 * (192 + 32 + 2 * 144) + 8 * 32
    extra = C.attention_forward_flops(TINY, lengths) \
        + C.lora_forward_flops_per_token(TINY, 2, ["wq"]) * tokens
    assert C.grpo_learn_flops(TINY, lengths, 2, ["wq"]) == \
        1 * (2 * n * tokens + extra) + 4 * n * tokens + 3 * extra
    # at the cell's sizes a learn call is ~108 TFLOP, a sixth of it attention
    call = C.grpo_learn_flops(REAL, [8192, 8192], 8, ["wq", "wv"])
    attention = 4 * C.attention_forward_flops(REAL, [8192, 8192])
    if int(REAL["num_hidden_layers"]) == 8:
        assert round(call / 1e12, 1) == 107.8
        assert 0.2 < attention / call < 0.25


def test_grouped_matmuls_are_four_forwards_worth():
    # 3 layers x 10 rows x 2 experts x 2 x 144
    assert C.grouped_matmul_forward_flops(TINY, 10) == 3 * 10 * 2 * 288
    assert C.learn_grouped_matmul_flops(TINY, 10) == 4 * 17280
    assert C.learn_grouped_matmul_flops(TINY, 10, remat=False) == 3 * 17280


def test_a_flash_execution_is_credited_with_its_own_pairs():
    half, band = C.live_pairs(8192), C.live_pairs(8192, 4096)
    shape = (56, 8192, 128)
    assert C.flash_execution_flops(REAL, "flash_fwd", shape) \
        == 2.0 * 56 * half * 2 * 128
    assert C.flash_execution_flops(REAL, "flash_fwd_win", shape) \
        == 2.0 * 56 * band * 2 * 128
    assert C.flash_execution_flops(REAL, "flash_dq_win", shape) \
        == 2.0 * 56 * band * 3 * 128
    assert C.flash_execution_flops(REAL, "flash_dkv", shape) \
        == 2.0 * 56 * half * 4 * 128
    # crediting a windowed execution with the causal half over-credits it
    assert half / band > 1.33
