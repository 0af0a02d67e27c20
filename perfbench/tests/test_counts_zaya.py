"""``counts_zaya`` held to hand arithmetic on a model small enough to count
in one's head, to the published widths of ``zaya1-8b`` (the issue's 207.6 M
a layer and 537.1 M in the tied embedding), and to the shapes the program
itself allocates."""

import json
from pathlib import Path

import jax

from perfbench import counts_zaya as C

# hidden 8; 4 query heads on 2 key heads of 2 (latent 8 / 4); kernels 2, 2;
# router 3 wide; 4 experts 5 wide, 1 a token; vocab 32; 3 layers
TINY = {"hidden_size": 8, "head_dim": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "cca_time0": 2, "cca_time1": 2,
        "router_hidden_size": 3, "moe_intermediate_size": 5, "num_experts": 4,
        "num_experts_per_tok": 1, "vocab_size": 32, "num_hidden_layers": 3}
ROOT = Path(__file__).resolve().parents[2]
REAL = json.loads((ROOT / "perfbench" / "configs" / "zaya1-8b.json").read_text())


def test_attention_projections_and_convolutions():
    # wq 8x8 + wk 8x4 + wv1 8x2 + wv2 8x2 + wo 8x8
    assert C.cca_params(TINY) == 64 + 32 + 16 + 16 + 64 == 192
    # depthwise 2 taps x 12 channels; head-wise 2 taps x 6 heads x 2 x 2
    assert C.conv_params(TINY) == {"conv0": 24, "conv1": 48}
    assert C.cca_params(REAL) == 2048 * (1024 + 256 + 128 + 128) \
        + 1024 * 2048 == 5_242_880
    assert C.conv_params(REAL) == {"conv0": 2 * 1280,
                                   "conv1": 2 * 10 * 128 * 128}


def test_router_and_experts():
    assert C.router_params(TINY) == 8 * 3 + 2 * 9 + 3 * 4 == 54
    assert C.expert_params(TINY) == 3 * 8 * 5 == 120
    assert C.router_params(REAL) == 2048 * 256 + 2 * 256 * 256 + 256 * 16 \
        == 659_456
    assert C.expert_params(REAL) == 3 * 2048 * 2048 == 12_582_912


def test_a_token_uses_one_expert():
    assert C.layer_active_params(TINY) == 192 + 72 + 54 + 120 == 438
    assert C.active_matmul_params(TINY) == 3 * 438 + 8 * 32 == 1570
    assert C.layer_active_params(REAL) == (
        5_242_880 + 2560 + 327_680 + 659_456 + 12_582_912) == 18_815_488
    n = int(REAL["num_hidden_layers"])
    assert C.active_matmul_params(REAL) == n * 18_815_488 + 2048 * 262272


def test_a_layer_whole_is_what_the_issue_states():
    # 16 x 12.58 M experts, 5.24 M projections, 0.33 M convolutions, 0.66 M
    # router, 0.02 M norms and merges: 207.6 M
    assert round(C.layer_params(REAL) / 1e6, 1) == 207.6
    assert round(2048 * 262272 / 1e6, 1) == 537.1


def test_the_program_allocates_what_is_counted():
    from agilerl_tpu.llm import model as M
    from perfbench.runners import grpo_loop_cca_moe as runner

    tiny = json.loads((ROOT / "perfbench" / "tests" / "configs"
                       / "tiny-cca-moe.json").read_text())
    cfg = runner.gpt_config(tiny)
    (run,) = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))["runs"]
    per_layer = sum(x.size for x in jax.tree_util.tree_leaves(run)) \
        // cfg.n_layer
    assert per_layer == C.layer_params(tiny)
    pool = jax.eval_shape(lambda: M.init_paged_cache(cfg, 4, 32, slots=2))
    assert M.paged_block_bytes(pool) == 32 * C.kv_bytes_per_token(tiny)
    slot_state = sum(x.size // x.shape[1] * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool.state))
    assert slot_state == C.rolling_state_bytes_per_slot(tiny)


def test_attention_and_adapters():
    # 4 heads x (2 + 2) x t^2 in 3 layers
    assert C.attention_forward_flops(TINY, [4, 6]) == 3 * 16 * (16 + 36)
    n = int(REAL["num_hidden_layers"])
    assert C.attention_forward_flops(REAL, [1024]) == n * 8 * 256 * 1024 ** 2
    # wq 8x8 -> 2 (16 + 16); wv1, wv2 8x2 -> 2 (16 + 4) each; 3 layers
    assert C.lora_forward_flops_per_token(TINY, 2, ["wq", "wv1", "wv2"]) \
        == 3 * (64 + 40 + 40)


def test_learn_flops():
    lengths, tokens = [4, 6], 10
    n = C.active_matmul_params(TINY)
    extra = C.attention_forward_flops(TINY, lengths) \
        + C.lora_forward_flops_per_token(TINY, 2, ["wq"]) * tokens
    assert C.grpo_learn_flops(TINY, lengths, 2, ["wq"]) == \
        2 * (2 * n * tokens + extra) + 4 * n * tokens + 3 * extra
    # the head is the larger part of a forward at the cut depth, the
    # smaller at the published one (the cell's why says so)
    head = 2048 * 262272
    cut = C.active_matmul_params(REAL)
    whole = C.active_matmul_params({**REAL, "num_hidden_layers": 40})
    assert head / cut > 0.5 > head / whole > 0.4


def test_grouped_matmuls():
    # 3 layers x 10 rows x 1 expert x 2 x 120
    assert C.grouped_matmul_forward_flops(TINY, 10) == 3 * 10 * 240
    assert C.learn_grouped_matmul_flops(TINY, 10) == 5 * 7200
    assert C.learn_grouped_matmul_flops(TINY, 10, remat=False) == 4 * 7200


def test_decode_step_bytes():
    assert C.kv_bytes_per_token(TINY) == 3 * 2 * 4 * 2
    # (1 + 1) rows of 12 channels + a value half of 2, bf16, 3 layers
    assert C.rolling_state_bytes_per_slot(TINY) == 3 * 26 * 2
    fixed = 3 * ((192 + 48) * 2 + (24 + 54) * 4) + 8 * 32 * 2
    assert C.decode_step_bytes(TINY, live_tokens=100, experts_hit=5,
                               slots=8) == \
        fixed + 5 * 120 * 2 + 100 * 48 + 2 * 8 * 156
    # at published widths 1 KB a token a layer in the pool, 5.4 KB a slot a
    # layer beside it
    n = int(REAL["num_hidden_layers"])
    assert C.kv_bytes_per_token(REAL) == n * 1024
    assert C.rolling_state_bytes_per_slot(REAL) == n * (2560 + 128) * 2
