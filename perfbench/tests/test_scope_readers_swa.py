"""The four trace readers that came with ``grpo_smallthinker_longdoc`` on a
trace small enough to work out by hand (the writer of
``test_scope_readers.py`` / ``test_scope_readers_device.py``, which are not
edited): flash attention's kernels credited by kind, the experts' grouped
matmuls, attention's share of a learn call, and ``None`` on a trace without
the names (a program that lacks what PR 38 added)."""

import pytest

from perfbench import counts_smallthinker as C
from perfbench.layer_metrics import (attn_learn_share, flash_bwd_roofline_swa,
                                     flash_fwd_roofline_swa,
                                     smallthinker_experts_roofline)
from perfbench.tests.test_scope_readers import US, metadata
from perfbench.tests.test_scope_readers_device import PEAK, ctx_for, write

#: a window of 40 in layers 1-3 of 4; heads of 128
CONFIG = {"hidden_size": 256, "head_dim": 128, "num_attention_heads": 2,
          "num_key_value_heads": 1, "moe_ffn_hidden_size": 64,
          "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 2,
          "vocab_size": 100, "num_hidden_layers": 4,
          "sliding_window_size": 40, "sliding_window_layout": [0, 1, 1, 1],
          "gpt_config": {"remat": True}}


def learn_trace(tmp_path, named=True):
    """``jit_logprobs`` (31): a global forward 10 us and a windowed one 8 us
    at BH 4, Tp 100. ``jit_update`` (32): the windowed forward 8 us, dq 20
    and 16 us, dkv 30 and 24 us; attention blocks under ``attn/full`` 40 us
    (it holds the global kernels) and ``attn/win`` 60 us; a grouped matmul
    found by its name, 50 us, and the ReGLU's product under ``moe/experts``,
    10 us. A decode chunk's windowed kernel is nobody's."""
    k = (lambda s: s) if named else (lambda s: "checkpoint")
    scope = (lambda s: s) if named else (lambda s: "blk")
    out3 = "(bf16[4,100,128]{2,1,0}, f32[4,1,100]{2,1,0}) custom-call(%q)"
    metas = [
        metadata(1, "jit_logprobs(31)"), metadata(2, "jit_update(32)"),
        metadata(3, "jit__decode_chunk_impl(33)"),
        metadata(4, f"%{k('flash_fwd')}.1 = " + out3,
                 f"jit(logprobs)/{scope('attn/full')}/pallas_call", 31),
        metadata(5, f"%{k('flash_fwd_win')}.1 = " + out3,
                 f"jit(logprobs)/{scope('attn/win')}/pallas_call", 31),
        metadata(6, f"%{k('flash_fwd_win')}.2 = " + out3,
                 f"jit(update)/checkpoint/{scope('attn/win')}/pallas_call", 32),
        metadata(7, f"%{k('flash_dq')}.2 = bf16[4,100,128]{{2,1,0}} "
                 "custom-call(%q)",
                 f"jit(update)/transpose/{scope('attn/full')}/pallas_call", 32),
        metadata(8, f"%{k('flash_dq_win')}.2 = bf16[4,100,128]{{2,1,0}} "
                 "custom-call(%q)",
                 f"jit(update)/transpose/{scope('attn/win')}/pallas_call", 32),
        metadata(9, f"%{k('flash_dkv')}.2 = (bf16[4,100,128]{{2,1,0}}, "
                 "bf16[4,100,128]{2,1,0}) custom-call(%q)",
                 f"jit(update)/transpose/{scope('attn/full')}/pallas_call", 32),
        metadata(10, f"%{k('flash_dkv_win')}.2 = (bf16[4,100,128]{{2,1,0}}, "
                 "bf16[4,100,128]{2,1,0}) custom-call(%q)",
                 f"jit(update)/transpose/{scope('attn/win')}/pallas_call", 32),
        metadata(11, "%fusion.7 = bf16[200,256] fusion(%a)",
                 f"jit(update)/{scope('attn/full')}/dot_general", 32),
        metadata(12, "%fusion.8 = bf16[200,256] fusion(%a)",
                 f"jit(update)/{scope('attn/win')}/dot_general", 32),
        # XLA:TPU's expansion of a grouped matmul: the tf_op reads ragged-dot
        metadata(13, "%fusion.20 = bf16[400,64] fusion(%a)",
                 "ragged-dot" if named else "jit(update)/dot_general", 32),
        metadata(14, "%fusion.9 = bf16[400,64] fusion(%a)",
                 f"jit(update)/{scope('moe/experts')}/mul", 32),
        metadata(15, "%flash_fwd_win.1 = " + out3,
                 "jit(_decode_chunk_impl)/pallas_call", 33),
    ]
    modules = [(1, 0, 100 * US), (2, 100 * US, 400 * US),
               (3, 600 * US, 100 * US)]
    ops = [(4, 10 * US, 10 * US), (5, 30 * US, 8 * US),
           (6, 110 * US, 8 * US), (11, 120 * US, 10 * US),
           (12, 130 * US, 12 * US), (13, 150 * US, 50 * US),
           (14, 200 * US, 10 * US), (7, 220 * US, 20 * US),
           (9, 240 * US, 30 * US), (8, 280 * US, 16 * US),
           (10, 300 * US, 24 * US), (15, 610 * US, 50 * US)]
    return write(tmp_path, metas, modules, ops)


def ctx(tmp_path, named=True, records=()):
    c = ctx_for(tmp_path, learn_trace(tmp_path, named), CONFIG)
    c.records = list(records)
    return c


def test_flash_executions_are_credited_by_kind(tmp_path):
    c = ctx(tmp_path)
    half, band = C.live_pairs(100), C.live_pairs(100, 40)
    assert (half, band) == (5050, 3220)
    per_pair = 2 * 4 * 128  # FLOPs a pair and a matmul over head_dim: BH 4
    # one global and two windowed forwards: 2 matmuls each
    assert flash_fwd_roofline_swa.read(c) == pytest.approx(
        100 * per_pair * 2 * (half + 2 * band) / 26e-6 / PEAK)
    # dq 3 matmuls, dkv 4, one of each kind
    assert flash_bwd_roofline_swa.read(c) == pytest.approx(
        100 * per_pair * 7 * (half + band) / 90e-6 / PEAK)


def test_experts_roofline_and_attentions_share(tmp_path):
    c = ctx(tmp_path, records=[{"learn_tokens": 200}])
    # 4 forwards' worth of 4 layers x 200 rows x 2 experts x 2 x 3 x 256 x 64
    flops = 4 * 4 * 200 * 2 * 2 * 3 * 256 * 64
    assert smallthinker_experts_roofline.read(c) == pytest.approx(
        100 * flops / 60e-6 / PEAK)
    # under attn/full 10 + 10 + 20 + 30, under attn/win 8 + 8 + 12 + 16 + 24
    assert attn_learn_share.read(c) == pytest.approx(100 * 138 / 500)


def test_a_program_without_the_names_leaves_the_metrics_out(tmp_path):
    c = ctx(tmp_path, named=False, records=[{"learn_tokens": 200}])
    for reader in (flash_fwd_roofline_swa, flash_bwd_roofline_swa,
                   smallthinker_experts_roofline, attn_learn_share):
        assert reader.read(c) is None
