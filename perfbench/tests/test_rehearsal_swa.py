"""CPU rehearsal of ``grpo_loop_swa_moe`` at a tiny, test-only configuration
of the real one's kind (two periods of [global attention without positions,
3 x window 8 with rotary], 8 ReGLU experts of which 2 a token by a router
that reads the attention block's input, an untied head; prompts 3-4 windows
long): control flow, counts, the shape of the last line and the reference
comparison with its prefix-hit row and its routing margins. No rate,
utilisation or idle share is printed or asserted: a CPU run has none to
give."""

import json

import pytest

from perfbench import harness
from perfbench.reference import smallthinker_f32
from perfbench.tests import test_rehearsal as base

REAL = "grpo_smallthinker_longdoc"
NEW_READERS = {"learn_mfu_smallthinker", "flash_fwd_roofline_swa",
               "flash_bwd_roofline_swa", "smallthinker_experts_roofline",
               "attn_learn_share"}


def tiny_config():
    return json.loads((base.HERE / "configs" / "tiny-swa-moe.json").read_text())


def tiny_cell(tmp_path) -> harness.Cell:
    return harness.Cell(
        name=REAL, chips=1, config=tiny_config(),
        traffic=json.loads(
            (base.HERE / "traffic" / "tiny_longdoc.json").read_text()),
        end_to_end=harness.metrics_of(base.BENCH["end_to_end"], REAL),
        per_layer=harness.metrics_of(base.BENCH["per_layer"], REAL),
        root=tmp_path)


def rehearse(tmp_path, trace, capsys):
    cell = tiny_cell(tmp_path)
    line = harness.run_cell(
        cell, seed=2147483747, seconds=0.2, trace=trace, t_process=0.0,
        gate=base.cpu_gate, peaks=base.FAKE_PEAKS,
        trace_layout=base.CPU_LAYOUT)
    notes = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    return cell, json.loads(line), notes


def test_the_real_cell_is_in_the_benchmark():
    cells = {w["name"]: w for w in base.BENCH["workloads"]}
    assert cells[REAL]["config"] == "smallthinker-21b-a3b"
    assert cells[REAL]["traffic"] == "longdoc_8064x128_g2"
    assert cells[REAL]["chips"] == 1
    listed = {m["name"] for m in harness.metrics_of(base.BENCH["per_layer"], REAL)}
    assert NEW_READERS <= listed
    assert {"compiles_in_window", "moe_route_share", "peak_hbm",
            "fused_loss_fwd_roofline", "learn_nograd_share"} <= listed
    # _kernels._flash credits every execution with the causal half
    assert not {"flash_fwd_roofline", "flash_bwd_roofline"} & listed
    config = {c["name"]: c for c in base.BENCH["configs"]}["smallthinker-21b-a3b"]
    assert config["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    mix = harness.load_cell(base.ROOT, REAL).traffic
    assert {k: mix[k] for k in ("prompts_per_step", "group_size",
                                "prompt_tokens", "new_tokens")} == {
        "prompts_per_step": 1, "group_size": 2,
        "prompt_tokens": [7936, 8064], "new_tokens": 128}


def test_the_configuration_file_holds_the_published_keys():
    """Every key of the catalog row's ``config`` as published, but the two
    in ``reduced``; the two layout lists whole (the stack is their first
    ``num_hidden_layers`` entries)."""
    real = harness.load_cell(base.ROOT, REAL).config
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    assert {k: real[k] for k in published} == published
    assert real["rope_layout"] == real["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    assert real["published"] == {"num_hidden_layers": 52,
                                 "max_position_embeddings": 16384}
    assert set(real["reduced"]) == {"num_hidden_layers",
                                    "max_position_embeddings"}
    assert real["num_hidden_layers"] in (12, 8, 4)
    assert real["max_position_embeddings"] == 8192
    for item in ("stored_dtype", "weights", "qk_scale", "rotary", "router",
                 "attention_bias", "secondary_sparsity", "lora", "sampling"):
        assert real["assumed"][item], item
    assert real["deployment"] and real["init"]["qk_std"] > 0.02


def test_the_runner_reads_the_layouts_and_refuses_what_it_does_not_compute():
    from perfbench.runners import grpo_loop_swa_moe as runner

    config = tiny_config()
    cfg = runner.gpt_config(config)
    assert cfg.layer_runs() == [("attn", 0, 8)] and cfg.run_period(0, 8) == 4
    assert cfg.window_layout == cfg.rope_layout == (0, 1, 1, 1) * 2
    assert cfg.sliding_window == 8 and cfg.expert_act == "relu"
    assert cfg.router_input == "attn" and not cfg.tie_embeddings
    for key, value in (("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True),
                       ("moe_primary_router_apply_softmax", False),
                       ("norm_topk_prob", False),
                       ("sliding_window_layout", [0, 1]),
                       ("model_name", "other")):
        with pytest.raises(ValueError, match=key):
            runner.gpt_config({**config, key: value})
    # the base is stored a position in the period, layer i from the key
    # init_params would hand it, wq and wk at the stated scale
    import jax
    import numpy as np

    from agilerl_tpu.llm import model as M

    base_ = runner.make_base(cfg, 5, 0.5)
    want = M.init_params(jax.random.PRNGKey(5), cfg)
    (run,), (ref_run,) = base_["runs"], want["runs"]
    assert isinstance(run, list) and len(run) == 4
    np.testing.assert_allclose(
        np.asarray(run[2]["wq"], np.float32),
        np.asarray((ref_run[2]["wq"] * 25.0).astype(runner.STORED), np.float32))
    np.testing.assert_allclose(run[1]["router"], ref_run[1]["router"],
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(base_["lm_head"], np.float32),
        np.asarray(want["lm_head"].astype(runner.STORED), np.float32))


def test_untraced_run_reports_the_cells_end_to_end_metrics(tmp_path, capsys):
    cell, result, notes = rehearse(tmp_path, False, capsys)
    # judged on the learn side alone, as the two other expert cells
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"learn_tok_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] > 0
    window = [n for n in notes if n.get("perfbench") == "window done"][0]
    assert window["problems"] == [] and result["correct"] is True
    assert window["steps"] >= 1


def test_traced_run_counts_and_reference(tmp_path, capsys):
    cell, result, notes = rehearse(tmp_path, True, capsys)
    window = [n for n in notes if n.get("perfbench") == "window done"][0]
    assert window["problems"] == []
    warm = [n for n in notes if n.get("perfbench") == "warm-up step"][0]
    rows = cell.traffic["group_size"] * cell.traffic["prompts_per_step"]
    assert warm["attempted"] == rows == 2 and warm["tier"] == "continuous"
    assert warm["prefix_cache_hits"] == 1
    assert warm["checked_prefix_hit_rows"] == 1
    # on the CPU the program computes in bf16 all the same
    assert warm["learn_lp_mean_abs_diff"] < smallthinker_f32.LP_MEAN_TOL
    assert warm["rollout_lp_mean_abs_diff"] < smallthinker_f32.LP_MEAN_TOL
    # 8 layers x the checked positions, each with a margin
    assert warm["routing_choices_checked"] == 8 * warm["positions_checked"]
    assert warm["positions_checked"] == 2 * cell.traffic["new_tokens"]
    # the rollout's last chunk: 2 rows 80 slots deep (a prompt bucket of 64
    # + 16), a window of 8, blocks of 32: the two prompt blocks of each row
    # are dead in the 6 window layers; the rows share the first (a prefix
    # hit) and row 1's second is its private copy: 3 blocks of 32 tokens x
    # (K + V) x 2 heads x 16 x 2 bytes
    from agilerl_tpu import observability

    gauges = observability.get_registry().dump()["gauges"]
    assert gauges["serving/window_dead_bytes"] == 3 * 6 * (32 * 2 * 2 * 16 * 2)
    metrics = result["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    assert set(metrics) <= listed
    # what a CPU trace can give: counters, records and host phases
    assert {"compiles_in_window", "learn_mfu_smallthinker", "learn_host_ms"} \
        <= set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # scope and kernel shares need a TPU's planes: left out here, not zero
    assert not (NEW_READERS - {"learn_mfu_smallthinker"}) & set(metrics)
    assert "moe_route_share" not in metrics
