"""CPU rehearsal of ``grpo_loop_cca_moe`` at a tiny, test-only configuration
of the real one's kind (CCA with its rolling state, 3 layers of 8 experts of
which one a token by a router MLP, scaled merges, a tied head): control
flow, counts, the shape of the last line and the reference comparison with
its prefix-hit row and its routing margins. No rate, utilisation or idle
share is printed or asserted: a CPU run has none to give."""

import json

import pytest

from perfbench import harness
from perfbench.reference import zaya_f32
from perfbench.tests import test_rehearsal as base

REAL = "grpo_zaya_reason"
NEW_READERS = {"learn_mfu_zaya", "zaya_experts_roofline", "cca_mix_share",
               "router_mlp_share"}


def tiny_config():
    return json.loads((base.HERE / "configs" / "tiny-cca-moe.json").read_text())


def tiny_cell(tmp_path) -> harness.Cell:
    return harness.Cell(
        name=REAL, chips=1, config=tiny_config(),
        traffic=json.loads((base.HERE / "traffic" / "tiny_loop.json").read_text()),
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
    assert cells[REAL]["config"] == "zaya1-8b"
    assert cells[REAL]["traffic"] == "reason_256x768_g8"
    assert cells[REAL]["chips"] == 1
    listed = {m["name"] for m in harness.metrics_of(base.BENCH["per_layer"], REAL)}
    assert NEW_READERS <= listed
    assert {"compiles_in_window", "moe_route_share", "peak_hbm"} <= listed
    config = {c["name"]: c for c in base.BENCH["configs"]}["zaya1-8b"]
    assert config["reduced"] == ["num_hidden_layers", "max_position_embeddings"]


def test_the_configuration_file_holds_the_published_keys():
    """Every number of the catalog row's ``config`` under its key, but the
    two in ``reduced``; nested groups whole."""
    real = harness.load_cell(base.ROOT, REAL).config
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "lm_head_bias": False, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "router_hidden_size": 256,
        "sliding_window": None, "tie_word_embeddings": True,
        "vocab_size": 262272}
    assert {k: real[k] for k in published} == published
    assert real["layer_types"] == ["hybrid"] * 40
    assert real["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert set(real["reduced"]) == {"num_hidden_layers",
                                    "max_position_embeddings"}
    assert 16 <= real["num_hidden_layers"] <= 24
    for item in ("stored_dtype", "weights", "router_bias", "tau", "merge",
                 "router_mlp", "rotary_pairing", "skip_route", "lora",
                 "sampling", "convolutions", "first_layer_gamma"):
        assert real["assumed"][item], item


def test_the_runner_refuses_what_the_program_does_not_compute():
    from perfbench.runners import grpo_loop_cca_moe as runner

    config = tiny_config()
    cfg = runner.gpt_config(config)
    assert cfg.layer_runs() == [("attn", 0, 3)] and cfg.head_dim == 16
    assert cfg.rope_theta == 5e6 and cfg.rotary_share == 0.5
    for key, value in (("sliding_window", 4096), ("attention_bias", True),
                       ("num_experts_per_tok", 2), ("hidden_act", "gelu"),
                       ("layer_types", ["hybrid", "hybrid_sliding", "hybrid"]),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            runner.gpt_config({**config, key: value})


def test_untraced_run_reports_the_cells_end_to_end_metrics(tmp_path, capsys):
    cell, result, notes = rehearse(tmp_path, False, capsys)
    # judged on the learn side alone: the rollout's time follows the experts
    # its rows hit (PERF.md section 6, PR 31 and PR 33)
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
    assert warm["attempted"] == rows and warm["tier"] == "continuous"
    assert warm["prefix_cache_hits"] == rows - 1
    assert warm["checked_prefix_hit_rows"] >= 1
    # on the CPU the program computes in bf16 all the same
    assert warm["learn_lp_mean_abs_diff"] < zaya_f32.LP_MEAN_TOL
    assert warm["rollout_lp_mean_abs_diff"] < zaya_f32.LP_MEAN_TOL
    # 3 layers x the checked positions, each with a margin
    assert warm["routing_choices_checked"] == 3 * warm["positions_checked"]
    assert 0 <= warm["routing_choices_fragile"] <= warm["routing_choices_checked"]
    metrics = result["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    assert set(metrics) <= listed
    # what a CPU trace can give: counters, records and host phases
    assert {"compiles_in_window", "learn_mfu_zaya", "learn_host_ms"} \
        <= set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # 8 rows x 1 choice touch between 1 and 8 of 8 experts a layer a step
    steps = [n for n in notes if n.get("perfbench") == "steps"][0]["records"]
    chunks = -(-cell.traffic["new_tokens"] // cell.config["serving"]["decode_chunk"])
    slots = chunks * cell.config["serving"]["decode_chunk"] * 3 * 8
    assert all(slots / 8 <= r["experts_hit"] <= slots for r in steps)
    # scope shares need a TPU's planes: left out here, not zero
    assert not {"zaya_experts_roofline", "cca_mix_share", "router_mlp_share",
                "moe_route_share"} & set(metrics)
