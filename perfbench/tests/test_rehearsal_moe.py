"""CPU rehearsal of ``grpo_loop_mla_moe`` at a tiny, test-only configuration
of the real one's kind (latent attention, 1 dense + 2 expert layers of 8
sigmoid-routed experts, 2 a token, a shared expert): control flow, counts,
the shape of the last line and the reference comparison with its prefix-hit
row and its routing margins. No rate, utilisation or idle share is printed
or asserted: a CPU run has none to give."""

import json

import pytest

from perfbench import harness
from perfbench.reference import deepseek_v3_f32
from perfbench.tests import test_rehearsal as base

REAL = "grpo_kanana_reason"
NEW_READERS = {"learn_mfu_moe", "moe_experts_roofline", "moe_route_share"}


def tiny_cell(tmp_path) -> harness.Cell:
    return harness.Cell(
        name=REAL, chips=1,
        config=json.loads((base.HERE / "configs" / "tiny-mla-moe.json").read_text()),
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
    assert cells[REAL]["config"] == "kanana2-30b-a3b"
    assert cells[REAL]["traffic"] == "reason_256x768_g8"
    assert cells[REAL]["chips"] == 1
    listed = {m["name"] for m in harness.metrics_of(base.BENCH["per_layer"], REAL)}
    assert NEW_READERS <= listed and "compiles_in_window" in listed
    config = {c["name"]: c for c in base.BENCH["configs"]}["kanana2-30b-a3b"]
    assert config["reduced"] == ["num_hidden_layers", "max_position_embeddings"]


def test_the_runner_refuses_what_the_program_does_not_compute():
    from perfbench.runners import grpo_loop_mla_moe as runner

    config = json.loads(
        (base.HERE / "configs" / "tiny-mla-moe.json").read_text())
    assert runner.gpt_config(config).layer_runs() == [
        ("attn", 0, 1), ("attn", 1, 2)]
    for key, value in (("q_lora_rank", 16), ("n_group", 2),
                       ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            runner.gpt_config({**config, key: value})


def test_untraced_run_reports_the_cells_end_to_end_metrics(tmp_path, capsys):
    cell, result, notes = rehearse(tmp_path, False, capsys)
    # judged on the learn side alone: the rollout's time follows the experts
    # its rows hit, which spreads ~1 % by seed (PERF.md section 6, PR 31)
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
    assert warm["learn_lp_mean_abs_diff"] < deepseek_v3_f32.LP_MEAN_TOL
    assert warm["rollout_lp_mean_abs_diff"] < deepseek_v3_f32.LP_MEAN_TOL
    # 2 expert layers x the checked positions, each with a margin
    assert warm["routing_choices_checked"] > 0
    assert 0 <= warm["routing_choices_fragile"] <= warm["routing_choices_checked"]
    metrics = result["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    assert set(metrics) <= listed
    # what a CPU trace can give: counters, records and host phases
    assert {"compiles_in_window", "learn_mfu_moe", "learn_host_ms"} \
        <= set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # 8 rows x 2 choices touch between 2 and 8 of 8 experts a layer a step
    steps = [n for n in notes if n.get("perfbench") == "steps"][0]["records"]
    chunks = -(-cell.traffic["new_tokens"] // cell.config["serving"]["decode_chunk"])
    slots = chunks * cell.config["serving"]["decode_chunk"] * 2 * 8
    assert all(slots / 4 <= r["experts_hit"] <= slots for r in steps)
    # scope shares need a TPU's planes: left out here, not zero
    assert not {"moe_experts_roofline", "moe_route_share"} & set(metrics)
