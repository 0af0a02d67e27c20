"""CPU rehearsal of ``grpo_loop_hybrid`` at a tiny, test-only hybrid
configuration (the layer pattern of the real one: attention every fourth
layer, two periods): control flow, counts, the shape of the last line and
the reference comparison with its prefix-hit row. No rate, utilisation or
idle share is printed or asserted: a CPU run has none to give."""

import json

import pytest

from perfbench import harness
from perfbench.reference import jamba_f32
from perfbench.tests import test_rehearsal as base

REAL = "grpo_jamba_reason"


def tiny_cell(tmp_path) -> harness.Cell:
    return harness.Cell(
        name=REAL, chips=1,
        config=json.loads((base.HERE / "configs" / "tiny-jamba.json").read_text()),
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
    assert cells[REAL]["config"] == "jamba2-3b"
    assert cells[REAL]["traffic"] == "reason_256x768_g8"
    assert cells[REAL]["chips"] == 1


def test_untraced_run_reports_the_cells_end_to_end_metrics(tmp_path, capsys):
    cell, result, notes = rehearse(tmp_path, False, capsys)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"grpo_tok_s", "rollout_tok_s", "learn_tok_s", "setup_s"}
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
    assert warm["learn_lp_mean_abs_diff"] < jamba_f32.LP_MEAN_TOL
    assert warm["rollout_lp_mean_abs_diff"] < jamba_f32.LP_MEAN_TOL
    metrics = result["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    assert set(metrics) <= listed
    # what a CPU trace can give: counters, records and host phases
    assert {"compiles_in_window", "tier_continuous_share", "prefix_hit_share",
            "learn_mfu_hybrid", "state_restore_ms", "sched_host_ms_per_chunk",
            "learn_host_ms"} <= set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["prefix_hit_share"]["value"] == 100.0 * (rows - 1) / rows
    assert metrics["state_restore_ms"]["value"] > 0
    # scope shares need a TPU's planes: left out here, not zero
    assert "ssm_scan_share" not in metrics and "ssm_step_share" not in metrics
    restores = [n for n in notes if n.get("perfbench") == "steps"]
    assert restores and all(r["prefix_cache_hits"] == rows - 1
                            for r in restores[0]["records"])


def test_a_configuration_the_program_does_not_compute_is_refused():
    from perfbench.runners import grpo_loop_hybrid

    config = json.loads((base.HERE / "configs" / "tiny-jamba.json").read_text())
    with pytest.raises(ValueError, match="num_experts"):
        grpo_loop_hybrid.gpt_config(dict(config, num_experts=16))
