"""CPU rehearsal of each runner at a tiny, test-only configuration: control
flow, counts and the shape of the last line. No rate, utilisation or idle
share is printed or asserted: a CPU run has none to give. The real command
still refuses to run without a TPU (last test)."""

import json
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from perfbench import harness

HERE = Path(__file__).parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FAKE_PEAKS = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0, "hbm_bytes": 1.0}
# the CPU client runs operations on host threads of the one host plane
CPU_LAYOUT = {"device_plane": re.compile(r"^/host:CPU$"),
              "ops_line": re.compile(r"^tf_XLA"),
              "modules_line": re.compile(r"^$")}

CELLS = {  # tiny configuration, tiny mix, and the real cell whose metrics it reports
    "grpo_loop": ("tiny-gqa", "tiny_loop", "grpo_k4_reason", 1),
    "grpo_learn_mesh": ("tiny-gqa-fsdp4", "tiny_learn", "grpo_learn_fsdp4", 4),
    "evo_generation": ("tiny-evoppo", "tiny_generations",
                       "evoppo_p64_generation", 1),
}


def tiny_cell(runner, tmp_path) -> harness.Cell:
    config, mix, real, chips = CELLS[runner]
    return harness.Cell(
        name=real, chips=chips,
        config=json.loads((HERE / "configs" / f"{config}.json").read_text()),
        traffic=json.loads((HERE / "traffic" / f"{mix}.json").read_text()),
        end_to_end=harness.metrics_of(BENCH["end_to_end"], real),
        per_layer=harness.metrics_of(BENCH["per_layer"], real),
        root=tmp_path)


def cpu_gate(chips):
    devices = jax.devices()
    if len(devices) < chips:
        pytest.skip(f"needs {chips} virtual CPU devices "
                    "(--xla_force_host_platform_device_count)")
    return devices[:chips]


def rehearse(runner, tmp_path, trace, capsys):
    cell = tiny_cell(runner, tmp_path)
    line = harness.run_cell(
        cell, seed=3, seconds=0.2, trace=trace, t_process=0.0, gate=cpu_gate,
        peaks=FAKE_PEAKS, trace_layout=CPU_LAYOUT)
    notes = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    return cell, json.loads(line), notes


@pytest.mark.parametrize("runner", sorted(CELLS))
def test_untraced_run_reports_the_cells_end_to_end_metrics(
        runner, tmp_path, capsys):
    cell, result, notes = rehearse(runner, tmp_path, False, capsys)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(set(v) == {"value", "unit"} for v in result["metrics"].values())
    assert result["failed"] == 0 and result["attempted"] > 0
    window = [n for n in notes if n.get("perfbench") == "window done"][0]
    assert window["problems"] == [] and result["correct"] is True
    assert window["steps"] >= 1
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == cell.chips


@pytest.mark.parametrize("runner", sorted(CELLS))
def test_traced_run_reports_per_layer_metrics_and_a_breakdown(
        runner, tmp_path, capsys):
    cell, result, notes = rehearse(runner, tmp_path, True, capsys)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    listed = {m["name"] for m in cell.per_layer}
    assert set(result["metrics"]) <= listed
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert result["device"]["busy_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    window = [n for n in notes if n.get("perfbench") == "window done"][0]
    assert window["problems"] == []


def test_grpo_loop_counts(tmp_path, capsys):
    cell, result, notes = rehearse("grpo_loop", tmp_path, True, capsys)
    warm = [n for n in notes if n.get("perfbench") == "warm-up step"][0]
    rows = cell.traffic["group_size"] * cell.traffic["prompts_per_step"]
    assert warm["attempted"] == rows and warm["tier"] == "continuous"
    assert warm["new_tokens"] == rows * cell.traffic["new_tokens"]
    assert warm["prefix_cache_hits"] == rows - 1
    # the rollout's own log-probabilities and the learn side's, both
    # against the plain reference (on the CPU the program computes in bf16
    # all the same)
    assert warm["learn_lp_mean_abs_diff"] < 2 ** -4
    assert warm["rollout_lp_mean_abs_diff"] < 2 ** -4
    metrics = result["metrics"]
    assert metrics["tier_continuous_share"]["value"] == 100.0
    assert metrics["prefix_hit_share"]["value"] == 100.0 * (rows - 1) / rows


def test_the_command_refuses_to_run_without_a_tpu():
    run = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         "evoppo_p64_generation", "--seed", "0", "--seconds", "1"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert run.returncode != 0
    assert "no TPU" in run.stderr
    assert not any(line.startswith('{"correct"')
                   for line in run.stdout.splitlines())
