"""chip_smoke.py off the chip: its phase functions at tiny sizes on the
virtual CPU mesh, its refusal to run without a TPU, and the one rule that
places JAX's persistent compilation cache.

The four phase tests are the first rehearsal before a chip call. Each costs
the CPU compiler 20-30 s whatever the sizes, so they sit in the full tier
(``slow``); what the fast tier keeps costs seconds."""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.parallel.compile_cache import enable_jax_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

TINY = dict(d_model=64, n_head=4, n_kv_head=2, d_ff=128, vocab_size=512,
            max_seq_len=128)


def tiny_config(n_layer=2):
    """qwen2-7b's switches (qkv bias, untied head, bf16, remat, flash) at
    toy widths."""
    return preset("qwen2-7b", n_layer=n_layer, **TINY)


@pytest.mark.slow
def test_rl_phase_tiny():
    rec = chip_smoke.rl_phase(0, pop_size=4, num_envs=4, rollout_len=8,
                              generations=2)
    assert rec["generations"] == 2
    assert np.isfinite(rec["fitness_mean_last"])


def test_pick_depth_takes_the_largest_that_fits(monkeypatch):
    """The choice itself, with the compile stubbed: a learn step of
    ``1000 * n_layer`` bytes. ``test_llm_phase_tiny`` compiles the real one."""

    class Analysis:
        output_size_in_bytes = temp_size_in_bytes = alias_size_in_bytes = 0

        def __init__(self, agent):
            self.argument_size_in_bytes = 1000 * agent.model_config.n_layer

        def memory_analysis(self):
            return self

    monkeypatch.setattr(chip_smoke, "learn_step_program",
                        lambda agent, rows, seq_len: Analysis(agent))
    kw = dict(group_size=4, rows=4, seq_len=48, new_tokens=16)
    cfg, tried = chip_smoke.pick_depth(
        0, tiny_config(), (1, 2, 3), limit_bytes=1 << 40, **kw)
    assert cfg.n_layer == 3 and [t["n_layer"] for t in tried] == [3]
    pool3 = tried[0]["pool_bytes"]
    assert tried[0]["learn_step_bytes"] == 3000 and pool3 > 0
    # the pool counts: depth 3 alone fits this limit, depth 3 + its pool not
    cfg, tried = chip_smoke.pick_depth(
        0, tiny_config(), (1, 2, 3), limit_bytes=3000 + pool3 - 1, **kw)
    assert cfg.n_layer == 2
    assert [(t["n_layer"], t["fits"]) for t in tried] == [(3, False), (2, True)]
    with pytest.raises(RuntimeError, match="no depth"):
        chip_smoke.pick_depth(0, tiny_config(), (1, 2), limit_bytes=1, **kw)


@pytest.mark.slow
def test_llm_phase_tiny():
    rec = chip_smoke.llm_phase(0, tiny_config(), prompts_per_step=1,
                               group_size=4, prompt_len=32, new_tokens=16,
                               steps=2)
    assert rec["seq_len"] == 48 and len(rec["steps"]) == 2
    for step in rec["steps"]:
        assert np.isfinite(step["loss"]) and step["lora_max_abs_change"] > 0
    assert rec["kernel_lp_mean_abs_diff"] <= chip_smoke.LP_MEAN_TOL
    # the learn step takes its kernels from the same gate as GRPO.learn:
    # off the chip the gate is shut and the program holds no Mosaic call
    assert rec["learn_step_tpu_custom_calls"] == 0


def test_seeded_reward_varies_with_the_completion_ids():
    tok = chip_smoke.IdTokenizer()
    reward = chip_smoke.seeded_reward(3)
    a = reward(tok.decode([151000, 7, 42]), 5, "")
    b = reward(tok.decode([151000, 7, 43]), 5, "")
    assert 0.0 <= a < 1.0 and 0.0 <= b < 1.0 and a != b
    assert a == reward(tok.decode([151000, 7, 42]), 5, "")


@pytest.mark.slow
def test_mesh_grpo_phase_on_four_virtual_devices():
    devices = jax.devices()[:4]
    rec = chip_smoke.mesh_grpo_phase(0, tiny_config(), devices, prompts=1,
                                     group_size=4, prompt_len=32,
                                     new_tokens=32)
    assert rec["mesh"] == {"dp": 1, "fsdp": 4, "tp": 1}
    assert all(abs(s - 0.25) < 0.05
               for s in rec["base_share_per_device"].values())
    assert rec["lora_update_cosine"] > 0.9


@pytest.mark.slow
def test_pod_phase_on_four_virtual_devices():
    rec = chip_smoke.pod_phase(0, jax.devices()[:4], pop_size=8, num_envs=4,
                               rollout_len=8)
    assert rec["members_per_device"] == 2 and rec["members_matching"] >= 0.9


def test_script_fails_without_a_tpu():
    """A second process: it refuses to run, prints no result, and names the
    same cache directory as this process does."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
    assert str(REPO / ".jax_cache") in proc.stderr


@pytest.fixture
def jax_cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield was
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_helper_sets_no_directory_when_the_variable_is_set(
        monkeypatch, tmp_path, jax_cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_jax_cache() is None
    assert jax.config.jax_compilation_cache_dir == jax_cache_dir_restored


def test_cache_helper_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, jax_cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert enable_jax_cache() == want == enable_jax_cache()
    assert jax.config.jax_compilation_cache_dir == want
