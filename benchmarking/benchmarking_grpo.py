"""GRPO benchmarking harness (parity: benchmarking/benchmarking_grpo.py —
the reference's headline LLM workload: Qwen2.5-0.5B-Instruct, countdown-style
arithmetic reasoning, pop 4, ctx 1024).

With ``--model`` (or INIT_HP.MODEL) it loads that model's HF weights through
llm/hf.load_hf_model and fails if it cannot: a run never reports one model's
numbers under another's name. With no model asked it runs a random-init toy
8-layer model. Reports tokens/sec + MFU — the BASELINE.md metric (>=35% MFU
target on v5p for the 7B class).
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.modules.configs import load_yaml_config
from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym
from agilerl_tpu.utils.profiling import StepTimer, estimate_mfu


def make_dataset(n, seed):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        nums = rng.integers(1, 50, 3)
        target = int(nums[0] + nums[1] - nums[2])
        rows.append({
            "question": f"use {nums[0]} {nums[1]} {nums[2]} to make {target} = ",
            "answer": f"{nums[0]}+{nums[1]}-{nums[2]}",
        })
    return rows


def reward_fn(completion, answer, prompt):
    return 1.0 if str(answer) in completion else 0.0


def main(config_path: str, model_name: str = None, steps: int = 10):
    cfg = load_yaml_config(config_path) if config_path else {}
    hp = cfg.get("INIT_HP", {})
    model_name = model_name or hp.get("MODEL")

    if model_name:
        from agilerl_tpu.llm.hf import load_hf_model, load_hf_tokenizer

        model_cfg, base_params = load_hf_model(model_name)
        tok = load_hf_tokenizer(model_name)
    else:
        base_params = None
        tok = CharTokenizer()
        model_cfg = M.GPTConfig(
            vocab_size=tok.vocab_size, n_layer=8, n_head=8, d_model=512,
            max_seq_len=512,
        )

    env = ReasoningGym(make_dataset(256, 0), make_dataset(32, 1), tok,
                       reward_fn=reward_fn, data_batch_size=hp.get("BATCH_SIZE", 8))
    agent = GRPO(
        config=model_cfg, base_params=base_params,
        pad_token_id=tok.pad_token_id, eos_token_id=tok.eos_token_id,
        group_size=hp.get("GROUP_SIZE", 8), batch_size=hp.get("BATCH_SIZE", 8),
        lr=hp.get("LR", 5e-6), beta=hp.get("BETA", 0.04),
        max_output_tokens=hp.get("MAX_OUTPUT_TOKENS", 32),
        lora_rank=hp.get("LORA_RANK", 8), seed=0,
        continuous_decode=hp.get("CONTINUOUS_DECODE", False),
        speculative_decode=hp.get("SPECULATIVE_DECODE"),
        capture_logprobs=hp.get("CAPTURE_LOGPROBS", False),
    )

    timer = StepTimer()
    prompts = env.reset()
    tokens_per_step = None
    for step in range(steps):
        comp, cmask = agent.get_action(prompts)
        ids, masks = env.assemble_learn_batch(comp, cmask)
        prompts, rewards = env.step(comp, cmask)
        loss, _ = agent.learn((ids, masks, rewards))
        tokens_per_step = int(np.prod(ids.shape))
        dt = timer.tick()
        if dt and step > 1:
            mfu = estimate_mfu(model_cfg, tokens_per_step, dt)
            print(f"[{step}] loss {loss:.4f} reward {np.mean(rewards):.3f} "
                  f"tok/s {tokens_per_step/dt:.0f} MFU {mfu:.1%}")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="configs/training/grpo.yaml")
    p.add_argument("--model", default=None)
    p.add_argument("--steps", type=int, default=10)
    a = p.parse_args()
    main(a.config, a.model, a.steps)
