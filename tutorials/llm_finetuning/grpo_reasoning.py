"""Tutorial — GRPO reasoning finetune on arithmetic tasks
(parity: tutorials/llm_finetuning/grpo_reasoning.py — Countdown-Tasks +
Qwen2.5 become a char-tokenised arithmetic gym + in-tree GPT so the tutorial
runs anywhere; swap CFG/tokenizer for llm/hf.py-imported real weights)."""

# allow running directly as `python tutorials/<dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

import jax.numpy as jnp
import numpy as np

from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.training.train_llm import finetune_llm_reasoning
from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym


def make_rows(n, seed):
    rng = np.random.default_rng(seed)
    return [{"question": f"{a}+{b}=", "answer": str(a + b)}
            for a, b in rng.integers(0, 10, (n, 2))]


def reward_fn(completion, answer, prompt):
    return float(completion.strip().startswith(str(answer)))


if __name__ == "__main__":
    tok = CharTokenizer()
    cfg = M.GPTConfig(vocab_size=tok.vocab_size, n_layer=4, n_head=4,
                      d_model=128, max_seq_len=64, dtype=jnp.float32)
    env = ReasoningGym(make_rows(256, 0), make_rows(64, 1), tok,
                       reward_fn=reward_fn, data_batch_size=8)
    agent = GRPO(config=cfg, pad_token_id=tok.pad_token_id,
                 eos_token_id=tok.eos_token_id, group_size=4, batch_size=32,
                 max_output_tokens=6, lr=1e-4, seed=0)
    pop, fitnesses = finetune_llm_reasoning(
        [agent], env, max_steps=60, evaluation_interval=10,
    )
    print("final accuracy:", fitnesses[0][-1])
