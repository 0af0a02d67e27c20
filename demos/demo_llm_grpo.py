"""GRPO LLM finetuning demo (parity: the reference's
benchmarking/benchmarking_grpo.py workload — Qwen2.5-0.5B-Instruct on
Countdown-style tasks — runs through llm/hf.load_hf_model when weights are
available locally; this demo uses the in-tree char-level model so it runs
anywhere, swap `load_hf_model("Qwen/Qwen2.5-0.5B-Instruct")` in for the real
workload)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np

from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.llm import model as M
from agilerl_tpu.training.train_llm import finetune_llm_reasoning
from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym


def make_dataset(n, seed):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        a, b = rng.integers(0, 10, 2)
        rows.append({"question": f"{a}+{b}=", "answer": str(a + b)})
    return rows


def reward_fn(completion, answer, prompt):
    return 1.0 if completion.strip().startswith(str(answer)) else 0.0


if __name__ == "__main__":
    tok = CharTokenizer()
    cfg = M.GPTConfig(vocab_size=tok.vocab_size, n_layer=4, n_head=4,
                      d_model=128, max_seq_len=64)
    env = ReasoningGym(make_dataset(512, 0), make_dataset(64, 1), tok,
                       reward_fn=reward_fn, data_batch_size=8)
    pop = [
        GRPO(config=cfg, pad_token_id=tok.pad_token_id, eos_token_id=tok.eos_token_id,
             group_size=8, batch_size=16, max_output_tokens=4, lr=1e-4, index=i, seed=i)
        for i in range(2)
    ]
    # share one frozen base across the population (adapters differ)
    for agent in pop[1:]:
        agent.base_params = pop[0].base_params
    tournament = TournamentSelection(2, True, 2, eval_loop=1)
    mutations = Mutations(no_mutation=0.5, architecture=0.0, parameters=0.0,
                          activation=0.0, rl_hp=0.5)
    pop, fitnesses = finetune_llm_reasoning(
        pop, env, max_steps=100, evaluation_interval=10,
        tournament=tournament, mutation=mutations, verbose=True,
    )
