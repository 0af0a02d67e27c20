"""Tutorial — GRPO reasoning finetune WITH evolutionary HPO over a population
(parity: tutorials/llm_finetuning/grpo_reasoning_hpo.py — only RL
hyperparameters mutate for LLMs; base weights are shared across members)."""

# allow running directly as `python tutorials/<dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

import jax.numpy as jnp
import numpy as np

from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.llm import model as M
from agilerl_tpu.training.train_llm import finetune_llm_reasoning
from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym
from tutorials.llm_finetuning.grpo_reasoning import make_rows, reward_fn

if __name__ == "__main__":
    tok = CharTokenizer()
    cfg = M.GPTConfig(vocab_size=tok.vocab_size, n_layer=4, n_head=4,
                      d_model=128, max_seq_len=64, dtype=jnp.float32)
    env = ReasoningGym(make_rows(256, 0), make_rows(64, 1), tok,
                       reward_fn=reward_fn, data_batch_size=8)
    pop = [GRPO(config=cfg, pad_token_id=tok.pad_token_id,
                eos_token_id=tok.eos_token_id, group_size=4, batch_size=16,
                max_output_tokens=6, index=i, seed=i) for i in range(4)]
    for member in pop[1:]:
        member.base_params = pop[0].base_params  # share the frozen base
    pop, fitnesses = finetune_llm_reasoning(
        pop, env, max_steps=60, evaluation_interval=10,
        tournament=TournamentSelection(2, True, 4, 1),
        mutation=Mutations(no_mutation=0.5, architecture=0.0, parameters=0.0,
                           activation=0.0, rl_hp=0.5),
    )
    print("best accuracy:", max(f[-1] for f in fitnesses))
    print("surviving HPs:", [(a.lr, a.beta, a.group_size) for a in pop])
