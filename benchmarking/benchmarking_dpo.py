"""DPO benchmarking (parity: benchmarking/benchmarking_dpo.py)."""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import numpy as np

from agilerl_tpu.algorithms.dpo import DPO
from agilerl_tpu.hpo import Mutations, TournamentSelection
from agilerl_tpu.llm import model as M
from agilerl_tpu.training.train_llm import finetune_llm_preference
from agilerl_tpu.utils.llm_utils import CharTokenizer, PreferenceGym


def make_dataset(n, seed):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        a = int(rng.integers(0, 8))
        rows.append({"prompt": f"{a}+1=", "chosen": str(a + 1), "rejected": str(a)})
    return rows


def main():
    tok = CharTokenizer()
    cfg = M.GPTConfig(vocab_size=tok.vocab_size, n_layer=4, n_head=4,
                      d_model=128, max_seq_len=64)
    env = PreferenceGym(make_dataset(256, 0), make_dataset(32, 1), tok,
                        data_batch_size=16)
    pop = [DPO(config=cfg, pad_token_id=tok.pad_token_id,
               eos_token_id=tok.eos_token_id, lr=1e-3, beta=0.2, index=i, seed=i)
           for i in range(2)]
    for agent in pop[1:]:
        agent.base_params = pop[0].base_params
    pop, fitnesses = finetune_llm_preference(
        pop, env, max_steps=50, evaluation_interval=10,
        tournament=TournamentSelection(2, True, 2, 1),
        mutation=Mutations(no_mutation=0.5, architecture=0.0, parameters=0.0,
                           activation=0.0, rl_hp=0.5),
    )
    print(f"preference accuracy: {max(f[-1] for f in fitnesses):.3f}")


if __name__ == "__main__":
    main()
