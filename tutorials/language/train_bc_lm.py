"""Tutorial — behavioural cloning on language (BC_LM baseline for ILQL)
(parity: tutorials/language/train_bc_lm.py)."""

# allow running directly as `python tutorials/<dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

import numpy as np

from agilerl_tpu.algorithms.ilql import BC_LM
from agilerl_tpu.data.rl_data import Language_Observation, RL_Dataset
from agilerl_tpu.llm.model import GPTConfig
from agilerl_tpu.utils.llm_utils import CharTokenizer

if __name__ == "__main__":
    tok = CharTokenizer()
    cfg = GPTConfig(vocab_size=tok.vocab_size, n_layer=2, n_head=4, d_model=64,
                    max_seq_len=32)
    rng = np.random.default_rng(0)
    obs = [
        Language_Observation(sequence=[(f"{a}+1=", None), (str(a + 1), 1.0)])
        for a in rng.integers(0, 5, 256)
    ]
    ds = RL_Dataset(obs, tok, max_len=10)
    agent = BC_LM(config=cfg, lr=1e-3, seed=0)
    for step in range(200):
        loss = agent.learn(ds.sample_batch(16, rng))
        if step % 50 == 0:
            print(f"[{step}] bc loss {loss:.4f}")
    # llm.generate takes LEFT-padded prompts and returns completions only
    ids = tok.encode("3+1=")
    prompt = np.asarray([[0] * 4 + ids], np.int32)
    mask = (prompt != 0).astype(np.float32)
    comp, comp_mask = agent.generate(prompt, mask, max_new_tokens=2,
                                     temperature=0.0)
    real = np.asarray(comp[0])[np.asarray(comp_mask[0], bool)]
    print("completion for 3+1= :", tok.decode([int(t) for t in real]))
