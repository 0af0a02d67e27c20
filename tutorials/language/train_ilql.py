"""Tutorial — offline RL on language with ILQL
(parity: tutorials/language/train_ilql.py — the wordle dataset becomes a
synthetic rewarded-dialogue set; Language_Observation carries the same
(utterance, reward) structure)."""

# allow running directly as `python tutorials/<dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

import numpy as np

from agilerl_tpu.algorithms.ilql import ILQL, ILQL_Policy, TopAdvantageNGrams
from agilerl_tpu.data.rl_data import Language_Observation, RL_Dataset
from agilerl_tpu.llm.model import GPTConfig
from agilerl_tpu.utils.llm_utils import CharTokenizer

if __name__ == "__main__":
    tok = CharTokenizer()
    cfg = GPTConfig(vocab_size=tok.vocab_size, n_layer=2, n_head=4, d_model=64,
                    max_seq_len=32)
    rng = np.random.default_rng(0)
    obs = []
    for _ in range(256):
        a = int(rng.integers(0, 5))
        good = rng.random() < 0.5
        answer = str(a + 1) if good else str(a)
        obs.append(Language_Observation(
            sequence=[(f"{a}+1=", None), (answer, 1.0 if good else -1.0)],
        ))
    ds = RL_Dataset(obs, tok, max_len=10)

    agent = ILQL(config=cfg, lr=1e-3, seed=0)
    for step in range(200):
        loss = agent.learn(ds.sample_batch(16, rng))
        if step % 50 == 0:
            print(f"[{step}] ilql loss {loss:.4f}")

    # what did the Q function decide is good text?
    probe = TopAdvantageNGrams(tokenizer=tok, n_gram=2, print_k=5)
    probe.evaluate(agent, ds.sample_batch(64, rng))
    print("top-advantage n-grams:", probe.top())

    # act with the learned policy
    policy = ILQL_Policy(agent, kind="beam", max_new_tokens=2, beam_width=4)
    prompt = np.asarray([tok.encode("3+1=") + [0] * 4], np.int32)
    mask = (prompt != 0).astype(np.float32)
    out_tokens, out_mask = policy.act(prompt, mask)
    real = out_tokens[0][np.asarray(out_mask[0], bool)]
    print("generation:", tok.decode([int(t) for t in real]))
