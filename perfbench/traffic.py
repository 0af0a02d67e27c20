"""The one traffic generator. A mix is a data file of parameters under
``perfbench/traffic/``; everything drawn is drawn from ``--seed``.

Parameters a mix may give (a runner reads those its loop needs):

- ``prompts_per_step``, ``group_size``: a step's rows are their product;
- ``prompt_tokens``: ``[min, max]`` tokens of a prompt, padded to ``max``;
- ``new_tokens``: tokens generated (or, in a learn-only mix, the completion
  positions of a seeded batch);
- ``rows``: rows of a seeded learn batch (learn-only mixes);
- ``generations_per_step``: generations dispatched back to back before the
  host fetches a fitness (population mixes).

Copied from ``chip_smoke.py`` (``IdTokenizer``, ``seeded_reward``,
``make_rows``, ``seeded_batch``) so that the yardstick imports no file a
later PR may edit; the originals are listed in ``PERF.md``.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

ALPHABET = "0123456789+-*=() abcdefghijklmnopqrstuvwxyz"
GOLDEN = 0.6180339887498949


class IdTokenizer:
    """Char-level ids for the prompts (0 = pad, 1 = eos); ``decode`` keeps
    every id as decimal text. A random 152064-way head emits ids no char
    table knows; dropping them would decode every completion to "" and make
    every reward, and so every advantage, equal."""

    pad_token_id = 0
    eos_token_id = 1

    def __init__(self):
        self._c2i = {c: i + 2 for i, c in enumerate(ALPHABET)}
        self.vocab_size = len(ALPHABET) + 2

    def encode(self, text: str) -> List[int]:
        return [self._c2i[c] for c in text if c in self._c2i]

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def seeded_reward(seed: int):
    """A reward in [0, 1) hashed from the completion's ids: it varies inside
    a group whenever the sampled completions differ."""

    def reward_fn(completion: str, answer, prompt: str) -> float:
        total = sum(int(w) for w in completion.split())
        return ((total + int(answer)) * 2654435761 + seed) % 1009 / 1009.0

    return reward_fn


def prompt_lengths(seed: int, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` lengths, each uniform over ``lo..hi``, in mirrored pairs: a
    golden-ratio sequence from a seeded start gives rows 0, 2, 4, ... and
    row ``2k + 1`` is ``lo + hi`` less row ``2k``. Any even number of
    consecutive rows from row 0 then holds the same number of tokens
    whatever the seed: the work is fixed, only its order is drawn (plain
    draws put the seed's luck, +-0.7 % over six steps, into tokens/s)."""
    start = np.random.default_rng(seed).random()
    frac = (start + GOLDEN * np.arange((n + 1) // 2)) % 1.0
    first = lo + np.floor(frac * (hi - lo + 1)).astype(int)
    return np.stack([first, lo + hi - first], axis=1).ravel()[:n]


def dataset_rows(seed: int, n: int, traffic: Dict[str, Any]) -> List[Dict]:
    """``n`` question/answer rows whose questions tokenize to the mix's
    prompt lengths (one id a character)."""
    lo, hi = traffic["prompt_tokens"]
    rng = np.random.default_rng(seed)
    letters = np.array(list(ALPHABET))
    return [{"question": "".join(rng.choice(letters, size=int(length))),
             "answer": int(rng.integers(0, 1000))}
            for length in prompt_lengths(seed, n, lo, hi)]


def learn_batch(seed: int, step: int, vocab_size: int,
                traffic: Dict[str, Any]):
    """A learn batch without a rollout, as a learner fed from elsewhere gets
    it: ``(ids, action_masks, rewards)`` in ``assemble_learn_batch``'s
    layout, rewards varying inside each group."""
    rng = np.random.default_rng([seed, step])
    rows, group = int(traffic["rows"]), int(traffic["group_size"])
    prompt = int(traffic["prompt_tokens"][1])
    length = prompt + int(traffic["new_tokens"])
    ids = rng.integers(2, vocab_size, size=(rows, length)).astype(np.int32)
    action_masks = np.zeros((rows, length - 1), np.float32)
    action_masks[:, prompt - 1:] = 1.0
    rewards = rng.random((rows // group, group)).astype(np.float32)
    return ids, action_masks, rewards
