"""``perfbench/traffic.py``: the same seed gives the same inputs, and the
work of a window does not depend on the seed."""

import numpy as np

from perfbench import traffic

MIX = {"prompts_per_step": 1, "group_size": 8, "prompt_tokens": [200, 256],
       "new_tokens": 768}


def test_prompt_lengths_are_uniform_and_an_even_run_of_rows_holds_fixed_work():
    every = []
    for seed in range(40):
        lengths = traffic.prompt_lengths(seed, 64, 200, 256)
        assert lengths.min() >= 200 and lengths.max() <= 256
        # any even number of rows from row 0: the same tokens for every seed
        for n in (2, 6, 14):
            assert lengths[:n].sum() == n * (200 + 256) // 2
        every.append(lengths)
    every = np.concatenate(every)
    # uniform: every length of the range turns up, none twice as often as another
    counts = np.bincount(every - 200, minlength=57)
    assert counts.min() > 0 and counts.max() < 2.5 * counts.mean()
    assert not np.array_equal(traffic.prompt_lengths(1, 8, 200, 256),
                              traffic.prompt_lengths(2, 8, 200, 256))


def test_rows_and_batches_repeat_for_a_seed():
    tok = traffic.IdTokenizer()
    a, b = traffic.dataset_rows(5, 8, MIX), traffic.dataset_rows(5, 8, MIX)
    assert a == b and a != traffic.dataset_rows(6, 8, MIX)
    assert [len(tok.encode(r["question"])) for r in a] == \
        traffic.prompt_lengths(5, 8, 200, 256).tolist()
    mix = {"rows": 16, "group_size": 8, "prompt_tokens": [256, 256],
           "new_tokens": 768}
    ids, masks, rewards = traffic.learn_batch(3, 0, 1000, mix)
    again = traffic.learn_batch(3, 0, 1000, mix)
    assert all(np.array_equal(x, y) for x, y in zip((ids, masks, rewards), again))
    assert not np.array_equal(ids, traffic.learn_batch(3, 1, 1000, mix)[0])
    assert ids.shape == (16, 1024) and masks.shape == (16, 1023)
    assert masks[:, :255].sum() == 0 and masks[:, 255:].all()
    assert rewards.shape == (2, 8) and (rewards.std(axis=1) > 0).all()


def test_the_reward_varies_with_the_completion_and_decode_keeps_every_id():
    tok = traffic.IdTokenizer()
    assert tok.decode([7, 152063, 0]) == "7 152063 0"
    reward = traffic.seeded_reward(3)
    values = {reward(tok.decode([i, i + 1]), 5, "") for i in range(50)}
    assert len(values) > 25 and all(0 <= v < 1 for v in values)
