"""The two readings behind ``zaya_f32``'s limits, through the runner's own
``reference_check`` on fresh seeds:

- the program (``M.token_logprobs`` as ``GRPO._logprob_fn`` calls it, kernels
  on where the backend has them; the adapters are zero at the check, so none
  are passed) must come out correct;
- the reference's own mathematics run in bfloat16 THROUGHOUT
  (``dtype=jnp.bfloat16``: weights, residual stream, convolutions, norms,
  softmax and the router MLP, which the program keeps in float32) must NOT:
  by the median's limit on every seed (the mean's is wide for the routing
  flips' sake and does not tell it); with the stored matrices rounded to
  float8 (e4m3), by the median's and the mean's.

At published widths this needs the chip (a ~9.4 GB base, its layers in
float32 at ``highest`` precision): run it there, one call for all seeds, and
keep the records it prints::

    chiprun -- python3 -m pytest perfbench/tests/test_precision_control_zaya.py -q -s

Off a TPU the same code runs at ``configs/tiny-cca-moe.json``, where only
the plumbing can be held to anything: a tiny model's log-probabilities move
by less than the limits whatever is rounded."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.llm import model as M
from agilerl_tpu.ops import pallas_enabled
from perfbench.reference import zaya_f32 as ref
from perfbench.runners import grpo_loop_cca_moe as runner
from perfbench.tests import test_rehearsal as base

PUBLISHED = jax.default_backend() == "tpu"
CONFIG = json.loads(
    (base.ROOT / "perfbench" / "configs" / "zaya1-8b.json").read_text()
    if PUBLISHED else (base.HERE / "configs" / "tiny-cca-moe.json").read_text())
CFG = runner.gpt_config(CONFIG)
# the cell's learn batch: prompts of 200-256 tokens, left-padded to 256, and
# 768 new tokens
PROMPT, NEW = (256, 768) if PUBLISHED else (32, 64)
PAD = 0
SEEDS = [2147489533 + i for i in range(
    int(os.environ.get("CONTROL_SEEDS", 6)) if PUBLISHED else 2)]


@jax.jit
def program_logprobs(params, tokens, mask):
    on = pallas_enabled()
    return M.token_logprobs(CFG, params, tokens, attention_mask=mask,
                            use_pallas=on, flash=on)


def batch(seed):
    rng = np.random.default_rng(seed)
    ids = np.full((2, PROMPT + NEW), PAD, np.int32)
    action = np.zeros((2, PROMPT + NEW - 1), np.int32)
    for row in range(2):
        first = int(rng.integers(0, PROMPT * 56 // 256 + 1))
        ids[row, first:] = rng.integers(3, CFG.vocab_size,
                                        size=PROMPT + NEW - first)
        action[row, PROMPT - 1:] = 1  # predictions of the new tokens
    return ids, action


def lossy_reference(params, ids, **how):
    """The reference's own answer, in ``token_logprobs``' layout."""
    out = np.zeros((ids.shape[0], ids.shape[1] - 1), np.float32)
    for row in range(ids.shape[0]):
        first = int(np.flatnonzero(ids[row] != PAD)[0])
        at = np.arange(PROMPT - 1, ids.shape[1] - 1)
        # filled up on the right to one shape, as reference_check does
        tokens = np.concatenate([ids[row, first:], np.full(first, 2, ids.dtype)])
        out[row, at], _ = ref.token_logprobs(
            params, tokens, at - first, **runner.reference_args(CFG), **how)
    return out


def readings_of(seed):
    params = runner.make_base(CFG, seed)
    ids, action = batch(seed)
    check = lambda lp: runner.reference_check(  # noqa: E731
        CFG, params, ids, action, PAD, seed, lp, None, [False, True])
    sound = np.asarray(program_logprobs(
        params, jnp.asarray(ids), jnp.asarray(ids != PAD, jnp.int32)))
    out = {"program": check(sound),
           "bf16_throughout": check(
               lossy_reference(params, ids, dtype=jnp.bfloat16)),
           # rounded a layer at a time inside the reference: a float8 copy
           # of the base does not fit beside it
           "fp8_weights": check(
               lossy_reference(params, ids, store=jnp.float8_e4m3fn))}
    fragile = out["program"][1]
    print(json.dumps({
        "seed": seed, "published_widths": PUBLISHED,
        "layers": CFG.n_layer,
        "routing_choices_checked": fragile["routing_choices_checked"],
        "routing_choices_fragile": fragile["routing_choices_fragile"],
        "positions_with_a_fragile_choice":
            fragile["positions_with_a_fragile_choice"],
        **{what: {"median": record["learn_lp_median_abs_diff"],
                  "mean": record["learn_lp_mean_abs_diff"],
                  "max": record["learn_lp_max_abs_diff"]}
           for what, (_, record) in out.items()}}), flush=True)
    return out


def test_limits_pass_the_program_and_fail_one_precision_below():
    runs = [readings_of(seed) for seed in SEEDS]  # every record printed first
    mean = lambda r: r[1]["learn_lp_mean_abs_diff"]  # noqa: E731
    for seed, run in zip(SEEDS, runs):
        assert run["program"][0] == [], (seed, run["program"][0])
        for what in ("bf16_throughout", "fp8_weights"):
            assert mean(run[what]) > 0, (seed, what)  # the rounding is there
    if not PUBLISHED:
        return
    median = lambda r: r[1]["learn_lp_median_abs_diff"]  # noqa: E731
    for seed, run in zip(SEEDS, runs):
        # a bfloat16 run of the same mathematics fails the median's limit
        # (not the mean's: zaya_f32's header), float8 weights both
        assert run["bf16_throughout"][0], seed
        assert median(run["bf16_throughout"]) > ref.LP_MEDIAN_TOL, seed
        assert median(run["fp8_weights"]) > ref.LP_MEDIAN_TOL, seed
        assert mean(run["fp8_weights"]) > ref.LP_MEAN_TOL, seed
