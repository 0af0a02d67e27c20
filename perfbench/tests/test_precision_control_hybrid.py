"""The two readings behind ``jamba_f32``'s ``LP_MEAN_TOL`` / ``LP_MAX_TOL``,
through the runner's own ``reference_check`` on fresh seeds:

- the program (``M.token_logprobs`` as ``GRPO._logprob_fn`` calls it, kernels
  on where the backend has them; the adapters are zero at the check, so none
  are passed) must come out correct;
- the reference itself computed one precision below what the configuration
  states must NOT: with the SSM state and its discretisation rounded to
  bfloat16 at every position (``bf16_state=True``), by the max limit on every
  seed and by the mean limit on nearly every one; with the stored matrices
  rounded to float8 (e4m3), by both on every seed.

At published widths this needs the chip (a 6 GB base, 28 layers in float32
at ``highest`` precision): run it there, one call for all seeds, and keep
the records it prints::

    chiprun -- python3 -m pytest perfbench/tests/test_precision_control_hybrid.py -q -s

Off a TPU the same code runs at ``configs/tiny-jamba.json``, where only the
plumbing can be held to anything: a tiny model's log-probabilities move by
less than the limits whatever is rounded."""

import json

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.llm import model as M
from agilerl_tpu.ops import pallas_enabled
from perfbench.reference import jamba_f32 as ref
from perfbench.runners import grpo_loop_hybrid as runner
from perfbench.tests import test_rehearsal as base

PUBLISHED = jax.default_backend() == "tpu"
CONFIG = json.loads(
    (base.ROOT / "perfbench" / "configs" / "jamba2-3b.json").read_text()
    if PUBLISHED else (base.HERE / "configs" / "tiny-jamba.json").read_text())
CFG = runner.gpt_config(CONFIG)
# the cell's learn batch: prompts of 200-256 tokens, left-padded to 256, and
# 768 new tokens
PROMPT, NEW = (256, 768) if PUBLISHED else (32, 64)
PAD = 0
SEEDS = [2147484501 + i for i in range(12 if PUBLISHED else 2)]
REF = dict(n_head=CFG.n_head, n_kv=CFG.kv_heads, eps=CFG.rms_eps)


@jax.jit
def program_logprobs(params, tokens, mask):
    on = pallas_enabled()
    return M.token_logprobs(CFG, params, tokens, attention_mask=mask,
                            use_pallas=on, flash=on)


def fp8_weights(params):
    lossy = lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)  # noqa: E731
    return dict(params, tok_emb=lossy(params["tok_emb"]), runs=[
        {k: (lossy(v) if k in runner.MATRICES else v) for k, v in run.items()}
        for run in params["runs"]])


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
        out[row, at] = ref.token_logprobs(params, tokens, at - first,
                                          **REF, **how)
    return out


def readings_of(seed):
    params = runner.make_base(CFG, seed)
    ids, action = batch(seed)
    check = lambda lp: runner.reference_check(  # noqa: E731
        CFG, params, ids, action, PAD, seed, lp, None, [False, True])
    sound = np.asarray(program_logprobs(
        params, jnp.asarray(ids), jnp.asarray(ids != PAD, jnp.int32)))
    out = {"program": check(sound),
           "bf16_state": check(lossy_reference(params, ids, bf16_state=True)),
           "fp8_weights": check(lossy_reference(fp8_weights(params), ids))}
    print(json.dumps({"seed": seed, "published_widths": PUBLISHED, **{
        what: {"mean": record["learn_lp_mean_abs_diff"],
               "max": record["learn_lp_max_abs_diff"]}
        for what, (_, record) in out.items()}}), flush=True)
    return out


def test_limits_pass_the_program_and_fail_one_precision_below():
    runs = [readings_of(seed) for seed in SEEDS]  # every record printed first
    mean = lambda r: r[1]["learn_lp_mean_abs_diff"]  # noqa: E731
    worst = lambda r: r[1]["learn_lp_max_abs_diff"]  # noqa: E731
    for seed, run in zip(SEEDS, runs):
        assert run["program"][0] == [], (seed, run["program"][0])
        for what in ("bf16_state", "fp8_weights"):
            assert mean(run[what]) > 0, (seed, what)  # the rounding is there
    if not PUBLISHED:
        return
    for seed, run in zip(SEEDS, runs):
        # the worst position tells a rounded state on every seed ...
        assert run["bf16_state"][0] and worst(run["bf16_state"]) > ref.LP_MAX_TOL, seed
        assert run["fp8_weights"][0], seed
        assert mean(run["fp8_weights"]) > ref.LP_MEAN_TOL, seed
        assert worst(run["fp8_weights"]) > ref.LP_MAX_TOL, seed
    # ... the mean on all but the odd one (jamba_f32's header: a rounded
    # state's error sits in few positions, and one seed in 14 read a mean
    # of 0.0368, which the program's own rounding reached once)
    told = sum(mean(run["bf16_state"]) > ref.LP_MEAN_TOL for run in runs)
    assert told >= len(runs) - 2, told
