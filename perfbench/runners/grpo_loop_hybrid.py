"""Closed-loop GRPO on one chip for a hybrid stack (state-space layers beside
attention layers): the same loop and records as ``grpo_loop`` —
``ReasoningGym.reset`` -> ``GRPO.get_action`` (paged continuous tier) ->
``assemble_learn_batch`` + ``step`` -> ``GRPO.learn`` — with its own reading
of the configuration file (Jamba's published keys), a frozen base made run
by run in the type it is stored in, and the comparison with
``perfbench/reference/jamba_f32.py``, which must include a row that the
serving tier admitted by a prefix-cache hit: that row's first decode step
started from a recurrent-state snapshot, not from its own prefill."""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.llm import model as M
from agilerl_tpu.utils.llm_utils import ReasoningGym
from perfbench import harness, traffic
from perfbench.reference import jamba_f32 as ref
from perfbench.runners import _llm, grpo_loop

#: the frozen base's matrices are stored in this type; everything else
#: (norm scales, conv taps and biases, A_log, D, dt_bias) stays float32
STORED = jnp.bfloat16
MATRICES = ("tok_emb", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
            "in_proj", "x_proj", "dt_proj", "out_proj")


def gpt_config(config: Dict[str, Any]) -> M.GPTConfig:
    """The configuration file's published keys, under the program's names.
    What the program does not compute refuses here."""
    extra = dict(config.get("gpt_config", {}))
    extra["dtype"] = jnp.dtype(extra.get("dtype", "bfloat16")).type
    wrong = {
        "hidden_act": config["hidden_act"] != "silu",
        "num_experts": int(config["num_experts"]) != 1,
        "mamba_proj_bias": bool(config["mamba_proj_bias"]),
        "mamba_conv_bias": not config["mamba_conv_bias"],
        "sliding_window": config.get("sliding_window") is not None,
    }
    if any(wrong.values()):
        raise ValueError(
            "the program computes SwiGLU (silu), one dense expert, no "
            "projection bias, a conv bias and no sliding window; the "
            f"configuration differs in {[k for k, v in wrong.items() if v]}")
    return M.GPTConfig(
        vocab_size=int(config["vocab_size"]),
        n_layer=int(config["num_hidden_layers"]),
        n_head=int(config["num_attention_heads"]),
        n_kv_head=int(config["num_key_value_heads"]),
        d_model=int(config["hidden_size"]),
        d_ff=int(config["intermediate_size"]),
        max_seq_len=int(config["max_position_embeddings"]),
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope=False,
        attn_layer_period=int(config["attn_layer_period"]),
        attn_layer_offset=int(config["attn_layer_offset"]),
        mamba_d_state=int(config["mamba_d_state"]),
        mamba_d_conv=int(config["mamba_d_conv"]),
        mamba_expand=int(config["mamba_expand"]),
        mamba_dt_rank=int(config["mamba_dt_rank"]), **extra)


def _stored(tree):
    return {k: (v.astype(STORED) if k in MATRICES else v)
            for k, v in tree.items()}


def make_base(cfg: M.GPTConfig, seed: int):
    """The frozen base on the device from the seed, in ``init_params``'
    layout and the stored type: one jitted call a run of layers (the float32
    draw of ONE run is the most that exists beside the base), layer ``i``
    from the key ``M.init_params`` would hand it."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 3)

    def run(first, n):
        def make(ks):
            return jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[_stored(M.init_block(ks[j], cfg, first + j))
                  for j in range(n)])

        return jax.jit(make)(keys[first + 1:first + 1 + n])

    base = {"tok_emb": jax.jit(lambda k: (0.02 * jax.random.normal(
                k, (cfg.vocab_size, cfg.d_model), jnp.float32)
            ).astype(STORED))(keys[0]),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
            "runs": [run(first, n) for _, first, n in cfg.layer_runs()]}
    if not cfg.tie_embeddings:
        raise ValueError("the reference's head is tied to the embedding")
    return base


def reference_check(cfg: M.GPTConfig, base, ids: np.ndarray,
                    action_masks: np.ndarray, pad_id: int, seed: int,
                    program_lp: np.ndarray, rollout_lp: Optional[np.ndarray],
                    hit_rows: List[bool]):
    """``_llm.reference_check`` against the hybrid reference: the learn
    side's and the paged tier's own log-probabilities on ``CHECK_ROWS`` rows,
    a seeded sample of ``CHECK_POSITIONS`` completion positions each. At
    least one checked row must have been admitted by a prefix-cache hit.
    Returns (problems, a record for the progress line)."""
    rng = np.random.default_rng([seed, 7])
    rows = list(range(min(_llm.CHECK_ROWS, ids.shape[0])))
    checked_hits = sum(bool(hit_rows[r]) for r in rows if r < len(hit_rows))
    problems, record = [], {"checked_rows": rows,
                            "checked_prefix_hit_rows": checked_hits}
    if not checked_hits:
        problems.append(
            f"no checked row ({rows}) was admitted by a prefix-cache hit "
            f"(hits by row: {hit_rows}): the snapshot path went unchecked")
    diffs = {"learn": [], "rollout": []}
    magnitude = []
    for row in rows:
        # the bare sequence without its left padding, filled up on the right
        # to one shape (what follows a position cannot change it)
        real = np.flatnonzero(ids[row] != pad_id)
        first = int(real[0]) if real.size else 0
        tokens = np.concatenate([ids[row, first:], np.full(first, 2, ids.dtype)])
        cols = np.flatnonzero(action_masks[row] > 0)
        cols = rng.choice(cols, size=min(_llm.CHECK_POSITIONS, cols.size),
                          replace=False)
        cols.sort()
        want = ref.token_logprobs(base, tokens, cols - first,
                                  n_head=cfg.n_head, n_kv=cfg.kv_heads,
                                  eps=cfg.rms_eps)
        magnitude.append(np.abs(want))
        diffs["learn"].append(np.abs(program_lp[row, cols] - want))
        if rollout_lp is not None:
            n_new = rollout_lp.shape[1]
            comp_cols = cols - (ids.shape[1] - 1 - n_new)
            diffs["rollout"].append(np.abs(rollout_lp[row, comp_cols] - want))
    record["ref_lp_mean_abs"] = float(np.concatenate(magnitude).mean())
    for name, parts in diffs.items():
        if not parts:
            continue
        d = np.concatenate(parts)
        record[f"{name}_lp_mean_abs_diff"] = float(d.mean())
        record[f"{name}_lp_max_abs_diff"] = float(d.max())
        if not np.isfinite(d).all() or d.mean() > ref.LP_MEAN_TOL \
                or d.max() > ref.LP_MAX_TOL:
            problems.append(
                f"warm-up batch: {name} log-probabilities against the "
                f"reference: mean {d.mean():.4f} (tolerance "
                f"{ref.LP_MEAN_TOL}), max {d.max():.4f} (tolerance "
                f"{ref.LP_MAX_TOL})")
    return problems, record


class Session(grpo_loop.Session):
    """``grpo_loop.Session``'s window (``end_to_end``, ``finish``,
    ``trace_steps``) over this file's set-up and step."""

    # one traced step, not grpo_loop's two: a step here is 768 decode steps
    # x 28 layers of small operations, ~1.5 M device events; two of them
    # are a 200 MB .xplane.pb that takes two minutes to stop, load and
    # reduce (PERF.md, PR 27), one is half of each
    trace_steps = 1

    def __init__(self, cell, seed, devices):
        self.device = devices[0]
        mix = cell.traffic
        self.group = int(mix["group_size"])
        self.rows = int(mix["prompts_per_step"]) * self.group
        self.new_tokens = int(mix["new_tokens"])
        self.tok = traffic.IdTokenizer()
        self.cfg = gpt_config(cell.config)
        base = make_base(self.cfg, seed)
        self.agent = _llm.make_agent(
            self.cfg, base, seed, cell.config, self.tok, group_size=self.group,
            rows=self.rows, new_tokens=self.new_tokens)
        if {d for x in jax.tree_util.tree_leaves(base) for d in x.devices()} \
                != {self.device}:
            raise AssertionError("the base is not on the cell's device")
        self.env = ReasoningGym(
            traffic.dataset_rows(seed, grpo_loop.DATASET_ROWS, mix),
            traffic.dataset_rows(seed + 1, int(mix["prompts_per_step"]), mix),
            self.tok, reward_fn=traffic.seeded_reward(seed),
            data_batch_size=int(mix["prompts_per_step"]),
            max_context_length=int(mix["prompt_tokens"][1]))
        self.prompts = self.env.reset(eval_mode=True)
        self.problems: List[str] = []
        self.lora_flat = _llm.flat(self.agent.actor.params)
        record = self.step(check_reference=seed)
        harness.note(perfbench="warm-up step", **record)
        gen = self.agent._get_continuous_generator()
        stated = cell.config["serving"]
        built = {k: getattr(gen, k) for k in stated}
        if built != stated:
            self.problems.append(
                f"the rollout tier was built with {built}, the configuration "
                f"file states {stated}")

    def step(self, check_reference=None) -> Dict[str, Any]:
        agent, env = self.agent, self.env
        problems = []
        t_step = time.perf_counter()
        before = self.lora_flat
        with harness.span("get_action"):
            t0 = time.perf_counter()
            comp, cmask = agent.get_action(self.prompts)
            rollout_s = time.perf_counter() - t0
        info = agent.last_generation_info
        if info is None or "slots" not in info:
            problems.append(f"rollout left the continuous tier: {info}")
            info = {}
        with harness.span("assemble_and_reward"):
            ids, masks = env.assemble_learn_batch(comp, cmask)
            self.prompts, rewards = env.step(comp, cmask)
        ref_record = {}
        if check_reference is not None:
            lp = agent.behavior_logprobs(ids, masks)
            found, ref_record = reference_check(
                self.cfg, agent.base_params, ids, masks,
                self.tok.pad_token_id, check_reference, lp,
                info.get("logprobs"), info.get("prefix_hit_rows", []))
            problems += found
        with harness.span("learn"):
            t0 = time.perf_counter()
            loss, kl = agent.learn((ids, masks, rewards))
            learn_s = time.perf_counter() - t0
        after = self.lora_flat = _llm.flat(agent.actor.params)
        step_s = time.perf_counter() - t_step

        empty = int((cmask.sum(axis=1) == 0).sum())
        real = ids != self.tok.pad_token_id
        if not (rewards.std(axis=1) > 0).all():
            problems.append(f"rewards do not vary inside a group: {rewards}")
        if not (np.isfinite(loss) and np.isfinite(kl)):
            problems.append(f"loss {loss} kl {kl}")
        if not np.isfinite(after).all() or np.array_equal(before, after):
            problems.append("the adapters did not change")
        self.problems += problems
        return {
            "attempted": self.rows, "failed": empty if not problems else self.rows,
            "step_s": step_s, "rollout_s": rollout_s, "learn_s": learn_s,
            "new_tokens": int(cmask.sum()),
            "nonpad_tokens": int(real.sum()), "learn_tokens": int(ids.size),
            "row_lengths": real.sum(axis=1).tolist(),
            "prefix_cache_hits": int(info.get("prefix_cache_hits", -1)),
            "tier": "continuous" if "slots" in info else "other",
            "loss": float(loss), "kl": float(kl), **ref_record,
        }
