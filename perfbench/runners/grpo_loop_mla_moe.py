"""Closed-loop GRPO on one chip for a DeepSeek-V3-class stack (latent
attention, a leading dense layer, dropless sigmoid-routed experts with a
shared expert): the same loop and records as ``grpo_loop`` —
``ReasoningGym.reset`` -> ``GRPO.get_action`` (paged continuous tier over
the latent pool) -> ``assemble_learn_batch`` + ``step`` -> ``GRPO.learn`` —
with its own reading of the configuration file (the published
``deepseek_v3`` keys), a frozen base made run by run in the type it is
stored in, and the comparison with
``perfbench/reference/deepseek_v3_f32.py``, which must include a row that
the serving tier admitted by a prefix-cache hit: that row's first decode
step read a COPIED latent block, so prefill -> latent cache -> absorbed
decode is held to the reference's full expanded forward."""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu import observability
from agilerl_tpu.llm import model as M
from agilerl_tpu.utils.llm_utils import ReasoningGym
from perfbench import harness, traffic
from perfbench.reference import deepseek_v3_f32 as ref
from perfbench.runners import _llm, grpo_loop

#: the frozen base's matrices are stored in this type; norm scales, the
#: router's matrix and its selection bias stay float32
STORED = jnp.bfloat16
MATRICES = ("tok_emb", "lm_head", "wq", "wkv_a", "wkv_b", "wo", "w_gate",
            "w_up", "w_down", "ws_gate", "ws_up", "ws_down")


def gpt_config(config: Dict[str, Any]) -> M.GPTConfig:
    """The configuration file's published keys, under the program's names.
    What the program does not compute refuses here."""
    extra = dict(config.get("gpt_config", {}))
    extra["dtype"] = jnp.dtype(extra.get("dtype", "bfloat16")).type
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    wrong = {
        "hidden_act": config["hidden_act"] != "silu",
        "q_lora_rank": config["q_lora_rank"] is not None,
        "rope_scaling": config["rope_scaling"] is not None,
        "rope_interleave": not config["rope_interleave"],
        "attention_bias": bool(config["attention_bias"]),
        "scoring_func": config["scoring_func"] != "sigmoid",
        "topk_method": config["topk_method"] != "noaux_tc",
        "n_group": (int(config["n_group"]), int(config["topk_group"])) != (1, 1),
        "moe_layer_freq": int(config["moe_layer_freq"]) != 1,
        "qk_head_dim": int(config["qk_head_dim"]) != nope + rope,
        "num_key_value_heads": int(config["num_key_value_heads"])
        != int(config["num_attention_heads"]),
        "tie_word_embeddings": bool(config["tie_word_embeddings"]),
    }
    if any(wrong.values()):
        raise ValueError(
            "the program computes SwiGLU (silu), latent attention without a "
            "query latent, bias or rotary scaling and with paired rotary "
            "dimensions, a sigmoid router with a selection bias and no group "
            "limiting, experts in every layer after the leading dense ones "
            "and an untied head; the configuration differs in "
            f"{[k for k, v in wrong.items() if v]}")
    return M.GPTConfig(
        vocab_size=int(config["vocab_size"]),
        n_layer=int(config["num_hidden_layers"]),
        n_head=int(config["num_attention_heads"]),
        d_model=int(config["hidden_size"]),
        d_ff=int(config["intermediate_size"]),
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_dim=nope, qk_rope_dim=rope,
        v_head_dim=int(config["v_head_dim"]),
        n_dense_layers=int(config["first_k_dense_replace"]),
        n_experts=int(config["n_routed_experts"]),
        expert_top_k=int(config["num_experts_per_tok"]),
        capacity_factor=None,
        d_ff_expert=int(config["moe_intermediate_size"]),
        d_ff_shared=int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        router_score="sigmoid", router_bias=True,
        norm_topk=bool(config["norm_topk_prob"]),
        routed_scale=float(config["routed_scaling_factor"]), **extra)


def reference_args(cfg: M.GPTConfig) -> Dict[str, Any]:
    return dict(n_head=cfg.n_head, nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim,
                theta=cfg.rope_theta, eps=cfg.rms_eps, top_k=cfg.expert_top_k,
                scale=cfg.routed_scale, norm_topk=cfg.norm_topk)


def _stored(tree):
    return {k: (v.astype(STORED) if k in MATRICES else v)
            for k, v in tree.items()}


def make_base(cfg: M.GPTConfig, seed: int):
    """The frozen base on the device from the seed, in ``init_params``'
    layout and the stored type: one jitted call a run of layers that draws
    and stores a LAYER at a time (``lax.map``: the float32 draw of one
    layer, 2.6 GB for an expert layer, is the most that exists beside the
    base), layer ``i`` from the key ``M.init_params`` would hand it."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 3)
    wide = lambda k, shape: jax.jit(lambda k: (  # noqa: E731
        0.02 * jax.random.normal(k, shape, jnp.float32)).astype(STORED))(k)

    def run(first, n):
        # every layer of a run has layer ``first``'s structure
        return jax.jit(lambda ks: jax.lax.map(
            lambda k: _stored(M.init_block(k, cfg, first)), ks))(
                keys[first + 1:first + 1 + n])

    return {"tok_emb": wide(keys[0], (cfg.vocab_size, cfg.d_model)),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
            "runs": [run(first, n) for _, first, n in cfg.layer_runs()],
            "lm_head": wide(keys[-1], (cfg.d_model, cfg.vocab_size))}


def reference_check(cfg: M.GPTConfig, base, ids: np.ndarray,
                    action_masks: np.ndarray, pad_id: int, seed: int,
                    program_lp: np.ndarray, rollout_lp: Optional[np.ndarray],
                    hit_rows: List[bool], positions_out: Optional[dict] = None,
                    **how):
    """``_llm.reference_check`` against the DeepSeek-V3 reference: the learn
    side's and the paged tier's own log-probabilities on ``CHECK_ROWS`` rows,
    a seeded sample of ``CHECK_POSITIONS`` completion positions each. At
    least one checked row must have been admitted by a prefix-cache hit.
    The record also counts the reference's fragile routing choices at the
    checked positions (``ref.MARGIN``). ``positions_out``, where given, is
    filled with the checked positions' smallest margin and learn-side
    difference; ``how`` goes to the reference (the precision control passes
    ``dtype``). Returns (problems, a record)."""
    rng = np.random.default_rng([seed, 7])
    rows = list(range(min(_llm.CHECK_ROWS, ids.shape[0])))
    checked_hits = sum(bool(hit_rows[r]) for r in rows if r < len(hit_rows))
    problems, record = [], {"checked_rows": rows,
                            "checked_prefix_hit_rows": checked_hits}
    if not checked_hits:
        problems.append(
            f"no checked row ({rows}) was admitted by a prefix-cache hit "
            f"(hits by row: {hit_rows}): the copied latent block went "
            "unchecked")
    diffs = {"learn": [], "rollout": []}
    magnitude, margins = [], []
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
        want, margin = ref.token_logprobs(base, tokens, cols - first,
                                          **reference_args(cfg), **how)
        magnitude.append(np.abs(want))
        margins.append(margin)
        diffs["learn"].append(np.abs(program_lp[row, cols] - want))
        if rollout_lp is not None:
            n_new = rollout_lp.shape[1]
            comp_cols = cols - (ids.shape[1] - 1 - n_new)
            diffs["rollout"].append(np.abs(rollout_lp[row, comp_cols] - want))
    record["ref_lp_mean_abs"] = float(np.concatenate(magnitude).mean())
    margins = np.concatenate(margins, axis=1)  # [expert layers, positions]
    if positions_out is not None:  # the precision control's closer look
        positions_out.update(
            margin_min=margins.min(axis=0).tolist(),
            learn=np.concatenate(diffs["learn"]).tolist())
    record["routing_choices_checked"] = int(margins.size)
    record["routing_choices_fragile"] = int((margins < ref.MARGIN).sum())
    record["positions_checked"] = int(margins.shape[1])
    record["positions_with_a_fragile_choice"] = int(
        (margins < ref.MARGIN).any(axis=0).sum())
    record["routing_margin_min"] = float(margins.min())
    for name, parts in diffs.items():
        if not parts:
            continue
        d = np.concatenate(parts)  # ALL checked positions, fragile or not
        found = {"median": float(np.median(d)), "mean": float(d.mean()),
                 "max": float(d.max())}
        limits = {"median": ref.LP_MEDIAN_TOL, "mean": ref.LP_MEAN_TOL,
                  "max": ref.LP_MAX_TOL}
        for what, value in found.items():
            record[f"{name}_lp_{what}_abs_diff"] = value
        if not np.isfinite(d).all() \
                or any(found[w] > limits[w] for w in limits):
            problems.append(
                f"warm-up batch: {name} log-probabilities against the "
                "reference: " + ", ".join(
                    f"{w} {found[w]:.4f} (tolerance {limits[w]})"
                    for w in limits))
    return problems, record


class Session(grpo_loop.Session):
    """``grpo_loop.Session``'s window (``end_to_end``, ``finish``) over this
    file's set-up and step."""

    # one traced step: a step is 768 decode steps x 7 layers of small
    # operations; two of them double the .xplane.pb and the time to stop,
    # load and reduce it (the grpo_loop_hybrid precedent)
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
        hit = observability.get_registry().counter(
            "serving/moe_experts_hit_total")
        hit_before = hit.value
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
            # distinct experts the rollout's decode steps touched, summed
            # over expert layers and steps: what a step's time follows
            "experts_hit": int(hit.value - hit_before),
            "loss": float(loss), "kl": float(kl), **ref_record,
        }
