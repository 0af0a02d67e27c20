"""Closed-loop GRPO on one chip for a SmallThinker-class stack (sliding-window
layers with rotary positions and global layers without any, mixed by the two
published layout lists; 64 ReGLU experts of which 6 a token, chosen by a
router that reads the attention block's normed input; an untied head): the
same loop and records as ``grpo_loop`` — ``ReasoningGym.reset`` ->
``GRPO.get_action`` (paged continuous tier) -> ``assemble_learn_batch`` +
``step`` -> ``GRPO.learn`` — with its own reading of the configuration file
(the published ``smallthinker`` keys), a frozen base made a position in the
layer pattern's period at a time in the type it is stored in, and the
comparison with ``perfbench/reference/smallthinker_f32.py``, which must
include a row that the serving tier admitted by a prefix-cache hit: that
row's first decode step read a COPIED block, so prefill -> pool -> copied
block -> windowed paged decode is held to the reference's full masked
forward."""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.llm import model as M
from agilerl_tpu.utils.llm_utils import ReasoningGym
from perfbench import harness, traffic
from perfbench.reference import smallthinker_f32 as ref
from perfbench.runners import _llm, grpo_loop

#: the frozen base's matrices are stored in this type; norm scales and the
#: router's matrix stay float32
STORED = jnp.bfloat16
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def gpt_config(config: Dict[str, Any]) -> M.GPTConfig:
    """The configuration file's published keys, under the program's names.
    The two layout lists are kept whole as published; the stack is their
    first ``num_hidden_layers`` entries. What the program does not compute
    refuses here."""
    extra = dict(config.get("gpt_config", {}))
    extra["dtype"] = jnp.dtype(extra.get("dtype", "bfloat16")).type
    n = int(config["num_hidden_layers"])
    wrong = {
        "model_name": not str(config["model_name"]).startswith("smallthinker"),
        "rope_scaling": config["rope_scaling"] is not None,
        "tie_word_embeddings": bool(config["tie_word_embeddings"]),
        "moe_primary_router_apply_softmax":
            not config["moe_primary_router_apply_softmax"],
        "norm_topk_prob": not config["norm_topk_prob"],
        "sliding_window_layout": len(config["sliding_window_layout"]) < n,
        "rope_layout": len(config["rope_layout"]) < n,
    }
    if any(wrong.values()):
        raise ValueError(
            "the program computes grouped-query attention layers that are "
            "windowed and rotary by the two layouts (one entry a layer), "
            "unscaled rotary, ReGLU experts weighted by a softmax over the "
            "chosen logits, and an untied head; the configuration differs "
            f"in {[k for k, v in wrong.items() if v]}")
    return M.GPTConfig(
        vocab_size=int(config["vocab_size"]), n_layer=n,
        n_head=int(config["num_attention_heads"]),
        n_kv_head=int(config["num_key_value_heads"]),
        head_size=int(config["head_dim"]),
        d_model=int(config["hidden_size"]),
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]), tie_embeddings=False,
        sliding_window=int(config["sliding_window_size"]),
        window_layout=tuple(int(x) for x in config["sliding_window_layout"][:n]),
        rope_layout=tuple(int(x) for x in config["rope_layout"][:n]),
        n_experts=int(config["moe_num_primary_experts"]),
        expert_top_k=int(config["moe_num_active_primary_experts"]),
        capacity_factor=None,
        d_ff_expert=int(config["moe_ffn_hidden_size"]),
        router_score="softmax", norm_topk=True, expert_act="relu",
        router_input="attn", **extra)


def reference_args(cfg: M.GPTConfig) -> Dict[str, Any]:
    return dict(n_head=cfg.n_head, n_kv=cfg.kv_heads, theta=cfg.rope_theta,
                eps=cfg.rms_eps, top_k=cfg.expert_top_k,
                window=cfg.sliding_window, window_layout=cfg.window_layout,
                rope_layout=cfg.rope_layout)


def make_base(cfg: M.GPTConfig, seed: int, qk_std: float = 0.02):
    """The frozen base on the device from the seed, in ``init_params``'
    layout (a run of period P: P trees, one a position in the period) and
    the stored type: one jitted call a position that draws and stores a
    LAYER at a time (``lax.map``: the float32 draw of one layer, 1.6 GB, is
    the most that exists beside the base), layer ``i`` from the key
    ``M.init_params`` would hand it. ``wq`` and ``wk`` are drawn at
    ``qk_std`` where ``init_block`` draws every matrix at 0.02 (the
    configuration file says why)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 3)
    gain = qk_std / 0.02

    def stored(blk):
        out = {}
        for k, v in blk.items():
            if k in ("wq", "wk"):
                v = v * gain
            out[k] = v.astype(STORED) if k in MATRICES else v
        return out

    def run(first, n):
        period = cfg.run_period(first, n)
        # every layer of a run has layer ``first``'s leaves: a layer's
        # variant (window, rotary) changes no weight
        trees = [jax.jit(lambda ks: jax.lax.map(
            lambda k: stored(M.init_block(k, cfg, first)), ks))(
                keys[first + 1 + p:first + 1 + n:period])
            for p in range(period)]
        return trees if period > 1 else trees[0]

    matrix = lambda shape: jax.jit(lambda k: (  # noqa: E731
        0.02 * jax.random.normal(k, shape, jnp.float32)).astype(STORED))
    return {"tok_emb": matrix((cfg.vocab_size, cfg.d_model))(keys[0]),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
            "runs": [run(first, n) for _, first, n in cfg.layer_runs()],
            "lm_head": matrix((cfg.d_model, cfg.vocab_size))(keys[-1])}


def reference_check(cfg: M.GPTConfig, base, ids: np.ndarray,
                    action_masks: np.ndarray, pad_id: int, seed: int,
                    program_lp: np.ndarray, rollout_lp: Optional[np.ndarray],
                    hit_rows: List[bool]):
    """The learn side's and the paged tier's own log-probabilities against
    ``smallthinker_f32`` on ``_llm.CHECK_ROWS`` rows, a seeded sample of (at
    most) ``_llm.CHECK_POSITIONS`` completion positions each: against the
    reference as it is (float32), held to the three limits on the
    difference, and against the SAME reference computed in bfloat16
    throughout — a side has to lie nearer to the first than to the second
    (``ref.LP_NEARER_SLACK``; the reference file says why a fixed limit cannot
    tell the two). At least one checked row must have been admitted by a
    prefix-cache hit. The record also counts the reference's fragile choices
    at the checked positions (``ref.MARGIN``). Returns (problems, a
    record)."""
    rng = np.random.default_rng([seed, 7])
    rows = list(range(min(_llm.CHECK_ROWS, ids.shape[0])))
    checked_hits = sum(bool(hit_rows[r]) for r in rows if r < len(hit_rows))
    problems, record = [], {"checked_rows": rows,
                            "checked_prefix_hit_rows": checked_hits}
    if not checked_hits:
        problems.append(
            f"no checked row ({rows}) was admitted by a prefix-cache hit "
            f"(hits by row: {hit_rows}): the copied block went unchecked")
    # by side: |side - float32 reference|, |side - bfloat16 reference|
    diffs = {"learn": ([], []), "rollout": ([], [])}
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
        want, margin = ref.token_logprobs(
            base, tokens, cols - first, **reference_args(cfg))
        low, _ = ref.token_logprobs(
            base, tokens, cols - first, dtype=jnp.bfloat16,
            **reference_args(cfg))
        magnitude.append(np.abs(want))
        margins.append(margin)
        sides = {"learn": program_lp[row, cols]}
        if rollout_lp is not None:
            n_new = rollout_lp.shape[1]
            sides["rollout"] = rollout_lp[
                row, cols - (ids.shape[1] - 1 - n_new)]
        for name, got in sides.items():
            diffs[name][0].append(np.abs(got - want))
            diffs[name][1].append(np.abs(got - low))
    record["ref_lp_mean_abs"] = float(np.concatenate(magnitude).mean())
    margins = np.concatenate(margins, axis=1)  # [layers, positions]
    record["routing_choices_checked"] = int(margins.size)
    record["routing_choices_fragile"] = int((margins < ref.MARGIN).sum())
    record["positions_checked"] = int(margins.shape[1])
    record["positions_with_a_fragile_choice"] = int(
        (margins < ref.MARGIN).any(axis=0).sum())
    limits = {"median": ref.LP_MEDIAN_TOL, "mean": ref.LP_MEAN_TOL,
              "max": ref.LP_MAX_TOL}
    for name, (to_f32, to_bf16) in diffs.items():
        if not to_f32:
            continue
        d = np.concatenate(to_f32)  # ALL checked positions, fragile or not
        found = {"median": float(np.median(d)), "mean": float(d.mean()),
                 "max": float(d.max())}
        for what, value in found.items():
            record[f"{name}_lp_{what}_abs_diff"] = value
        # how far from the bfloat16 reference: nearer to float32, or as near
        far = float(np.median(np.concatenate(to_bf16)))
        record[f"{name}_lp_median_abs_diff_bf16"] = far
        nearer = found["median"] <= far + ref.LP_NEARER_SLACK
        if not np.isfinite(d).all() or not nearer \
                or any(found[w] > limits[w] for w in limits):
            problems.append(
                f"warm-up batch: {name} log-probabilities against the "
                "reference: " + ", ".join(
                    f"{w} {found[w]:.4f} (tolerance {limits[w]})"
                    for w in limits)
                + f"; median against the reference in bfloat16 {far:.4f} "
                f"(the float32 median may exceed it by {ref.LP_NEARER_SLACK})")
    return problems, record


class Session(grpo_loop.Session):
    """``grpo_loop.Session``'s window (``end_to_end``, ``finish``) over this
    file's set-up and step."""

    # one traced step holds 3 learn programs' worth of kernels and 128
    # decode steps: enough for every reader, and a trace that stays small
    trace_steps = 1

    def __init__(self, cell, seed, devices):
        self.device = devices[0]
        mix = cell.traffic
        self.group = int(mix["group_size"])
        self.rows = int(mix["prompts_per_step"]) * self.group
        self.new_tokens = int(mix["new_tokens"])
        self.tok = traffic.IdTokenizer()
        self.cfg = gpt_config(cell.config)
        base = make_base(self.cfg, seed,
                         float(cell.config["init"]["qk_std"]))
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
