"""Closed-loop GRPO on one chip for a ZAYA1-class stack (compressed
convolutional attention with a rolling per-slot state beside the paged K/V
pool, 16 experts of which one a token chosen by a router MLP whose state is
carried from layer to layer, scaled residual merges, a tied head): the same
loop and records as ``grpo_loop`` — ``ReasoningGym.reset`` ->
``GRPO.get_action`` (paged continuous tier) -> ``assemble_learn_batch`` +
``step`` -> ``GRPO.learn`` — with its own reading of the configuration file
(the published ``zaya`` keys), a frozen base made run by run in the type it
is stored in, and the comparison with ``perfbench/reference/zaya_f32.py``,
which must include a row that the serving tier admitted by a prefix-cache
hit: that row's first decode step read a COPIED block and a rolling state
RESTORED from its snapshot, so prefill -> snapshot -> restored state ->
decode is held to the reference's full forward."""

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
from perfbench.reference import zaya_f32 as ref
from perfbench.runners import _llm, grpo_loop

#: the frozen base's matrices are stored in this type; norm scales, the
#: depthwise taps, both convolutions' biases, ``tau``, the merge vectors and
#: the whole router stay float32
STORED = jnp.bfloat16
MATRICES = ("tok_emb", "wq", "wk", "wv1", "wv2", "wo", "conv1_w", "w_gate",
            "w_up", "w_down")


def gpt_config(config: Dict[str, Any]) -> M.GPTConfig:
    """The configuration file's published keys, under the program's names.
    ``layer_types`` and ``rope_parameters`` are kept whole as published; the
    stack is the first ``num_hidden_layers`` entries. What the program does
    not compute refuses here."""
    extra = dict(config.get("gpt_config", {}))
    extra["dtype"] = jnp.dtype(extra.get("dtype", "bfloat16")).type
    n = int(config["num_hidden_layers"])
    rope = config["rope_parameters"]["hybrid"]
    wrong = {
        "model_type": config["model_type"] != "zaya",
        "hidden_act": config["hidden_act"] != "silu",
        "attention_bias": bool(config["attention_bias"]),
        "lm_head_bias": bool(config["lm_head_bias"]),
        "sliding_window": config["sliding_window"] is not None,
        "layer_types": set(config["layer_types"][:n]) != {"hybrid"}
        or len(config["layer_types"]) < n,
        "rope_parameters": rope["rope_type"] != "default"
        or rope["partial_rotary_factor"] != config["partial_rotary_factor"],
        "tie_word_embeddings": not config["tie_word_embeddings"],
        "num_experts_per_tok": int(config["num_experts_per_tok"]) != 1,
    }
    if any(wrong.values()):
        raise ValueError(
            "the program computes a stack of 'hybrid' layers (CCA, then "
            "SwiGLU experts of which ONE a token by softmax), full "
            "attention with unscaled rotary on a share of a head, no "
            "projection or head bias and a tied head; the configuration "
            f"differs in {[k for k, v in wrong.items() if v]}")
    return M.GPTConfig(
        vocab_size=int(config["vocab_size"]), n_layer=n,
        n_head=int(config["num_attention_heads"]),
        n_kv_head=int(config["num_key_value_heads"]),
        head_size=int(config["head_dim"]),
        d_model=int(config["hidden_size"]),
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=float(rope["rope_theta"]),
        rotary_share=float(config["partial_rotary_factor"]),
        rms_eps=float(config["rms_norm_eps"]), tie_embeddings=True,
        cca_time0=int(config["cca_time0"]), cca_time1=int(config["cca_time1"]),
        n_experts=int(config["num_experts"]), expert_top_k=1,
        capacity_factor=None,
        d_ff_expert=int(config["moe_intermediate_size"]),
        router_score="softmax", router_bias=True, norm_topk=False,
        router_hidden=int(config["router_hidden_size"]), scaled_merge=True,
        **extra)


def reference_args(cfg: M.GPTConfig) -> Dict[str, Any]:
    return dict(n_head=cfg.n_head, n_kv=cfg.kv_heads, theta=cfg.rope_theta,
                rotary=cfg.rotary_share, eps=cfg.rms_eps)


def _stored(tree):
    return {k: (v.astype(STORED) if k in MATRICES else v)
            for k, v in tree.items()}


def make_base(cfg: M.GPTConfig, seed: int):
    """The frozen base on the device from the seed, in ``init_params``'
    layout and the stored type: one jitted call a run of layers that draws
    and stores a LAYER at a time (``lax.map``: the float32 draw of one
    layer, 0.83 GB, is the most that exists beside the base), layer ``i``
    from the key ``M.init_params`` would hand it. The head is the embedding."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 3)

    def run(first, n):
        # every layer of a run has layer ``first``'s structure
        return jax.jit(lambda ks: jax.lax.map(
            lambda k: _stored(M.init_block(k, cfg, first)), ks))(
                keys[first + 1:first + 1 + n])

    emb = jax.jit(lambda k: (0.02 * jax.random.normal(
        k, (cfg.vocab_size, cfg.d_model), jnp.float32)).astype(STORED))
    return {"tok_emb": emb(keys[0]),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
            "runs": [run(first, n) for _, first, n in cfg.layer_runs()]}


def reference_check(cfg: M.GPTConfig, base, ids: np.ndarray,
                    action_masks: np.ndarray, pad_id: int, seed: int,
                    program_lp: np.ndarray, rollout_lp: Optional[np.ndarray],
                    hit_rows: List[bool], **how):
    """The learn side's and the paged tier's own log-probabilities against
    ``zaya_f32`` on ``_llm.CHECK_ROWS`` rows, a seeded sample of
    ``_llm.CHECK_POSITIONS`` completion positions each. At least one checked
    row must have been admitted by a prefix-cache hit. The record also
    counts the reference's fragile top-1 choices at the checked positions
    (``ref.MARGIN``). ``how`` goes to the reference (the precision control
    passes ``dtype``). Returns (problems, a record)."""
    rng = np.random.default_rng([seed, 7])
    rows = list(range(min(_llm.CHECK_ROWS, ids.shape[0])))
    checked_hits = sum(bool(hit_rows[r]) for r in rows if r < len(hit_rows))
    problems, record = [], {"checked_rows": rows,
                            "checked_prefix_hit_rows": checked_hits}
    if not checked_hits:
        problems.append(
            f"no checked row ({rows}) was admitted by a prefix-cache hit "
            f"(hits by row: {hit_rows}): the restored rolling state went "
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
    margins = np.concatenate(margins, axis=1)  # [layers, positions]
    record["routing_choices_checked"] = int(margins.size)
    record["routing_choices_fragile"] = int((margins < ref.MARGIN).sum())
    record["positions_checked"] = int(margins.shape[1])
    record["positions_with_a_fragile_choice"] = int(
        (margins < ref.MARGIN).any(axis=0).sum())
    limits = {"median": ref.LP_MEDIAN_TOL, "mean": ref.LP_MEAN_TOL,
              "max": ref.LP_MAX_TOL}
    for name, parts in diffs.items():
        if not parts:
            continue
        d = np.concatenate(parts)  # ALL checked positions, fragile or not
        found = {"median": float(np.median(d)), "mean": float(d.mean()),
                 "max": float(d.max())}
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

    # one traced step: a step is 768 decode steps x the layers of small
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
            # over the layers and steps: what a step's time follows
            "experts_hit": int(hit.value - hit_before),
            "loss": float(loss), "kl": float(kl), **ref_record,
        }
