"""Closed-loop GRPO on one chip through the entry points a user calls:
``ReasoningGym.reset`` -> ``GRPO.get_action`` (paged continuous tier) ->
``assemble_learn_batch`` + ``step`` (decode to text, reward) ->
``GRPO.learn``. A step is one such round; the next starts when it ends."""

from __future__ import annotations

import statistics
import time
from typing import Any, Dict, List

import jax
import numpy as np

from agilerl_tpu.utils.llm_utils import ReasoningGym
from perfbench import harness, traffic
from perfbench.runners import _llm

DATASET_ROWS = 256  # more prompts than any window takes steps


class Session:
    trace_steps = 2

    def __init__(self, cell, seed, devices):
        self.device = devices[0]
        mix = cell.traffic
        self.group = int(mix["group_size"])
        self.rows = int(mix["prompts_per_step"]) * self.group
        self.new_tokens = int(mix["new_tokens"])
        self.tok = traffic.IdTokenizer()
        self.cfg = _llm.gpt_config(cell.config)
        base = _llm.make_base(self.cfg, seed)
        self.agent = _llm.make_agent(
            self.cfg, base, seed, cell.config, self.tok, group_size=self.group,
            rows=self.rows, new_tokens=self.new_tokens)
        if {d for x in jax.tree_util.tree_leaves(base) for d in x.devices()} \
                != {self.device}:
            raise AssertionError("the base is not on the cell's device")
        self.env = ReasoningGym(
            traffic.dataset_rows(seed, DATASET_ROWS, mix),
            traffic.dataset_rows(seed + 1, int(mix["prompts_per_step"]), mix),
            self.tok, reward_fn=traffic.seeded_reward(seed),
            data_batch_size=int(mix["prompts_per_step"]),
            max_context_length=int(mix["prompt_tokens"][1]))
        # the warm-up step takes the test split's prompt, so that the window
        # starts at row 0 of the training rows (see traffic.prompt_lengths)
        self.prompts = self.env.reset(eval_mode=True)
        self.problems: List[str] = []
        self.lora_flat = _llm.flat(self.agent.actor.params)
        # one whole warm-up step compiles every shape of the loop; the
        # reference is compared on its batch, before the adapters first move
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
        if check_reference is not None:
            lp = agent.behavior_logprobs(ids, masks)
            found, ref_record = _llm.reference_check(
                self.cfg, agent.base_params, ids, masks,
                self.tok.pad_token_id, check_reference, lp, "warm-up batch",
                rollout_lp=info.get("logprobs"))
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
        record = {
            "attempted": self.rows, "failed": empty if not problems else self.rows,
            "step_s": step_s, "rollout_s": rollout_s, "learn_s": learn_s,
            "new_tokens": int(cmask.sum()),
            "nonpad_tokens": int(real.sum()), "learn_tokens": int(ids.size),
            "row_lengths": real.sum(axis=1).tolist(),
            "prefix_cache_hits": int(info.get("prefix_cache_hits", -1)),
            "tier": "continuous" if "slots" in info else "other",
            "loss": float(loss), "kl": float(kl),
        }
        if check_reference is not None:
            record.update(ref_record)
        return record

    def end_to_end(self, records) -> Dict[str, float]:
        # tokens of a step: the mean over whole mirrored pairs of steps (see
        # traffic.prompt_lengths), which is the same for every seed; time of
        # a step: the median, which a single late step does not move
        pairs = len(records) - len(records) % 2 or len(records)
        tokens = sum(r["nonpad_tokens"] for r in records[:pairs]) / pairs
        return {
            "grpo_tok_s": tokens / statistics.median(
                r["step_s"] for r in records),
            "rollout_tok_s": statistics.median(
                r["new_tokens"] / r["rollout_s"] for r in records),
            "learn_tok_s": statistics.median(
                r["learn_tokens"] / r["learn_s"] for r in records),
        }

    def finish(self, records) -> List[str]:
        # step() files its problems under self.problems as it goes
        return []
