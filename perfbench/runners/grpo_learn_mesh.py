"""``GRPO.learn`` on a mesh over the cell's chips (``GRPO.to_mesh``), fed
seeded batches as a learner is fed by a rollout tier elsewhere. A step is
one ``learn`` call: two no-grad log-probability passes and the update."""

from __future__ import annotations

import statistics
import time
from typing import Any, Dict, List

import jax
import numpy as np

from agilerl_tpu.parallel import plan as PL
from agilerl_tpu.parallel.mesh import make_mesh
from perfbench import harness, traffic
from perfbench.runners import _llm


def bytes_per_device(tree) -> Dict[Any, int]:
    held: Dict[Any, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return held


class Session:
    trace_steps = 3

    def __init__(self, cell, seed, devices):
        self.seed, self.mix = seed, cell.traffic
        self.tok = traffic.IdTokenizer()
        self.cfg = _llm.gpt_config(cell.config)
        self.mesh = make_mesh(devices=list(devices), **cell.config["mesh"])
        plan = PL.grpo_plan_for_mesh(self.mesh)
        # the base is made already sharded: GRPO.__init__ would otherwise
        # build all of it on chip 0, which bounds the depth by one chip
        base = _llm.make_base(
            self.cfg, seed,
            shardings=lambda shapes: plan.shardings("params", shapes, self.mesh))
        self.agent = _llm.make_agent(
            self.cfg, base, seed, cell.config, self.tok,
            group_size=int(self.mix["group_size"]), rows=int(self.mix["rows"]),
            new_tokens=int(self.mix["new_tokens"]))
        self.agent.to_mesh(self.mesh)
        self.problems: List[str] = []
        held = bytes_per_device(self.agent.base_params)
        total = sum(x.nbytes for x in
                    jax.tree_util.tree_leaves(self.agent.base_params))
        shares = {str(d): held.get(d, 0) / total for d in devices}
        want = 1.0 / len(devices)
        if not all(0.8 * want <= s <= 1.2 * want for s in shares.values()):
            self.problems.append(f"the base is not spread evenly: {shares}")
        self.n_steps = 0
        ids, masks, _ = traffic.learn_batch(
            seed, 0, self.cfg.vocab_size, self.mix)
        with self.mesh:
            lp = self.agent.behavior_logprobs(ids, masks)
        found, ref_record = _llm.reference_check(
            self.cfg, self.agent.base_params, ids, masks,
            self.tok.pad_token_id, seed, lp, "mesh log-probabilities")
        self.problems += found
        record = self.step()
        harness.note(perfbench="warm-up step", base_share_per_device=shares,
             **ref_record, **record)

    def step(self) -> Dict[str, Any]:
        ids, masks, rewards = traffic.learn_batch(
            self.seed, self.n_steps, self.cfg.vocab_size, self.mix)
        self.n_steps += 1
        before = _llm.flat(self.agent.actor.params)
        with harness.span("learn"), self.mesh:
            t0 = time.perf_counter()
            loss, kl = self.agent.learn((ids, masks, rewards))
            learn_s = time.perf_counter() - t0
        after = _llm.flat(self.agent.actor.params)
        problems = []
        if not (np.isfinite(loss) and np.isfinite(kl)):
            problems.append(f"loss {loss} kl {kl}")
        if not np.isfinite(after).all() or np.array_equal(before, after):
            problems.append("the adapters did not change")
        self.problems += problems
        rows = int(ids.shape[0])
        return {"attempted": rows, "failed": rows if problems else 0,
                "learn_s": learn_s, "learn_tokens": int(ids.size),
                "row_lengths": [int(ids.shape[1])] * rows,
                "loss": float(loss), "kl": float(kl)}

    def end_to_end(self, records) -> Dict[str, float]:
        return {"learn_tok_s": statistics.median(
            r["learn_tokens"] / r["learn_s"] for r in records)}

    def finish(self, records) -> List[str]:
        return []
