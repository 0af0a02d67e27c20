"""Useful FLOPs of a ``GRPO.learn`` call (``counts.grpo_learn_flops``: a
frozen base, so no weight gradients; remat's second forward not counted)
over its wall time and the chips' bf16 peak. Median over the steps."""

import statistics

from perfbench import counts


def read(ctx):
    steps = [r for r in ctx.records if "learn_s" in r]
    if not steps:
        return None
    agent = ctx.cell.config["agent"]
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * statistics.median(
        counts.grpo_learn_flops(ctx.cell.config, r["row_lengths"],
                                int(agent["lora_rank"]), agent["lora_targets"])
        / r["learn_s"] / peak for r in steps)
