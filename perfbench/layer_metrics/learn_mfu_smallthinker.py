"""``learn_mfu`` for a SmallThinker-class stack: useful FLOPs of a
``GRPO.learn`` call (``counts_smallthinker.grpo_learn_flops`` — ACTIVE
parameters only: the attention projections, the router, a token's six
experts, the untied head; attention by LIVE PAIRS, the causal half in a
global layer and the band in a window layer, over the rows' real lengths;
the adapters; one no-grad pass and the update; a frozen base, remat's second
forward not counted) over its wall time and the chips' bf16 peak. Median
over the steps: the share of the whole learn call."""

import statistics

from perfbench import counts_smallthinker


def read(ctx):
    steps = [r for r in ctx.records if "learn_s" in r]
    if not steps:
        return None
    agent = ctx.cell.config["agent"]
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * statistics.median(
        counts_smallthinker.grpo_learn_flops(
            ctx.cell.config, r["row_lengths"], int(agent["lora_rank"]),
            agent["lora_targets"]) / r["learn_s"] / peak for r in steps)
