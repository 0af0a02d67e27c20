"""``learn_mfu`` for a hybrid stack: useful FLOPs of a ``GRPO.learn`` call
(``counts_hybrid.grpo_learn_flops``: matmuls of both layer kinds and the
tied head, attention of the attention layers, the scan's elementwise work,
the adapters; a frozen base, remat's second forward not counted) over its
wall time and the chips' bf16 peak. Median over the steps. The scan's work
runs on the vector units, whose peak is far under the matrix units': the
share of the whole learn call, not of what its parts could reach."""

import statistics

from perfbench import counts_hybrid


def read(ctx):
    steps = [r for r in ctx.records if "learn_s" in r]
    if not steps:
        return None
    agent = ctx.cell.config["agent"]
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * statistics.median(
        counts_hybrid.grpo_learn_flops(
            ctx.cell.config, r["row_lengths"], int(agent["lora_rank"]),
            agent["lora_targets"]) / r["learn_s"] / peak for r in steps)
