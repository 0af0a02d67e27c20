"""Roofline share of a decode step, memory-bound: the least bytes it has
to read (``counts.decode_step_bytes``: block and head weights once in the
compute dtype, and the live KV of every slot at the middle of the rollout)
over the peak HBM bandwidth, over the measured time of a step."""

from perfbench import counts
from perfbench.layer_metrics import decode_ms_per_step


def read(ctx):
    ms = decode_ms_per_step.read(ctx)
    steps = [r for r in ctx.records if "row_lengths" in r]
    if ms is None or not steps:
        return None
    new = int(ctx.cell.traffic["new_tokens"])
    live = sum(sum(n - new / 2 for n in r["row_lengths"]) for r in steps) / len(steps)
    least_s = counts.decode_step_bytes(ctx.cell.config, live) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
