"""``decode_hbm_share`` for a hybrid stack: the least bytes a decode step
has to move (``counts_hybrid.decode_step_bytes``: every matmul weight and
the tied head once in the stored dtype, the live KV of the attention layers
at the middle of the rollout, every slot's recurrent state read and written
once) over the peak HBM bandwidth, over the measured time of a step."""

from perfbench import counts_hybrid
from perfbench.layer_metrics import decode_ms_per_step


def read(ctx):
    ms = decode_ms_per_step.read(ctx)
    steps = [r for r in ctx.records if "row_lengths" in r]
    slots = ctx.cell.config.get("serving", {}).get("slots")
    if ms is None or not steps or not slots:
        return None
    new = int(ctx.cell.traffic["new_tokens"])
    live = sum(sum(n - new / 2 for n in r["row_lengths"]) for r in steps) / len(steps)
    least_s = counts_hybrid.decode_step_bytes(
        ctx.cell.config, live, int(slots)) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
