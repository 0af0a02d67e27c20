"""Device time of the learn programs spent in Pallas kernels (custom
calls: flash attention fwd/dQ/dKV, fused loss fwd/dH) over all of their
device time."""

from perfbench import xplane
from perfbench.layer_metrics import _common


def read(ctx):
    lo, hi = ctx.trace.window
    inside = lambda program: bool(_common.LEARN_PROGRAMS.search(program))  # noqa: E731
    ops = xplane.op_seconds(ctx.trace, lo, hi, inside=inside)
    total = sum(ops.values())
    if not total:
        return None
    kernels = sum(v for k, v in ops.items()
                  if ctx.trace.categories.get(k) == "custom-call")
    return 100.0 * kernels / total
