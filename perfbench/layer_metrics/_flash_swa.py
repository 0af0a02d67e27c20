"""Flash attention's kernels against the chip's bf16 peak in a stack that
mixes window and full attention: every execution inside the learn programs
is credited with the pairs ITS mask allows
(``counts_smallthinker.flash_execution_flops``: a ``flash_*_win`` execution
its band, any other the causal half; BH and Tp from the event's own result
type), summed, over their device seconds. ``_kernels.roofline`` credits
every execution with the causal half, which over-credits a windowed one (by
a third at T 8192 under a window of 4096). ``None`` without a trace file or
such a kernel."""

from perfbench import counts_smallthinker
from perfbench.layer_metrics import _common, _kernels, _scopes


def roofline(ctx, kernels):
    path = _scopes.cell_trace(ctx)
    if path is None:
        return None
    # the windowed name holds the plain one: look for it first
    stems = tuple(k + "_win" for k in kernels) + tuple(kernels)
    found = _kernels.executions(ctx.trace, _kernels.operations(str(path)),
                                stems, _common.LEARN_PROGRAMS)
    seconds = sum(s for _, _, s in found)
    if not seconds:
        return None
    flops = sum(counts_smallthinker.flash_execution_flops(
        ctx.cell.config, stem, _kernels.shapes(result)[0])
        for stem, result, _ in found)
    return 100.0 * flops / seconds / ctx.peaks["bf16_flops_per_s"]
