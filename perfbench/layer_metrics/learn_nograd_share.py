"""Device time of the two no-grad log-probability programs over the device
time of all of ``GRPO.learn``'s programs."""

from perfbench.layer_metrics import _common


def read(ctx):
    nograd, _ = _common.program_total(ctx, _common.NOGRAD_PROGRAMS)
    every, _ = _common.program_total(ctx, _common.LEARN_PROGRAMS)
    return 100.0 * nograd / every if every else None
