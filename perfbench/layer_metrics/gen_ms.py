"""Device time of one generation program."""

from perfbench.layer_metrics import _common


def read(ctx):
    seconds, calls = _common.program_total(ctx, _common.GENERATION_PROGRAM)
    return 1e3 * seconds / calls if calls else None
