"""Host time of a prefix-cache hit's restore on a hybrid stack: the
``sched/state_restore`` phase (the dispatch of the one program that copies
the last KV block and the recurrent-state snapshot into the slot), mean per
hit."""

import statistics

from perfbench.layer_metrics import _phases


def read(ctx):
    return _phases.per_call(ctx.trace, "sched/state_restore",
                            lambda s: s.dur * _phases.MS,
                            reduce=statistics.mean)
