"""Device milliseconds of a generation under ``evo/update`` (the scan over
an epoch's minibatches: loss, backward, the optax update, apply): the time
under the scope inside ``jit_generation`` over its calls, as ``gen_ms``
divides. ``None`` on a program without the scope (before PR 36)."""

from perfbench.layer_metrics import _common, _kernels


def read(ctx):
    return _kernels.scope_ms(ctx, "evo/update", _common.GENERATION_PROGRAM)
