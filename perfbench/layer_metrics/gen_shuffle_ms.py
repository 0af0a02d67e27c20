"""Device milliseconds of a generation under ``evo/shuffle`` (the call of
``shuffled_minibatches`` in every PPO epoch: the permutation's sorts with
the rows riding them): the time under the scope inside ``jit_generation``
over its calls, as ``gen_ms`` divides. ``None`` on a program without the
scope (before PR 36)."""

from perfbench.layer_metrics import _common, _kernels


def read(ctx):
    return _kernels.scope_ms(ctx, "evo/shuffle", _common.GENERATION_PROGRAM)
