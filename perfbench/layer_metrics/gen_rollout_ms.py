"""Device milliseconds of a generation under ``evo/rollout``
(``EvoPPO.member_iteration``: the rollout scan, the bootstrap value and
GAE): the time under the scope inside ``jit_generation`` over its calls, as
``gen_ms`` divides. With ``gen_shuffle_ms`` and ``gen_update_ms`` it adds up
to less than ``gen_ms``; the rest is tournament, mutation and the
operations between the scopes. ``None`` on a program without the scope
(before PR 36)."""

from perfbench.layer_metrics import _common, _kernels


def read(ctx):
    return _kernels.scope_ms(ctx, "evo/rollout", _common.GENERATION_PROGRAM)
