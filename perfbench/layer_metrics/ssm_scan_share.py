"""Device time of the selective scan, forward (``ssm/scan``) and backward
(``ssm/scan_bwd``: the recompute of a chunk's states, the adjoint scan and
the batched gradient reductions), over the device time of the learn programs
(``jit_logprobs``, ``jit_update``). Read from the cell's own ``.xplane.pb``
(``_scopes``); forward alone and backward alone: ``_scopes.share`` with one
of the two names."""

from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    return _scopes.share(ctx, ("ssm/scan",), _common.LEARN_PROGRAMS)
