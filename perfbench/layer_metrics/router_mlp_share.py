"""What a router that is a network costs in learn: device time under
``moe/score`` (the down-projection, the mix with the previous layer's router
state, the norm and the MLP, float32 throughout), forward and backward, over
the device time of the learn programs (``jit_logprobs``, ``jit_update``).
The dispatch (``moe/route``: softmax, top-1, sort, gather) is
``moe_route_share``'s. Read from the cell's own ``.xplane.pb``
(``_scopes``)."""

from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    return _scopes.share(ctx, ("moe/score",), _common.LEARN_PROGRAMS)
