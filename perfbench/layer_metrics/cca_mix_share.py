"""What CCA's sequence mixing costs in learn: device time under ``cca/mix``
(the depthwise and the head-wise causal convolution over the query/key
latents, the head means, the L2 norms, the key temperature, rotary and the
value shift), forward and backward, over the device time of the learn
programs (``jit_logprobs``, ``jit_update``). The projections
(``cca/project``) and attention itself are not in it. Read from the cell's
own ``.xplane.pb`` (``_scopes``)."""

from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    return _scopes.share(ctx, ("cca/mix",), _common.LEARN_PROGRAMS)
