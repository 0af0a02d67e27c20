"""How much of a learn call is attention: device time under ``attn/win`` +
``attn/full`` (a window or a global layer's attention block in the learn
programs: norm, q / k / v with adapters and rotary, the flash kernels, ``wo``
and the residual), forward and backward, over the device time of the learn
programs (``jit_logprobs``, ``jit_update``). The scopes exist in a stack
whose layers have variants only (``GPTConfig.varies``). Read from the cell's
own ``.xplane.pb`` (``_scopes``)."""

from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    return _scopes.share(ctx, ("attn/win", "attn/full"),
                         _common.LEARN_PROGRAMS)
