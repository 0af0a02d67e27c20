"""What dropless dispatch costs: device time under ``moe/route`` (router
matmul, sigmoid, top-k, the sort of the (token, choice) pairs by expert and
the gather that permutes the rows) and ``moe/combine`` (the un-sort and the
weighted sum), forward and backward, over the device time of the learn
programs (``jit_logprobs``, ``jit_update``). Read from the cell's own
``.xplane.pb`` (``_scopes``)."""

from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    return _scopes.share(ctx, ("moe/route", "moe/combine"),
                         _common.LEARN_PROGRAMS)
