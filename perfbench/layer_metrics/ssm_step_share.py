"""Device time of the one-token state-space step (``ssm/step``: the conv
window's shift and taps, and the update of the SSM state with its read-out)
over the device time of the decode-chunk program. Read from the cell's own
``.xplane.pb`` (``_scopes``)."""

from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    return _scopes.share(ctx, ("ssm/step",), _common.DECODE_PROGRAM)
