"""Device time of paged decode attention (``paged/attend``: the chunk loop
of ``ops.decode_attention.chunked_paged_attention`` — taking a live chunk's
blocks from the pool through the block table, putting the new K/V in, the
two matmuls and the online softmax; every attention layer, both cache
layouts) over the device time of the decode-chunk program. Parts "gather +
attention" from "projections, head and small operations" inside
``decode_ms_per_step``. Read from the cell's own ``.xplane.pb``
(``_scopes``); a program without the scope (before PR 32) gives ``None``."""

from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    return _scopes.share(ctx, ("paged/attend",), _common.DECODE_PROGRAM)
