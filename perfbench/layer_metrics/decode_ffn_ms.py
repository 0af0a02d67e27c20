"""Device milliseconds a decode step spends under ``decode/ffn``
(``model.forward_paged``'s call of ``_block_ffn`` in every layer: norm,
gate / up / down or the expert layer with its ``moe/*`` scopes, residual):
the time under the scope inside the decode-chunk program over calls x the
chunk's length, as ``decode_ms_per_step`` divides. ``None`` on a program
without the scope (before PR 36)."""

from perfbench.layer_metrics import _kernels


def read(ctx):
    return _kernels.decode_scope_ms(ctx, "decode/ffn")
