"""Device milliseconds a decode step spends under ``decode/proj``
(``model.forward_paged``'s attention layers: the norm, the q / k / v
projections with bias, adapters and rotary — ``cca/*`` or ``mla/expand``
nested where the stack has them — and ``wo`` with its residual merge;
state-space layers have ``ssm/step``): the time under the scope inside the
decode-chunk program over calls x the chunk's length, as
``decode_ms_per_step`` divides. ``None`` on a program without the scope
(before PR 36)."""

from perfbench.layer_metrics import _kernels


def read(ctx):
    return _kernels.decode_scope_ms(ctx, "decode/proj")
