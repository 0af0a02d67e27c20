"""Flash attention's forward kernel (``flash_fwd``,
``ops/flash_attention_vjp.py``) against the chip's bf16 peak: BH x Tp^2 x
(d + dv) FLOPs an execution — causal, so half the square; BH and Tp from the
event's own result type, the head widths from the configuration — summed
over the executions inside the learn programs, over their device seconds
(``_kernels``). Compute bounds it: a block of K and V is read once a query
block. ``None`` on a program whose kernel has no name (before PR 36)."""

from perfbench.layer_metrics import _kernels


def read(ctx):
    return _kernels.roofline(ctx, ("flash_fwd",))
