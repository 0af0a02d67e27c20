"""The fused lm-head loss's forward kernel (``fused_loss_fwd``,
``ops/fused_loss.py``; every no-grad pass and the update's forward) against
the chip's bf16 peak: 2 N D V FLOPs an execution, N from the event's own
result type, D and V from the configuration, over the executions' device
seconds inside the learn programs (``_kernels``). Compute bounds it since
PR 28 (the head is read N / 1024 times). ``None`` without the kernel."""

from perfbench.layer_metrics import _kernels


def read(ctx):
    return _kernels.roofline(ctx, ("fused_loss_fwd",))
