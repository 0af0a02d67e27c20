"""The fused lm-head loss's backward kernel with respect to the hidden
states (``fused_loss_dh``, ``ops/fused_loss.py``) against the chip's bf16
peak: 4 N D V FLOPs an execution — it forms the logits again, then
multiplies their gradient by the head — N from the event's own result type,
D and V from the configuration, over the executions' device seconds inside
the learn programs (``_kernels``). ``None`` without the kernel."""

from perfbench.layer_metrics import _kernels


def read(ctx):
    return _kernels.roofline(ctx, ("fused_loss_dh",))
