"""Flash attention's two backward kernels (``flash_dq`` and ``flash_dkv``,
``ops/flash_attention_vjp.py``) against the chip's bf16 peak: together BH x
Tp^2 x (4 d + 3 dv) FLOPs a pair — both form QK^T and dO V^T again; causal —
counted per execution from the event's own result type and the
configuration's head widths, over the device seconds of both (``_kernels``).
``None`` on a program whose kernels have no name (before PR 36)."""

from perfbench.layer_metrics import _kernels


def read(ctx):
    return _kernels.roofline(ctx, ("flash_dq", "flash_dkv"))
