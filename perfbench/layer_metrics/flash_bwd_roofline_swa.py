"""Flash attention's two backward kernels (``flash_dq`` and ``flash_dkv``
in the global layers, ``flash_dq_win`` and ``flash_dkv_win`` in the window
layers) against the chip's bf16 peak, each execution credited with its own
live pairs (``_flash_swa``)."""

from perfbench.layer_metrics import _flash_swa


def read(ctx):
    return _flash_swa.roofline(ctx, ("flash_dq", "flash_dkv"))
