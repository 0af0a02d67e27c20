"""Flash attention's forward kernel (``flash_fwd`` in the global layers,
``flash_fwd_win`` in the window layers) against the chip's bf16 peak, each
execution credited with its own live pairs (``_flash_swa``). Compute bounds
it."""

from perfbench.layer_metrics import _flash_swa


def read(ctx):
    return _flash_swa.roofline(ctx, ("flash_fwd",))
