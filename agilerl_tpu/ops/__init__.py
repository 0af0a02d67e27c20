"""TPU Pallas kernels + the gate deciding when the framework uses them."""

import os

import jax


def pallas_enabled() -> bool:
    """True when the hot paths should route through the Pallas kernels:
    on the TPU backend, unless AGILERL_TPU_DISABLE_PALLAS is set (the XLA
    paths compute the same thing, less fused). The ONE gate: a run that
    lands on another backend takes the XLA paths, so a result that must
    come from the kernels has to check the device it ran on."""
    if os.environ.get("AGILERL_TPU_DISABLE_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


from agilerl_tpu.ops.flash_attention_vjp import flash_attention_diff  # noqa: E402
from agilerl_tpu.ops.fused_loss import fused_token_logprob
from agilerl_tpu.ops.ring_attention import make_ring_attention, ring_attention

__all__ = ["flash_attention_diff", "fused_token_logprob", "ring_attention",
           "make_ring_attention", "pallas_enabled"]
