"""1 - the union of the device's operation intervals over the traced
window (the one-chip GRPO cells; it should move ``grpo_tok_s``)."""

from perfbench.layer_metrics._common import idle_share as read  # noqa: F401
