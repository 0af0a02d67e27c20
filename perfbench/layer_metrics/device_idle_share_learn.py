"""``device_idle_share`` where the end-to-end metric it should move is
``learn_tok_s`` (the learn-only cell); averaged over the mesh's chips."""

from perfbench.layer_metrics._common import idle_share as read  # noqa: F401
