"""``device_idle_share`` where the end-to-end metric it should move is
``env_steps_s``."""

from perfbench.layer_metrics._common import idle_share as read  # noqa: F401
