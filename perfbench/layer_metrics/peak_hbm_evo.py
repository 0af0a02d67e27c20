"""``peak_hbm`` where the end-to-end metric it should move is
``env_steps_s``: memory freed is environments a member can run."""

from perfbench.layer_metrics._common import peak_gib as read  # noqa: F401
