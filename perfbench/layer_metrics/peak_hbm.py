"""``memory_stats()["peak_bytes_in_use"]``, the fullest chip, in GiB:
memory freed is depth or rows the learn step can use."""

from perfbench.layer_metrics._common import peak_gib as read  # noqa: F401
