"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip."""

from benchmark.layer_metrics._common import peak_hbm_gib as read  # noqa: F401
