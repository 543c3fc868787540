"""Device, serve cells: peak bytes in use on the chip."""

from benchmark.layer_metrics._common import peak_hbm_gib as read  # noqa: F401
