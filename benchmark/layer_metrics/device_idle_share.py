"""Device: 1 - busy / window on device 0, from the trace."""

from benchmark.layer_metrics._common import idle_share_percent as read  # noqa: F401
