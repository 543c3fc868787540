"""Compile: backend compiles inside the window; any makes the run wrong."""

from benchmark.layer_metrics._common import compiles_in_window as read  # noqa: F401
