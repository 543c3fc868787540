"""Compile, serve cells: backend compiles inside the window."""

from benchmark.layer_metrics._common import compiles_in_window as read  # noqa: F401
