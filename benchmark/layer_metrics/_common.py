"""Arithmetic several readers share."""

from __future__ import annotations

from typing import Optional


def device0(obs) -> Optional[dict]:
    """The reduction of the lowest-numbered device in the trace."""
    trace = obs.trace
    if not trace or not trace.get("devices"):
        return None
    return trace["devices"][min(trace["devices"])]


def idle_share_percent(obs) -> Optional[float]:
    dev = device0(obs)
    if dev is None or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])


def peak_hbm_gib(obs) -> Optional[float]:
    peak = obs.spans.get("memory_peak_bytes")
    return None if peak is None else peak / 2**30


def compiles_in_window(obs) -> Optional[float]:
    n = obs.spans.get("compiles_in_window")
    return None if n is None else float(n)
