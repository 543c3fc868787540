"""Serve engine: p50 of the ``queue`` span plus p50 of the ``batch`` span
of the engine's own ledger (submit -> popped -> batch closed)."""


def read(obs):
    span = obs.engine_stats.get("span_ms", {})
    if "queue" not in span or "batch" not in span:
        return None
    return span["queue"]["p50"] + span["batch"]["p50"]
