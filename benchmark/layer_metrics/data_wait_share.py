"""Input layer: share of step time the loop waited for the loader.
Sum of ``data_ms`` over sum of ``total_ms`` of the program's step events."""


def read(obs):
    total = sum(e["total_ms"] for e in obs.step_events)
    if total <= 0:
        return None
    return 100.0 * sum(e["data_ms"] for e in obs.step_events) / total
