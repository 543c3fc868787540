"""Trainer loop: median host time around the call of the jitted step."""

from benchmark.stats import median


def read(obs):
    if not obs.step_events:
        return None
    return median([e["dispatch_ms"] for e in obs.step_events])
