"""Serve engine: valid rows over rows sent to the device."""


def read(obs):
    eff = obs.engine_stats.get("pad_efficiency")
    return None if eff is None else 100.0 * eff
