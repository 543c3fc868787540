"""Load generator: p99 of (actual submit - due time). Beyond the traffic
file's ``late_limit_ms`` the run is void, not fast."""


def read(obs):
    return obs.spans.get("late_ms_p99")
