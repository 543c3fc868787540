"""Input: device milliseconds a step of the loader's per-batch program
(``device_prep.make_resident_prep``: the gather of the batch's rows, the
geometry on uint8, colour and normalisation), every op of its executions
in the window (``_scopes.py``). Nothing where the loader has no such
program or the trace holds no execution of it."""

from benchmark.layer_metrics import _scopes


def read(obs):
    res = _scopes.attribution(obs)
    return None if res is None else res["programs"].get(_scopes.PREP)
