"""Step program: device milliseconds a step of the ops under the scope
``optimizer_update`` (the optimizer's update of parameters and moments, and
the batch statistics it carries over; ``_scopes.py``). Nothing where no op
carries the scope."""

from benchmark.layer_metrics import _scopes


def read(obs):
    return _scopes.scope_ms(obs, "optimizer_update")
