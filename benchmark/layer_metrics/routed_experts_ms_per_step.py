"""Step program: device milliseconds a step of the ops under the scope
``routed_experts`` (the routed sum: ``dispatch``, ``expert_matmul`` and
``combine`` inside it, and the self time of the ``conditional`` that picks
its buffer), forward, recomputation and backward (``_scopes.py``). Nothing
where no op carries the scope."""

from benchmark.layer_metrics import _scopes


def read(obs):
    return _scopes.scope_ms(obs, "routed_experts")
