"""Kernels and XLA ops: device milliseconds a step of the ops under the
scope ``attention_core`` (a dense core's contractions and softmax, or the
Pallas kernels that stand for it), forward, recomputation and backward
(``_scopes.py``). Nothing where no op carries the scope."""

from benchmark.layer_metrics import _scopes


def read(obs):
    return _scopes.scope_ms(obs, "attention_core")
