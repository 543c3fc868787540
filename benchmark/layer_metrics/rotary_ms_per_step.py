"""Step program: device milliseconds a step of the ops under the scope
``rotary`` (the one-pass ``layers.rotate`` of the looped and banded stacks,
latent attention's ``interleaved_rotary``), forward, recomputation and
backward, each busy instant charged to the innermost op
(``_scopes.py``). Nothing where no op carries the scope."""

from benchmark.layer_metrics import _scopes


def read(obs):
    return _scopes.scope_ms(obs, "rotary")
