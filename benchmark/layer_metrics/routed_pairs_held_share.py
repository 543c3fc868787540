"""Step program: of the token-expert pairs a step routes, the share that
falls on experts this chip holds, which are the pairs it computes:
``routed_pairs_held / routed_pairs`` of the ``train_epoch`` spans (each
epoch's last drained values), in percent; median over the window's
epochs. An even load reads held / published experts; it rises only if the
layer computes pairs it does not hold. Nothing where the program counts no
routed pairs."""

from benchmark.layer_metrics._routed import window_epochs_attr
from benchmark.stats import median


def read(obs):
    held = window_epochs_attr(obs, "routed_pairs_held")
    routed = window_epochs_attr(obs, "routed_pairs")
    if not held or len(held) != len(routed) or not all(routed):
        return None
    return median([100.0 * h / r for h, r in zip(held, routed)])
