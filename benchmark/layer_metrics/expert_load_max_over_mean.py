"""Step program: the busiest held expert's rows over the mean held
expert's, mean over the expert layers: what sizes a grouped matrix
product's longest group here and an exchange's buffers in the deployment.
The program's counter of that name as the ``train_epoch`` spans carry it
(each epoch's last drained value); median over the window's epochs.
Nothing where the program counts no routed pairs."""

from benchmark.layer_metrics._routed import window_epochs_attr
from benchmark.stats import median


def read(obs):
    loads = window_epochs_attr(obs, "expert_load_max_over_mean")
    return median(loads) if loads else None
