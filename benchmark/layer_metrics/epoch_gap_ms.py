"""Trainer loop: median time from the last step event of one epoch to the
first step event of the next, taken by the benchmark on its own clock: the
tail of one ``train_epoch``, the head of the next, and that first step's
own loader wait and dispatch."""

from benchmark.stats import median


def read(obs):
    gaps = obs.spans.get("epoch_gap_ms")
    return median(gaps) if gaps else None
