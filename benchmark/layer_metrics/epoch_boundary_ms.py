"""Trainer loop: what the program itself spends between the last dispatch
of one epoch and the first of the next: ``epoch.tail`` of one
``train_epoch`` plus ``epoch.head`` and ``epoch.first_batch`` of the next.
Median over every pair of successive epochs after the first epoch (the
warm-up, which met everything for the first time), so the one boundary a
profiled slice may touch does not decide it. ``epoch_gap_ms`` times the
same boundary from outside and holds the first step's dispatch as well."""

from benchmark.layer_metrics._spans import child, seconds, total, train_ledger
from benchmark.stats import median


def read(obs):
    records = train_ledger(obs)
    if records is None:
        return None
    epochs = sorted((r for r in records if r["name"] == "train_epoch"),
                    key=lambda r: r["t0"])[1:]
    gaps = [total(seconds(child(records, e, "epoch.tail")),
                  seconds(child(records, nxt, "epoch.head")),
                  seconds(child(records, nxt, "epoch.first_batch")))
            for e, nxt in zip(epochs, epochs[1:])]
    gaps = [1e3 * g for g in gaps if g is not None]
    return median(gaps) if gaps else None
