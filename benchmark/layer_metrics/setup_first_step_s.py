"""Compile: the first ``train_step`` call of the process, the first
``train_epoch``'s ``epoch.first_dispatch``: tracing and lowering in
Python as well as the backend compile-or-load that ``compile_s`` counts."""

from benchmark.layer_metrics._spans import (child, first, seconds,
                                            train_ledger)


def read(obs):
    records = train_ledger(obs)
    if records is None:
        return None
    return seconds(child(records, first(records, "train_epoch"),
                         "epoch.first_dispatch"))
