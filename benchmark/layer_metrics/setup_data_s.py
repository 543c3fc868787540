"""Input: what the input layer costs before the first step. The
``trainer.data`` span (index, fingerprint, pack cache, loaders) plus the
first ``train_epoch``'s ``epoch.first_batch`` (the loader's first batch:
in a resident run the upload of the corpus)."""

from benchmark.layer_metrics._spans import (child, first, seconds, total,
                                            train_ledger)


def read(obs):
    records = train_ledger(obs)
    if records is None:
        return None
    warm_up = first(records, "train_epoch")
    return total(seconds(first(records, "trainer.data")),
                 seconds(child(records, warm_up, "epoch.first_batch")))
