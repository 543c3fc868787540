"""Trainer loop: model, schedule, optimizer, the eager flax init, the
state's placement (``trainer.state_init``) and the construction of the
step functions (``trainer.build_steps``)."""

from benchmark.layer_metrics._spans import (first, seconds, total,
                                            train_ledger)


def read(obs):
    records = train_ledger(obs)
    if records is None:
        return None
    return total(seconds(first(records, "trainer.state_init")),
                 seconds(first(records, "trainer.build_steps")))
