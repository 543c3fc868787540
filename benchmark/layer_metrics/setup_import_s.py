"""Entry point: seconds in the program's ``import`` spans, which cover the
import graph of ``train.py`` and ``tpuic/train/loop.py`` beyond what the
caller had imported before (the benchmark imports jax first)."""

from benchmark.layer_metrics._spans import train_ledger, union_seconds


def read(obs):
    records = train_ledger(obs)
    if records is None:
        return None
    return union_seconds([r for r in records if r["name"] == "import"])
