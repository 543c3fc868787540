"""What the readers of a routed layer's counters share: the program's
``train_epoch`` spans carry the family's counters as attributes (each
epoch's last drained values)."""

from __future__ import annotations

from typing import List, Optional

from benchmark.layer_metrics._spans import train_ledger


def window_epochs_attr(obs, name: str) -> Optional[List[float]]:
    """Attribute ``name`` of every ``train_epoch`` span after the first
    (the warm-up epoch), oldest first; None where there is no ledger, no
    such span, or one of them lacks the attribute (a program that counts
    no such thing)."""
    records = train_ledger(obs)
    if records is None:
        return None
    epochs = sorted((r for r in records if r["name"] == "train_epoch"),
                    key=lambda r: r["t0"])[1:]
    values = [(r.get("attrs") or {}).get(name) for r in epochs]
    return None if not values or any(v is None for v in values) else values
