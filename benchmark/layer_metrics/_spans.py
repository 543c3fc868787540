"""What the readers of the program's span ledger share.

The program keeps a ledger of the host work that is not a step
(``tpuic/telemetry/spans.py``: ``import``, ``trainer.*``, ``train_epoch``
and its ``epoch.*`` children, on the ``perf_counter`` clock), and the
benchmark runs it in its own process, so a reader reads the ledger
directly. A program that has no ledger, and a run that is not a training
run, read as nothing."""

from __future__ import annotations

from typing import List, Optional


def train_ledger(obs) -> Optional[List[dict]]:
    """The ledger's records, oldest first; None outside the train mode
    (whose mark is ``epoch_gap_ms`` among the benchmark's spans: the
    ledger is the process's, and may hold the spans of a Trainer that is
    not this run's) and where the program has no span ledger."""
    if "epoch_gap_ms" not in obs.spans:
        return None
    try:
        from tpuic.telemetry import spans
    except ImportError:
        return None
    return spans.ledger.snapshot()


def seconds(rec: Optional[dict]) -> Optional[float]:
    return None if rec is None else rec["t1"] - rec["t0"]


def first(records: List[dict], name: str) -> Optional[dict]:
    """The earliest record of that name: set-up is a first occurrence,
    whatever number the epoch carries."""
    named = [r for r in records if r["name"] == name]
    return min(named, key=lambda r: r["t0"]) if named else None


def child(records: List[dict], parent: Optional[dict],
          name: str) -> Optional[dict]:
    if parent is None:
        return None
    return next((r for r in records if r["parent"] == parent["id"]
                 and r["name"] == name), None)


def total(*parts: Optional[float]) -> Optional[float]:
    """The sum, or nothing when a part is missing."""
    return None if any(p is None for p in parts) else sum(parts)


def union_seconds(records: List[dict]) -> Optional[float]:
    """Time covered by any of the records (an import block that imports
    another module with its own record covers it too)."""
    if not records:
        return None
    covered, edge = 0.0, float("-inf")
    for r in sorted(records, key=lambda r: r["t0"]):
        lo = max(r["t0"], edge)
        if r["t1"] > lo:
            covered, edge = covered + r["t1"] - lo, r["t1"]
    return covered
