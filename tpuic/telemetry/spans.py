"""Span ledger: host work that happens once, or once an epoch.

The ``StepTimer`` covers the step path and nothing else; this module is the
record of what is not a step — the import graph, the stages of
``Trainer.__init__``, the head and tail of every ``train_epoch``
(docs/observability.md, "Spans"). A span is ``{id, parent, name, t0, t1,
thread, attrs}``: ``t0``/``t1`` are ``time.perf_counter()`` seconds,
``parent`` the id of the span open on the same thread when this one began.

Two clocks at once: a live ``span(...)`` also enters a
``jax.profiler.TraceAnnotation("tpuic." + name)`` — only when jax is
already imported, because the supervisor, gang and router processes import
``tpuic.telemetry`` and must stay jax-free — so in any profiler session the
same interval sits on the host plane beside the device ops. ``record(...)``
enters an interval that is already over into the ledger alone.

Always on, stdlib only. Bounded: the first ``KEEP_FIRST`` records (set-up
arrives first) are kept for the life of the process, the rest in a ring.
"""

from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from tpuic.telemetry.events import Event, bus

KEEP_FIRST = 64
KEEP_RECENT = 4096


class Ledger:
    """The process-wide list of closed spans, oldest first."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._first: List[dict] = []            # set-up: kept for good
        self._recent: deque = deque(maxlen=KEEP_RECENT)
        self.origin: Optional[float] = None     # t0 of the first record

    def add(self, rec: dict) -> None:
        with self._lock:
            if self.origin is None:
                self.origin = rec["t0"]
            if len(self._first) < KEEP_FIRST:
                self._first.append(rec)
            else:
                self._recent.append(rec)

    def snapshot(self) -> List[dict]:
        with self._lock:
            return [dict(r) for r in (*self._first, *self._recent)]

    def clear(self) -> None:
        with self._lock:
            self._first, self.origin = [], None
            self._recent.clear()


class _Open(threading.local):
    """Ids of the spans open on this thread, outermost first."""

    def __init__(self) -> None:
        self.stack: List[int] = []


ledger = Ledger()
_ids = itertools.count(1)
_open = _Open()


def _event_data(rec: dict) -> dict:
    return {**rec["attrs"], "name": rec["name"], "id": rec["id"],
            "parent": rec["parent"],
            "start_s": round(rec["t0"] - (ledger.origin or rec["t0"]), 6),
            "dur_ms": round(1e3 * (rec["t1"] - rec["t0"]), 3)}


def record(name: str, t0: float, t1: float, *, span_id: Optional[int] = None,
           **attrs) -> dict:
    """Enter an interval that is already over (``perf_counter`` seconds)
    as a child of the span now open on this thread."""
    rec = {"id": next(_ids) if span_id is None else span_id,
           "parent": _open.stack[-1] if _open.stack else None, "name": name,
           "t0": t0, "t1": t1, "thread": threading.get_ident(),
           "attrs": attrs}
    ledger.add(rec)
    if bus.active("span"):
        bus.publish("span", **_event_data(rec))
    return rec


def annotation(name: str):
    """``jax.profiler.TraceAnnotation("tpuic." + name)``: the interval on
    the profiler's clock alone, nothing in the ledger (the step loop's
    phases, ``tpuic.step.*``); a null context while jax is not imported."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return (profiler.TraceAnnotation("tpuic." + name)
            if profiler is not None else contextlib.nullcontext())


class span:
    """``with span("trainer.data", images=n) as sp:`` — ``sp.attrs`` may
    gain keys until the block ends."""

    def __init__(self, name: str, **attrs) -> None:
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "span":
        self.id = next(_ids)
        self._annotation = annotation(self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        _open.stack.append(self.id)
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        _open.stack.pop()
        record(self.name, self.t0, t1, span_id=self.id, **self.attrs)


def replay(fn: Callable[[Event], None]) -> int:
    """Hand every span of the ledger to one subscriber that arrived late
    (the ``--metrics-jsonl`` sink is attached at the end of
    ``Trainer.__init__``), as the ``span`` events it missed."""
    records = ledger.snapshot()
    tag = bus.rank_tag or {}
    for rec in records:
        fn(Event("span", time.time(), {**tag, **_event_data(rec)}))
    return len(records)


def self_time(records: List[dict]) -> Dict[int, float]:
    """id -> seconds of the span that none of its children cover (their
    union, not their sum: children recorded from shared timestamps may
    overlap)."""
    children: Dict[int, list] = {}
    for r in records:
        children.setdefault(r["parent"], []).append(r)
    out = {}
    for r in records:
        covered, edge = 0.0, r["t0"]
        for c in sorted(children.get(r["id"], ()), key=lambda c: c["t0"]):
            lo, hi = max(c["t0"], edge), min(c["t1"], r["t1"])
            if hi > lo:
                covered, edge = covered + hi - lo, hi
        out[r["id"]] = (r["t1"] - r["t0"]) - covered
    return out
