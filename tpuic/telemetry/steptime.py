"""Per-step wall-clock breakdown: data-wait vs. dispatch vs. device.

The round-5 VERDICT called the cross-round ResNet MFU drift
*unfalsifiable* because nothing in-band recorded where step time goes;
this module is the in-band record.  The split uses only dispatch
timestamps plus the loop's existing deferred drain — the exact
discipline PR-2 established for the skip guard:

- **data wait**: time spent inside the loader iterator's ``__next__``
  (``wrap_epoch``).  Includes the first batch's device-cache upload and
  any producer-thread stall — the input-bound fraction FireCaffe-style
  accounting wants isolated (arXiv 1511.00175 §5).
- **dispatch**: the ``train_step`` call itself.  Under JAX's async
  dispatch this returns as soon as the work is enqueued, so in steady
  state it is microseconds; a blocking compile (first step, retrace)
  shows up here and the goodput tracker reattributes it using the
  ``compile`` events from the jax.monitoring bridge.
- **device**: the residual of the step's wall time — dominated by the
  deferred log drain blocking on metric handles (one interval behind,
  so the host is throttled to device speed) plus loop bookkeeping.

No new host syncs, no new compiles: everything here is
``time.perf_counter`` arithmetic (asserted in tests/test_telemetry.py
by counting ``jax.device_get`` calls and the jit cache size with
telemetry on vs. off).

The same intervals sit on the profiler's clock: the loader's ``__next__``
enters ``jax.profiler.TraceAnnotation("tpuic.step.next")``, the step call
``tpuic.step.dispatch``, the ``step`` event's publication (what its
subscribers do) ``tpuic.step.end`` (and the loop's deferred log drain
``tpuic.step.drain``), so a device trace names what the host was doing in
each idle gap between the device's executions. Annotations only, nothing
in the span ledger; with no profiler session each is one TraceMe
construction.

Every completed step publishes one ``step`` event:
``{step, total_ms, data_ms, dispatch_ms, device_ms}``.  Percentile
summaries ride the shared ``tpuic.metrics.LatencyMeter`` — the same
primitive serve's queue-wait/latency stats and bench.py's per-step
spread use.
"""

from __future__ import annotations

import time
from typing import Iterable, Iterator, Optional

from tpuic.metrics.meters import LatencyMeter
from tpuic.telemetry.spans import annotation


class StepTimer:
    """Accumulates one step's phase timings and publishes the breakdown.

    Usage (tpuic/train/loop.py)::

        timer.epoch_start()
        it = timer.wrap_epoch(loader.epoch(e))   # times __next__ = data wait
        for step, batch in enumerate(it):
            timer.dispatch_start()
            state, metrics = train_step(state, batch)   # async dispatch
            timer.dispatch_end()
            ...deferred drain, bookkeeping...
            timer.step_end(global_step)
    """

    def __init__(self, bus=None, window: int = 4096) -> None:
        if bus is None:
            from tpuic.telemetry.events import bus as _global_bus
            bus = _global_bus
        self.bus = bus
        self.total = LatencyMeter(window)
        self.data_wait = LatencyMeter(window)
        self.dispatch = LatencyMeter(window)
        self.steps = 0
        self.last_step = 0  # last published global step number
        self._t_mark: Optional[float] = None
        self._data_s = 0.0
        self._dispatch_s = 0.0
        self._t_dispatch: Optional[float] = None
        # (t0, t1) of the epoch's first loader wait and first dispatch,
        # from the timestamps taken anyway: the loop records them as the
        # epoch.first_batch / epoch.first_dispatch spans (telemetry/spans.py).
        self.first_batch: Optional[tuple] = None
        self.first_dispatch: Optional[tuple] = None

    # -- loop hooks ----------------------------------------------------
    def epoch_start(self) -> None:
        """Step-boundary reset: the first step's total is measured from
        here, so epoch setup (permutation, resident-cache upload inside
        the first ``__next__``) is attributed, not lost."""
        self._t_mark = time.perf_counter()
        self._data_s = 0.0
        self._dispatch_s = 0.0
        self.first_batch = self.first_dispatch = None

    def wrap_epoch(self, it: Iterable) -> Iterator:
        """Pass-through iterator that accumulates time spent waiting on
        the loader into the upcoming step's data-wait."""
        it = iter(it)
        while True:
            t0 = time.perf_counter()
            try:
                with annotation("step.next"):
                    item = next(it)
            except StopIteration:
                return
            t1 = time.perf_counter()
            self._data_s += t1 - t0
            if self.first_batch is None:
                self.first_batch = (t0, t1)
            yield item

    def dispatch_start(self) -> None:
        self._dispatching = annotation("step.dispatch")
        self._dispatching.__enter__()
        self._t_dispatch = time.perf_counter()

    def dispatch_end(self) -> None:
        if self._t_dispatch is not None:
            now = time.perf_counter()
            self._dispatching.__exit__(None, None, None)
            self._dispatch_s += now - self._t_dispatch
            if self.first_dispatch is None:
                self.first_dispatch = (self._t_dispatch, now)
            self._t_dispatch = None

    def step_end(self, step: int) -> dict:
        """Close the step: compute the breakdown, publish the ``step``
        event, reset the accumulators.  Returns the breakdown dict."""
        now = time.perf_counter()
        if self._t_mark is None:
            self._t_mark = now
        total = max(0.0, now - self._t_mark)
        self._t_mark = now
        data = min(self._data_s, total)
        disp = min(self._dispatch_s, max(0.0, total - data))
        device = max(0.0, total - data - disp)
        self._data_s = 0.0
        self._dispatch_s = 0.0
        self.steps += 1
        self.last_step = int(step)
        self.total.update(total)
        self.data_wait.update(data)
        self.dispatch.update(disp)
        out = {"step": int(step),
               "total_ms": round(1000.0 * total, 3),
               "data_ms": round(1000.0 * data, 3),
               "dispatch_ms": round(1000.0 * disp, 3),
               "device_ms": round(1000.0 * device, 3)}
        with annotation("step.end"):
            self.bus.publish("step", **out)
        return out

    # -- reads ---------------------------------------------------------
    def mean_total_s(self) -> float:
        return self.total.total / self.total.count if self.total.count else 0.0

    def summary(self) -> dict:
        """Percentile summary over the window (shared-meter semantics:
        recent behavior, not lifetime)."""
        return {
            "steps": self.steps,
            "total_ms": self.total.percentiles_ms(),
            "data_ms": self.data_wait.percentiles_ms(),
            "dispatch_ms": self.dispatch.percentiles_ms(),
            "data_frac": (round(self.data_wait.total
                                / max(self.total.total, 1e-12), 4)
                          if self.total.count else None),
        }
