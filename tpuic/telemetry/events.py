"""Structured event bus: lightweight publish/subscribe of typed events.

The train loop, checkpoint manager, dataset quarantine, serving engine,
and the jax compile monitor all publish here instead of (only) printing
ad-hoc log lines; sinks subscribe — JSONL for machines, memory for
tests, TensorBoard for dashboards.  docs/observability.md documents the
event schema.

Design constraints (the hot-loop discipline):

- **Free when idle**: ``publish`` on a bus with no subscribers is one
  attribute read and a falsy check — telemetry wiring can stay in the
  per-step path unconditionally.
- **Host-only**: nothing in this module touches JAX arrays.  Event data
  values must be plain JSON-able scalars/strings the caller already has
  on host; publishing never forces a device sync (test-asserted).
- **Thread-safe**: emitters run in producer threads, the serve batcher,
  and the train loop; subscription mutates under a lock while publish
  reads an immutable snapshot tuple.
- **Sink failures are contained**: a sink raising must not take down
  the training step or the batcher — the error is counted
  (``bus.sink_errors``) and the event is delivered to the remaining
  subscribers.

This module deliberately imports neither jax nor numpy, so low-level
emitters (data/folder.py, checkpoint/manager.py) can import it with no
dependency cost; the jax.monitoring bridge imports jax lazily.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterable, Optional, Tuple

# The typed vocabulary (ISSUE 3).  Publishing an unlisted kind is allowed
# (the bus is a transport, not a validator) but the canonical emitters
# stick to these; docs/observability.md is the schema reference.
EVENT_KINDS = ("step", "epoch", "eval", "drain", "checkpoint_commit",
               "rollback", "skip", "quarantine", "compile", "serve_batch",
               "serve_span", "slo", "admission", "trace", "goodput",
               "restart", "heartbeat", "memory", "flight_dump", "profile",
               # Replica-router tier (tpuic/serve/router.py,
               # docs/serving.md "Replica routing and failover"):
               # per-replica lifecycle/health transitions, circuit-breaker
               # state changes, budgeted retries, and in-flight failover.
               "router_replica", "router_breaker", "router_retry",
               "router_failover",
               # Model-lifecycle tier (docs/serving.md, "Model
               # lifecycle: hot-swap, canary, rollback"): one 'swap'
               # event per engine weight flip (generation, digest,
               # executable reuse vs prewarm), one 'rollout' event per
               # canary-rollout transition (start/stage/rollback/
               # promote/refused — tpuic/serve/rollout.py).
               "swap", "rollout",
               # Elastic data parallelism (runtime/gang.py elastic mode,
               # docs/parallelism.md): one 'reform' event per membership
               # transition the trainer acted on — a degrade restores
               # the fleet-agreed step in place (no process restart), a
               # rejoin is noted without a restore.
               "reform",
               # Bulk-scoring tier (tpuic/score/, docs/robustness.md
               # "Bulk scoring"): one 'score_plan' per worker life (the
               # shard table), one 'score_shard' per shard attempt
               # (score/rescore_corrupt/adopt), exactly one
               # 'score_commit' per committed shard fleet-wide (the
               # audited ledger row; recovered=true when appended by a
               # survivor for a dead winner), 'score_duplicate' when
               # the link-arbitrated commit deduped double work, and
               # one 'score_done' per worker life (totals + the
               # steady-compile counter).
               "score_plan", "score_shard", "score_commit",
               "score_duplicate", "score_done",
               # Span ledger (telemetry/spans.py, docs/observability.md
               # "Spans"): one 'span' event per closed span of host work
               # that is not a step — the import graph, the stages of
               # Trainer.__init__, the head and tail of every train_epoch.
               "span",
               # Compiled-program registry (tpuic/compiled/,
               # docs/performance.md "Compiled-program registry"): one
               # 'compile_cache' event per registry action — a miss that
               # compiled (action=compile), a manifest-driven prewarm
               # compile (action=prewarm), a generation retirement
               # (action=retire), and the trainer's prewarm summary
               # (action=prewarm_done).
               "compile_cache")


@dataclasses.dataclass(frozen=True)
class Event:
    kind: str
    time: float          # wall clock (time.time()) at publish
    data: Dict[str, object]


class EventBus:
    """Synchronous pub/sub.  Subscribers run inline in the publishing
    thread (ordering is therefore the emission order); anything slow or
    blocking belongs in the subscriber's own buffering, not here."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # Immutable snapshot: publish iterates without the lock.
        self._subs: Tuple[Tuple[Optional[frozenset], Callable], ...] = ()
        self.published = 0
        self.sink_errors = 0
        # The newest subscriber failure: {subscriber, error, kind}. Each
        # failing subscriber is also named once on stderr.
        self.last_sink_error: Optional[Dict[str, str]] = None
        self._failed_subs: set = set()
        # Fleet rank tag (telemetry/fleet.py): when set (a plain dict,
        # e.g. {"rank": 2, "ranks": 8}), every published event's data is
        # merged over it, so per-rank JSONL streams are attributable
        # offline. None (the single-process default) costs one attribute
        # read per publish.  Emitter-provided keys win on collision.
        self.rank_tag: Optional[Dict[str, object]] = None

    def subscribe(self, fn: Callable[[Event], None],
                  kinds: Optional[Iterable[str]] = None) -> Callable[[], None]:
        """Register ``fn`` for ``kinds`` (None = every kind); returns an
        idempotent unsubscribe callable."""
        entry = (None if kinds is None else frozenset(kinds), fn)
        with self._lock:
            self._subs = self._subs + (entry,)

        def unsubscribe() -> None:
            with self._lock:
                self._subs = tuple(e for e in self._subs if e is not entry)
        return unsubscribe

    def active(self, kind: Optional[str] = None) -> bool:
        """Whether anything would receive ``kind`` (None: any subscriber
        at all) — lets emitters skip building expensive event data."""
        subs = self._subs
        if kind is None:
            return bool(subs)
        return any(k is None or kind in k for k, _ in subs)

    def publish(self, kind: str, **data) -> Optional[Event]:
        subs = self._subs
        if not subs:
            return None
        tag = self.rank_tag
        if tag is not None:
            data = {**tag, **data}
        ev = Event(kind, time.time(), data)
        delivered = False
        for kinds, fn in subs:
            if kinds is not None and kind not in kinds:
                continue
            delivered = True
            try:
                fn(ev)
            except Exception as e:
                # A broken sink must never kill the train loop or the
                # serve batcher; the counter and the one stderr line per
                # subscriber make the breakage visible.
                self._sink_failed(fn, e, kind)
        if delivered:
            self.published += 1
        return ev

    def _sink_failed(self, fn: Callable, e: Exception, kind: str) -> None:
        self.sink_errors += 1
        named = fn if hasattr(fn, "__qualname__") else type(fn)
        who = f"{named.__module__}.{named.__qualname__}"
        error = f"{type(e).__name__}: {e}"
        self.last_sink_error = {"subscriber": who, "kind": kind,
                                "error": error}
        if who not in self._failed_subs:
            self._failed_subs.add(who)
            print(f"[telemetry] subscriber {who} raised on a {kind!r} "
                  f"event: {error} (its later failures are only counted, "
                  "in bus.sink_errors)", file=sys.stderr)

    def reset(self) -> None:
        """Drop every subscriber (test isolation — the process-global
        bus otherwise accumulates them across constructed Trainers)."""
        with self._lock:
            self._subs = ()
            self.published = 0
            self.sink_errors = 0
            self.last_sink_error = None
            self._failed_subs = set()
            self.rank_tag = None


def read_jsonl(path: str, on_torn: Optional[Callable[[str], None]] = None
               ) -> list:
    """Tolerant JSONL reader: parse every line of ``path`` that parses.

    THE shared reader for event streams written by :class:`JsonlSink`
    and friends (chaos soak, perf-regression gate, fleet aggregator —
    one implementation, one torn-line policy).  A SIGKILL can tear a
    line mid-write and the next attempt appends its first event onto
    the fragment; such lines are skipped (reported via ``on_torn`` when
    given) instead of crashing the verdict path.  A missing or
    unreadable file reads as an empty stream — absence is the caller's
    assertion to make, not an exception to catch.
    """
    out: list = []
    try:
        fh = open(path)
    except OSError:
        return out
    with fh:
        for ln in fh:
            ln = ln.strip()
            if not ln:
                continue
            try:
                out.append(json.loads(ln))
            except json.JSONDecodeError:
                if on_torn is not None:
                    on_torn(ln)
    return out


# -- sinks -------------------------------------------------------------------
class MemorySink:
    """Bounded in-memory event recorder (tests, REPL debugging)."""

    def __init__(self, maxlen: int = 4096) -> None:
        self.events: deque = deque(maxlen=maxlen)

    def __call__(self, ev: Event) -> None:
        self.events.append(ev)

    def kinds(self) -> list:
        return [e.kind for e in self.events]

    def of(self, kind: str) -> list:
        return [e for e in self.events if e.kind == kind]


class JsonlSink:
    """One JSON line per event: ``{"event": kind, "t": ..., **data}``.

    Durability ladder (the chaos soak used to tolerate torn tail lines a
    SIGKILLed run simply lost; this sink stops losing them up front):

    - ``flush_every`` bounds buffered lines (1 = flush each event — the
      default, so a killed process loses nothing; per-line flush of an
      already-buffered file is microseconds against millisecond steps).
    - ``flush_interval_s`` bounds buffered *time* when ``flush_every``
      is raised for very hot event streams: the first write after the
      interval elapses flushes everything buffered.  The bound holds
      while events keep flowing (the hot-stream case it exists for);
      a stream that stops emitting holds its tail until the next
      ``flush()``/``close()`` — which every drain path calls — because
      the sink deliberately has no background timer thread.
    - ``fsync=True`` additionally fsyncs at every flush — survives a
      machine (not just process) kill; off by default, it is a real
      per-event disk round trip.
    - ``close()`` flushes (and fsyncs, if configured) before closing, so
      a clean drain never leaves a torn tail; it is idempotent and
      write-after-close is a no-op.

    Thread-safe: serve-thread and loop-thread events interleave whole
    lines, never bytes.
    """

    def __init__(self, path: str, flush_every: int = 1,
                 flush_interval_s: float = 0.5,
                 fsync: bool = False) -> None:
        self.path = path
        self._fh = open(path, "a")
        self._lock = threading.Lock()
        self._since_flush = 0
        self._flush_every = max(1, int(flush_every))
        self._flush_interval = max(0.0, float(flush_interval_s))
        self._fsync = bool(fsync)
        self._last_flush = time.monotonic()

    def _flush_locked(self) -> None:
        self._fh.flush()
        if self._fsync:
            try:
                os.fsync(self._fh.fileno())
            except OSError:
                pass  # durability best-effort; never kill the loop
        self._since_flush = 0
        self._last_flush = time.monotonic()

    def __call__(self, ev: Event) -> None:
        rec = {"event": ev.kind, "t": round(ev.time, 6), **ev.data}
        line = json.dumps(rec, default=str) + "\n"
        with self._lock:
            if self._fh is None:
                return
            self._fh.write(line)
            self._since_flush += 1
            if (self._since_flush >= self._flush_every
                    or time.monotonic() - self._last_flush
                    >= self._flush_interval):
                self._flush_locked()

    def flush(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._flush_locked()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._flush_locked()
                finally:
                    self._fh.close()
                    self._fh = None


class TensorBoardSink:
    """Bus -> TensorBoard bridge: skip/rollback/quarantine counts,
    goodput fractions, supervisor restarts, serve batch/span latencies,
    device-memory gauges, and SLO attainment become scalars instead of
    being log-only.

    Wraps an existing ``tpuic.metrics.tensorboard.TensorBoardWriter``
    (the MetricLogger's); subscribes to ``step`` only to track the
    current global step so step-less events (quarantine fires in a
    producer thread) land at a sensible x-coordinate.  Serve events have
    no train step at all, so they ride their own monotonic counters.
    """

    def __init__(self, writer) -> None:
        self._tb = writer
        self._step = 0
        self._quarantined = 0
        self._rollbacks = 0
        self._serve_batches = 0
        self._serve_spans = 0

    def __call__(self, ev: Event) -> None:
        if self._tb is None:
            return
        d = ev.data
        if ev.kind == "step":
            self._step = int(d.get("step", self._step))
            return
        if ev.kind == "skip":
            self._tb.scalars(int(d.get("step", self._step)),
                             skip_streak=float(d.get("streak", 0)))
        elif ev.kind == "rollback":
            self._rollbacks += 1
            self._tb.scalars(self._step, rollbacks=float(self._rollbacks))
        elif ev.kind == "quarantine":
            # Accumulate per event rather than trusting the publisher's
            # 'count': that figure is dataset-local (train and val each
            # keep their own), so taking the last event's value would
            # regress the scalar whenever more than one dataset (or an
            # out-of-order producer thread) quarantines.
            self._quarantined += 1
            self._tb.scalars(self._step,
                             quarantined_total=float(self._quarantined))
        elif ev.kind == "goodput":
            scalars = {f"goodput_{k[5:]}": float(v) for k, v in d.items()
                       if k.startswith("frac_")}
            if "mfu" in d and d["mfu"] is not None:
                scalars["mfu"] = float(d["mfu"])
            if d.get("compute_dtype"):
                # Info-style scalar (constant 1, dtype in the tag): TB
                # has no string scalars, and runs compared side by side
                # need the precision arm visible.
                scalars[f"compute_dtype_{d['compute_dtype']}"] = 1.0
            if d.get("checkpoint_async_s") is not None:
                scalars["goodput_checkpoint_async_s"] = float(
                    d["checkpoint_async_s"])
            if scalars:
                self._tb.scalars(int(d.get("step", self._step)), **scalars)
        elif ev.kind == "restart":
            # Supervisor restart (runtime/supervisor.py): the count and
            # the downtime it cost, at the step the resumed run re-opened.
            self._tb.scalars(self._step,
                             restarts=float(d.get("restart", 0)),
                             restart_downtime_s=float(
                                 d.get("downtime_s", 0.0)))
        elif ev.kind == "serve_batch":
            self._serve_batches += 1
            self._tb.scalars(self._serve_batches,
                             serve_batch_latency_ms=float(
                                 d.get("latency_ms", 0.0)),
                             serve_batch_images=float(d.get("images", 0)),
                             serve_batch_bucket=float(d.get("bucket", 0)))
        elif ev.kind == "serve_span":
            # One point per request: end-to-end latency plus the two
            # spans that dominate tuning decisions (queue wait = load,
            # device = model cost); the full ledger stays in JSONL.
            self._serve_spans += 1
            self._tb.scalars(self._serve_spans,
                             serve_request_total_ms=float(
                                 d.get("total_ms", 0.0)),
                             serve_request_queue_ms=float(
                                 d.get("queue_ms", 0.0)),
                             serve_request_device_ms=float(
                                 d.get("device_ms", 0.0)))
        elif ev.kind == "memory":
            # Device-memory accounting (telemetry/memory.py): the
            # aggregate gauges become scalars; the per-device split
            # stays in JSONL/prom (a per-device TB curve per chip would
            # be noise on a pod).
            scalars = {}
            for field in ("bytes_in_use", "peak_bytes_in_use",
                          "process_rss_bytes"):
                if d.get(field) is not None:
                    scalars[f"memory_{field}"] = float(d[field])
            if d.get("headroom_frac") is not None:
                scalars["memory_headroom_frac"] = float(d["headroom_frac"])
            if scalars:
                self._tb.scalars(int(d.get("step", self._step)), **scalars)
        elif ev.kind == "slo":
            name = str(d.get("name", "slo"))
            scalars = {}
            for field in ("attainment", "burn_rate", "budget_remaining"):
                if d.get(field) is not None:
                    scalars[f"slo_{name}_{field}"] = float(d[field])
            if scalars:
                self._tb.scalars(int(d.get("step", self._step)), **scalars)
        elif ev.kind == "profile":
            # Device-time waterfall (telemetry/profile.py): per-op-class
            # device milliseconds as scalars; layer rollups and verdicts
            # stay in JSONL/prom (a per-layer TB curve per analysis
            # would be noise).
            scalars = {}
            for cls, c in (d.get("classes") or {}).items():
                if isinstance(c, dict) and c.get("ms") is not None:
                    scalars[f"device_time_ms_{cls}"] = float(c["ms"])
            if d.get("device_ms_per_step") is not None:
                scalars["device_ms_per_step"] = float(
                    d["device_ms_per_step"])
            if scalars:
                self._tb.scalars(self._step, **scalars)


# -- the process-global bus --------------------------------------------------
bus = EventBus()


def publish(kind: str, **data) -> Optional[Event]:
    return bus.publish(kind, **data)


def subscribe(fn: Callable[[Event], None],
              kinds: Optional[Iterable[str]] = None) -> Callable[[], None]:
    return bus.subscribe(fn, kinds)


# -- jax.monitoring bridge ---------------------------------------------------
_COMPILE_PREFIX = "/jax/core/compile/"
_monitor_lock = threading.Lock()
_monitor_installed = False


def install_jax_compile_listener() -> bool:
    """Bridge jax's compile-duration monitoring into ``compile`` events.

    jax 0.4.x reports each compilation as three sequential phase
    durations (jaxpr trace, MLIR lowering, backend compile) under
    ``/jax/core/compile/*``; the listener republishes each phase as a
    ``compile`` event (``key``, ``duration_s``), so the goodput tracker
    can subtract compile time from the step it stalled and tests can
    count ``backend_compile`` events as a compile counter.  Idempotent;
    returns False when jax.monitoring is unavailable.  The listener is
    process-wide and permanent (jax has no unregister), but an idle bus
    makes each callback a single falsy check.
    """
    global _monitor_installed
    with _monitor_lock:
        if _monitor_installed:
            return True
        try:
            from jax import monitoring as _jm
        except Exception:
            return False

        def _listener(key: str, duration: float, **kw) -> None:
            if key.startswith(_COMPILE_PREFIX):
                publish("compile", key=key[len(_COMPILE_PREFIX):],
                        duration_s=round(float(duration), 6))

        _jm.register_event_duration_secs_listener(_listener)
        _monitor_installed = True
        return True
