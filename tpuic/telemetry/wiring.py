"""TrainTelemetry — one training run's telemetry wiring.

Moved out of ``tpuic/telemetry/__init__.py`` so the package import
stays dependency-free (PEP 562 lazy exports, the tpuic/__init__.py
idiom): stdlib-only parents — the supervisor, the gang supervisor, and
the replica router (``tpuic/serve/router.py``) — import
``tpuic.telemetry.events`` / ``tpuic.telemetry.prom`` without pulling
jax into a process that must outlive any backend wedge.
"""

from __future__ import annotations

import os
from typing import Optional

from tpuic.telemetry.events import (JsonlSink, TensorBoardSink, bus,
                                    install_jax_compile_listener, publish)
from tpuic.telemetry.goodput import (GoodputTracker, analytic_flops_per_step,
                                     hbm_bandwidth, peak_flops)
from tpuic.telemetry.memory import MemorySampler
from tpuic.telemetry.slo import SLOTracker, parse_objectives
from tpuic.telemetry.spans import replay as replay_spans
from tpuic.telemetry.steptime import StepTimer
from tpuic.telemetry.tracing import TraceTrigger


class TrainTelemetry:
    """One training run's telemetry wiring over the process-global bus.

    Owns the per-run subscribers (JSONL sink, step timer, goodput
    tracker, trace trigger, TensorBoard bridge); the emitters
    (checkpoint manager, dataset quarantine, jax compile listener)
    publish to the global bus without knowing any of this exists.

    Exactly one instance is live per process: constructing a new one
    closes the previous run's subscribers first, so a sweep driver (or
    a test session) building Trainer after Trainer never leaks bus
    subscriptions or appends run B's events into run A's JSONL file.
    """

    def __init__(self, run_cfg, *, model_name: str = "", image_size: int = 0,
                 global_batch: int = 0, n_devices: int = 1, device=None,
                 tb=None, compute_dtype: str = "") -> None:
        global _active
        if _active is not None:
            _active.close()
        _active = self
        self._sinks = []
        self._unsubs = []
        # Compile events (the jax.monitoring bridge) feed the goodput
        # compile bucket; idempotent, process-wide.
        install_jax_compile_listener()
        # Fleet view (telemetry/fleet.py, docs/observability.md): on a
        # multi-process run every event gains rank/ranks fields (one
        # dict merge at publish; single-process runs keep the tag off
        # and pay one attribute read).
        from tpuic.telemetry.fleet import rank_stream_path, tag_bus_with_rank
        self.rank, self.ranks = tag_bus_with_rank(bus)
        jsonl = getattr(run_cfg, "metrics_jsonl", "") or ""
        if jsonl:
            # Per-rank streams: rank 0 keeps the configured path (the
            # single-process contract every consumer was built on);
            # rank k writes '<stem>.rank<k>.jsonl' beside it — on a
            # shared filesystem the fleet's whole history lands in one
            # directory with no cross-process appends, and
            # 'python -m tpuic.telemetry.fleet <dir>' merges it into
            # straggler attribution offline.
            sink = JsonlSink(rank_stream_path(jsonl, self.rank))
            self._sinks.append(sink)
            self._unsubs.append(bus.subscribe(sink))
            # The stream starts with what ran before this sink existed:
            # the import and trainer.* spans (telemetry/spans.py). Only
            # this sink gets them; subscribers that were already there
            # (the flight recorder) saw each span as it closed.
            replay_spans(sink)
        # Supervised-liveness heartbeat (runtime/supervisor.py,
        # docs/robustness.md): when a supervisor parent set
        # TPUIC_HEARTBEAT_FILE for this process, mirror bus activity into
        # the atomically rewritten heartbeat file. Pure host-side
        # piggybacking on events the loop already publishes through its
        # deferred drain — zero device syncs, zero compiles added
        # (asserted in tests/test_supervisor.py with the
        # tpuic.analysis.runtime checkers).
        from tpuic.runtime.supervisor import HeartbeatWriter
        self.heartbeat = HeartbeatWriter.from_env(publish=publish)
        if self.heartbeat is not None:
            self._unsubs.append(bus.subscribe(self.heartbeat))
        self.steptime = StepTimer(bus)
        # Device-memory accounting (telemetry/memory.py): one host-side
        # metadata sample per step boundary — allocator counters where
        # the backend provides them, live-array bytes + RSS on CPU.
        # Zero device syncs, zero compiles (checker-asserted in
        # tests/test_fleet.py, the same discipline as the StepTimer).
        from tpuic.metrics.logging import host0_print
        self.memory = MemorySampler(publish=bus.publish, log=host0_print)
        self._unsubs.append(bus.subscribe(self.memory.on_event,
                                          kinds=("step",)))
        flops = analytic_flops_per_step(model_name, image_size, global_batch)
        # Dtype-aware roofline: an f32 run is judged against the f32 peak
        # (half the bf16 MXU rate on TPU), so MFU compares honestly
        # across --compute-dtype arms instead of flattering bf16 by 2x.
        peak = peak_flops(device, compute_dtype or "bf16") * max(
            1, int(n_devices))
        self.goodput = GoodputTracker(flops_per_step=flops, peak_flops=peak,
                                      global_batch=global_batch,
                                      compute_dtype=compute_dtype)
        self._unsubs.append(bus.subscribe(self.goodput.on_event))
        # Step-time SLOs (telemetry/slo.py): attainment + error-budget
        # burn over the 'step' events the StepTimer already publishes —
        # one more host-side subscriber, nothing new on the hot path.
        self.slo: Optional[SLOTracker] = None
        slo_specs = getattr(run_cfg, "slo", "") or ""
        if slo_specs:
            self.slo = SLOTracker(parse_objectives(
                slo_specs, allowed=("train_step",)))
            self._unsubs.append(self.slo.attach(bus))
        # Device-time attribution (telemetry/profile.py,
        # docs/observability.md "Device-time attribution"): with
        # run.trace_analyze set, captured trace windows are auto-analyzed
        # into a per-op-class waterfall ('profile' events) and a final
        # analysis runs at flush().  The Trainer wires the HLO provider
        # (the AOT-lowered train step) after construction; until then
        # the analyzer still ingests step device_ms — one deque append
        # per step, zero syncs, zero compiles (test-asserted on-vs-off).
        self.profile = None
        if getattr(run_cfg, "trace_analyze", False):
            # Imported lazily so `python -m tpuic.telemetry.profile`
            # does not re-import its own module through this package.
            from tpuic.telemetry.profile import CaptureAnalyzer
            # PER-DEVICE peak/bandwidth, NOT x n_devices: the analyzed
            # HLO is the SPMD-partitioned per-device program and the
            # measured step time is the wall clock of its parallel
            # execution — one device's roofline is the right ruler.
            self.profile = CaptureAnalyzer(
                peak=peak_flops(device),
                hbm_bytes_per_s=hbm_bandwidth(device),
                model_name=model_name, image_size=image_size,
                global_batch=global_batch,
                n_devices=max(1, int(n_devices)))
            # 'trace' too: steps measured inside a profiler window are
            # excluded from the waterfall's device distribution (the
            # analyzer's observer-effect taint).  Subscribed BEFORE the
            # tracer below, so the window-open/close ordering it sees is
            # exact.
            self._unsubs.append(bus.subscribe(self.profile.on_event,
                                              kinds=("step", "trace")))
        trace_dir = os.environ.get("TPUIC_TRACE", "") or \
            getattr(run_cfg, "trace_dir", "") or ""
        self.tracer: Optional[TraceTrigger] = None
        if trace_dir:
            self.tracer = TraceTrigger(
                trace_dir,
                threshold=float(getattr(run_cfg, "trace_threshold", 3.0)),
                trace_steps=int(getattr(run_cfg, "trace_steps", 3)),
                keep=int(getattr(run_cfg, "trace_keep", 4)),
                # TPUIC_TRACE=dir is the manual override: capture one
                # window immediately instead of waiting for a regression.
                force_first=bool(os.environ.get("TPUIC_TRACE")),
                on_capture=(self.profile.on_capture
                            if self.profile is not None else None))
            self._unsubs.append(bus.subscribe(self.tracer.on_event,
                                              kinds=("step",)))
        if tb is not None:
            tbs = TensorBoardSink(tb)
            # serve_batch/serve_span included: a train process never
            # publishes them, but a process embedding both a Trainer and
            # an InferenceEngine (predict-after-fit notebooks) gets its
            # serve latencies as scalars through the same sink.
            self._unsubs.append(bus.subscribe(
                tbs, kinds=("step", "skip", "rollback", "quarantine",
                            "goodput", "restart", "slo", "memory",
                            "serve_batch", "serve_span", "profile")))

    def flush(self) -> None:
        if self.profile is not None:
            # Run-end device-time analysis over the full step window
            # (final=True) BEFORE the sinks flush, so the event lands in
            # this run's JSONL.  The analyzer contains its own failures.
            self.profile.finalize()
        for s in self._sinks:
            s.flush()

    def close(self) -> None:
        """Unsubscribe this run's consumers and close its sinks (the
        global bus and emitters keep running for the process).
        Idempotent."""
        global _active
        for unsub in self._unsubs:
            unsub()
        self._unsubs = []
        for s in self._sinks:
            s.close()
        self._sinks = []
        if _active is self:
            _active = None


_active: Optional[TrainTelemetry] = None
