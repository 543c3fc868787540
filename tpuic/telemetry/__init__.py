"""tpuic.telemetry — unified observability subsystem.

The reference repo's only observability was an ``AverageMeter`` printed
per epoch; this reproduction grew a trainer, a serving engine, and a
fault-tolerance layer that each invented their own measurement (deferred
log drain, ServeStats, bench-script MFU math).  This package makes the
measurement a first-class subsystem — the layer every perf PR cites for
before/after evidence (docs/observability.md):

- ``events``   — structured publish/subscribe **event bus** with JSONL /
  in-memory / TensorBoard sinks.  train/loop.py, checkpoint/manager.py,
  data/folder.py, serve/engine.py, and the replica router
  (serve/router.py) emit typed events (``step``, ``epoch``, ``eval``,
  ``checkpoint_commit``, ``rollback``, ``skip``, ``quarantine``,
  ``compile``, ``serve_batch``, ``trace``, ``goodput``,
  ``router_*``) into it instead of ad-hoc log lines.
- ``steptime`` — per-step wall-clock **breakdown** (data-wait vs.
  dispatch vs. device) from dispatch timestamps + the existing deferred
  drain: zero new host syncs, zero new compiles (asserted in
  tests/test_telemetry.py, the PR-2 discipline).
- ``goodput``  — per-model analytic FLOPs (bench.py's math, now owned
  here and imported back by bench.py), running MFU, and a goodput
  report classifying wall time into productive / compile / checkpoint /
  skip / rollback / input-bound / eval buckets.
- ``tracing``  — triggered ``jax.profiler`` windows: arms automatically
  when step time regresses past a multiple of the rolling median (or
  via ``TPUIC_TRACE=dir``), writing to a bounded trace dir.
- ``prom``     — Prometheus-style text exposition of serve, train, and
  router counters (``--prom-dump/--prom-port``).
- ``memory``   — per-device **memory accounting** sampled at step
  boundaries (allocator counters where the backend provides them,
  live-array bytes + RSS on CPU): ``memory`` events, TensorBoard
  scalars, ``device_memory_bytes{device,kind}`` prom rows, one-shot
  low-headroom warning.
- ``flight``   — **crash flight recorder**: a bounded ring of the last
  N events, dumped as ``flightdump-<attempt>.jsonl`` on SIGQUIT /
  fatal exit alongside the supervisor's stack dumps.
- ``fleet``    — **per-rank fleet view**: rank-tagged events, per-rank
  JSONL streams, and the offline straggler-attribution aggregator
  (``python -m tpuic.telemetry.fleet <dir>``).
- ``spans``    — **span ledger** for host work that is not a step: the
  import graph, the stages of ``Trainer.__init__``, the head and tail of
  every ``train_epoch``; on ``perf_counter`` and, through
  ``TraceAnnotation``, on the profiler's clock (stdlib-only).
- ``wiring``   — ``TrainTelemetry``, one training run's subscriber set.

Everything is host-side: no module here ever calls ``jax.device_get``
or adds device work (test-asserted), so telemetry can stay on in
production hot loops.

Re-exports resolve lazily (PEP 562, the tpuic/__init__.py idiom) so
that importing this package — which stdlib-only parents do transitively
via ``tpuic.telemetry.events`` and ``tpuic.telemetry.prom`` — never
pulls jax/numpy into a supervisor or router process that must outlive
any backend wedge (the same rule runtime/supervisor.py documents).
"""

from __future__ import annotations

_LAZY = {
    # events (stdlib-only module — the cheap common case)
    "Event": ("tpuic.telemetry.events", "Event"),
    "EventBus": ("tpuic.telemetry.events", "EventBus"),
    "JsonlSink": ("tpuic.telemetry.events", "JsonlSink"),
    "MemorySink": ("tpuic.telemetry.events", "MemorySink"),
    "TensorBoardSink": ("tpuic.telemetry.events", "TensorBoardSink"),
    "bus": ("tpuic.telemetry.events", "bus"),
    "install_jax_compile_listener": ("tpuic.telemetry.events",
                                     "install_jax_compile_listener"),
    "publish": ("tpuic.telemetry.events", "publish"),
    "read_jsonl": ("tpuic.telemetry.events", "read_jsonl"),
    "subscribe": ("tpuic.telemetry.events", "subscribe"),
    # flight recorder
    "FlightRecorder": ("tpuic.telemetry.flight", "FlightRecorder"),
    "install_flight_recorder": ("tpuic.telemetry.flight",
                                "install_flight_recorder"),
    # goodput / roofline
    "GoodputTracker": ("tpuic.telemetry.goodput", "GoodputTracker"),
    "HBM_GBPS": ("tpuic.telemetry.goodput", "HBM_GBPS"),
    "PEAK_FLOPS": ("tpuic.telemetry.goodput", "PEAK_FLOPS"),
    "analytic_flops_per_step": ("tpuic.telemetry.goodput",
                                "analytic_flops_per_step"),
    "hbm_bandwidth": ("tpuic.telemetry.goodput", "hbm_bandwidth"),
    "peak_flops": ("tpuic.telemetry.goodput", "peak_flops"),
    "roofline_intensity": ("tpuic.telemetry.goodput",
                           "roofline_intensity"),
    # memory / slo / steptime / tracing
    "MemorySampler": ("tpuic.telemetry.memory", "MemorySampler"),
    "Objective": ("tpuic.telemetry.slo", "Objective"),
    "SLOTracker": ("tpuic.telemetry.slo", "SLOTracker"),
    "parse_objectives": ("tpuic.telemetry.slo", "parse_objectives"),
    "StepTimer": ("tpuic.telemetry.steptime", "StepTimer"),
    "TraceTrigger": ("tpuic.telemetry.tracing", "TraceTrigger"),
    # per-run wiring
    "TrainTelemetry": ("tpuic.telemetry.wiring", "TrainTelemetry"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        module, attr = _LAZY[name]
        value = getattr(importlib.import_module(module), attr)
        globals()[name] = value  # cache: next access skips the import
        return value
    raise AttributeError(
        f"module 'tpuic.telemetry' has no attribute '{name}'")


def __dir__():
    return sorted(set(list(globals()) + list(_LAZY)))
