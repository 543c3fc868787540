"""What every mode shares: the run's context, the compile log, the profiled
slice, and the assembly of the result line.

A mode (``benchmark/modes/<mode>.py``) sets its system up, calls
``ctx.open_window()``, measures, and returns a :class:`ModeResult`; the
harness turns that into the one JSON line. Nothing here knows a cell, a
configuration or a traffic mix by name: they arrive as data.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import shutil
import time
from typing import Callable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CACHE_DIR = os.path.join(REPO, ".bench_cache")
BACKEND_COMPILE = "backend_compile_duration"


def load_spec(path: str = "BENCHMARK.json") -> dict:
    """The benchmark's definition; ``path`` (relative to the checkout) may
    name a file of the same layout that holds cells which are built and
    measured but not admitted (``benchmark/candidates/``)."""
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def _with_tiny(d: dict, tiny: bool) -> dict:
    out = {k: v for k, v in d.items() if k != "tiny"}
    if tiny:
        out.update(d.get("tiny", {}))
    return out


def resolve_cell(spec: dict, workload: str, tiny: bool = False) -> dict:
    """Cell name -> its entry, configuration, traffic mix and metrics, by
    the lookups a later PR extends with new files and entries only."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(REPO, entry["file"])) as f:
        config = _with_tiny(json.load(f), tiny)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = _with_tiny(json.load(f), tiny)

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def load_mode(name: str):
    return importlib.import_module(f"benchmark.modes.{name}")


def load_reader(metric: str) -> Callable:
    """The reader of one per-layer metric: ``layer_metrics/<metric>.py``,
    function ``read(obs) -> float | None``."""
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read


def load_reference(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")


class CompileLog:
    """Every backend compile of the process, with the time it ended.

    Subscribes to the program's ``compile`` events, which bridge
    ``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``.
    JAX records that duration around compile-or-load-from-the-persistent-
    cache, so a program met for the first time inside the window counts
    whether or not the disk had it."""

    def __init__(self) -> None:
        from tpuic.telemetry.events import (install_jax_compile_listener,
                                            subscribe)
        if not install_jax_compile_listener():
            raise RuntimeError("jax.monitoring is unavailable: compiles "
                               "cannot be counted")
        self.events: list = []     # (perf_counter at the end, seconds)
        self._unsubscribe = subscribe(self._on, kinds=("compile",))

    def _on(self, ev) -> None:
        if ev.data.get("key") == BACKEND_COMPILE:
            self.events.append((time.perf_counter(),
                                float(ev.data["duration_s"])))

    def seconds_before(self, t: float) -> float:
        return sum(d for at, d in self.events if at <= t)

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for at, _ in self.events if t0 < at <= t1)

    def close(self) -> None:
        self._unsubscribe()


@dataclasses.dataclass
class Observations:
    """What the per-layer readers read. A reader that finds nothing to
    read returns None and its metric is left out of the line."""

    step_events: list        # the program's `step` events of the window's
                             # un-profiled part: dicts with total_ms, ...
    engine_stats: dict       # ServeStats snapshot of the un-profiled part
    trace: Optional[dict]    # trace_reduce.reduce_trace(), None untraced
    spans: dict              # the benchmark's own spans and counters


@dataclasses.dataclass
class ModeResult:
    end_to_end: dict                 # metric name -> value (not setup_s)
    attempted: int
    failed: int
    problems: list                   # non-empty -> correct is false
    obs: Observations


class Context:
    """One run of one cell."""

    def __init__(self, resolved: dict, *, seed: int, seconds: float,
                 trace: bool, tiny: bool, t_start: float) -> None:
        import jax
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.tiny = bool(trace), bool(tiny)
        self.t_start = t_start
        self.chips = int(self.cell["chips"])
        self.devices = jax.devices()[:self.chips]
        self.cache_dir = CACHE_DIR
        self.work_dir = os.path.join(CACHE_DIR, "work", self.cell["name"])
        shutil.rmtree(self.work_dir, ignore_errors=True)
        os.makedirs(self.work_dir)
        self.compiles = CompileLog()
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None

    def say(self, msg: str) -> None:
        print(f"[bench +{time.perf_counter() - self.t_start:7.2f}s] {msg}",
              flush=True)

    def enable_compile_cache(self) -> str:
        """The program's one cache location, with every program kept: the
        program's own threshold (1 s) leaves its hundreds of small set-up
        programs out of the cache, and every run of every later check
        would compile them again. (Only where the program does not set the
        threshold again after this call: ``build_engine`` does.)"""
        import jax
        from tpuic.compiled.cache import enable_compile_cache
        where = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        return where

    def open_window(self) -> float:
        self.t_open = time.perf_counter()
        self.say(f"window opens: setup_s={self.t_open - self.t_start:.3f} "
                 f"compile_s={self.compiles.seconds_before(self.t_open):.3f}")
        return self.t_open

    def close_window(self) -> float:
        self.t_close = time.perf_counter()
        return self.t_close

    def base_spans(self) -> dict:
        """The spans and counters every mode reports."""
        self.say(f"memory_stats of device 0: {self.devices[0].memory_stats()}")
        return {
            "compile_s": self.compiles.seconds_before(self.t_open),
            "compiles_in_window": self.compiles.count_between(
                self.t_open, self.t_close),
            "memory_peak_bytes": memory_peak_bytes(self.devices),
        }


class ProfiledSlice:
    """``jax.profiler`` around a short slice of the window, reduced with
    the benchmark's own code. The Python tracer stays off: it slows the
    host code whose pace the slice is there to show."""

    def __init__(self, ctx: Context) -> None:
        self.ctx = ctx
        self.dir = os.path.join(ctx.work_dir, "trace")
        self.t_begin: Optional[float] = None   # before start_trace
        self.t_end: Optional[float] = None     # after stop_trace
        self.running = False

    def start(self) -> None:
        import jax
        self.t_begin = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # TraceAnnotations, little else
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()
        self.running = False
        self.t_end = time.perf_counter()
        self.ctx.say(f"profiled slice: {self.t_end - self.t_begin:.2f} s "
                     "of host time, start and stop included")

    def covers(self, t: float) -> bool:
        return (self.t_begin is not None and t >= self.t_begin
                and (self.t_end is None or t <= self.t_end))

    def reduce(self) -> Optional[dict]:
        from benchmark.trace_reduce import find_xplane, reduce_trace
        path = find_xplane(self.dir)
        if path is None:
            return None
        return reduce_trace(path)


def annotation(name: str):
    """A host span in the profiler's own trace (free when none runs)."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes held on the fullest of ``devices``: the allocator's
    ``peak_bytes_in_use`` plus ``peak_bytes_reserved``. On the TPU runtime
    the scratch memory of running programs (XLA's temp buffers, gigabytes
    for a train step) is a reservation outside the allocator's pool: a
    program whose ``memory_analysis()`` gives 1.5 GiB of temp moved
    ``bytes_reserved`` by exactly that and ``peak_bytes_in_use`` by nothing
    (chip probe, PR 23). Left out, a train step would seem to need 2 GiB."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def centred_error(got, want) -> float:
    """Largest over the rows of ``|| (got - mean) - (want - mean) || /
    || want - mean ||``: the error of logits (or log-probabilities) up to
    the per-row shift that a softmax ignores, relative to how far the
    reference's own values spread."""
    import numpy as np
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    g = g - g.mean(axis=-1, keepdims=True)
    w = w - w.mean(axis=-1, keepdims=True)
    return float(np.max(np.linalg.norm(g - w, axis=-1)
                        / np.maximum(np.linalg.norm(w, axis=-1), 1e-30)))


def result_line(ctx: Context, resolved: dict, res: ModeResult) -> dict:
    """The contract's one JSON object for this run."""
    dev0 = ctx.devices[0]
    import jax
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": res.obs.spans.get("memory_peak_bytes")}
    if ctx.tiny:
        device["rehearsal"] = "tiny sizes: a rehearsal, not a measurement"
    problems = list(res.problems)
    values = dict(res.end_to_end)
    values["setup_s"] = ctx.t_open - ctx.t_start
    units = {m["name"]: m["unit"]
             for m in resolved["end_to_end"] + resolved["per_layer"]}
    line: dict = {}
    if ctx.trace:
        trace = res.obs.trace
        if trace and trace["devices"]:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            dev = trace["devices"][min(trace["devices"])]
            line["breakdown"] = {"device_ops": dev["ops"],
                                 "idle_gaps": dev["idle_gaps"]}
        elif dev0.platform == "tpu":
            problems.append("the traced run found no device plane with "
                            "ops in its trace")
        ctx.say("traced run, end to end (not judged): " + json.dumps(values))
        values = {}
        for m in resolved["per_layer"]:
            v = load_reader(m["name"])(res.obs)
            if v is not None:
                values[m["name"]] = v
    else:
        missing = [m["name"] for m in resolved["end_to_end"]
                   if m["name"] not in values]
        if missing:
            problems.append(f"end-to-end metrics not measured: {missing}")
    for name, v in values.items():
        if not isinstance(v, (int, float)) or not math.isfinite(v):
            problems.append(f"metric {name} is {v!r}")
    for p in problems:
        ctx.say(f"NOT CORRECT: {p}")
    return {"correct": not problems, "attempted": int(res.attempted),
            "failed": int(res.failed),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items() if k in units},
            "device": device, **line}


def plain_variables(variables):
    """The program's variables as a tree of plain arrays on the host: flax
    wraps kernels that carry sharding names in a box, which the model's
    ``apply`` opens itself and a plain reference cannot."""
    import jax
    from flax.core import meta
    return jax.device_get(meta.unbox(variables))
