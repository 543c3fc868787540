"""From a profiler trace (``.xplane.pb``) to the few numbers the device
metrics are made of.

What a TPU trace written by ``jax.profiler`` holds (read by hand from the
recorded chip trace under ``fixtures/``): one plane per chip named
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per execution
of a jitted program, named ``jit_<fn>(<fingerprint>)``), a line ``XLA Ops``
(one event per HLO op on the TensorCore, named by the op's HLO text,
``%fusion.12 = bf16[...] fusion(...)``; ops on this line never overlap) and
a line ``Async XLA Ops`` (start-to-done spans of asynchronous copies and
collectives, which overlap the ops line). Host threads are lines of the
``/host:CPU`` plane; ``jax.profiler.TraceAnnotation`` spans appear there
under their own names, on the same clock.

The measured window on a device runs from the start of one execution of
the dominant program (the one with the most device time: the train step,
a bucket's forward) to the start of its last execution, after skipping
the first few, which the profiler's own start-up stalls: whole periods, so
the idle time between executions is inside it. Busy time is the union of
the op intervals in that window, never their sum and never a host clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional, Sequence

from benchmark.stats import merge, subtract, union_length

COLLECTIVE_TAGS = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
ANNOTATION_PREFIX = "bench."


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def is_collective(name: str) -> bool:
    return any(tag in name for tag in COLLECTIVE_TAGS)


def _clip(intervals: Sequence[tuple], lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_device(modules: Sequence[tuple], ops: Sequence[tuple],
                  async_ops: Sequence[tuple] = (), *, skip_first: int = 2,
                  annotations: Sequence[tuple] = ()) -> Optional[dict]:
    """Reduce one device's events; every event is ``(name, start_s,
    duration_s)``. Returns None when no op ran."""
    ops = sorted(ops, key=lambda e: e[1])
    if not ops:
        return None
    per_module: dict = {}
    for name, _, dur in modules:
        per_module[name] = per_module.get(name, 0.0) + dur
    dominant = max(per_module, key=per_module.get) if per_module else None
    starts = sorted(s for name, s, _ in modules if name == dominant)
    if len(starts) - skip_first >= 2:
        starts = starts[skip_first:]
    if len(starts) >= 2:
        lo, hi, steps = starts[0], starts[-1], len(starts) - 1
    else:       # no two executions to span: the extent of the ops
        lo, hi = ops[0][1], max(s + d for _, s, d in ops)
        steps = len(starts)
    op_iv = [(op_name(n), s, s + d) for n, s, d in ops]
    busy = _clip([(s, e) for _, s, e in op_iv], lo, hi)
    coll = [(s, e) for n, s, e in op_iv if is_collective(n)]
    coll += [(s, s + d) for n, s, d in async_ops
             if is_collective(op_name(n))]
    coll = _clip(coll, lo, hi)
    compute = _clip([(s, e) for n, s, e in op_iv if not is_collective(n)],
                    lo, hi)
    per_op: dict = {}
    for n, s, e in op_iv:
        for cs, ce in _clip([(s, e)], lo, hi):
            per_op[n] = per_op.get(n, 0.0) + (ce - cs)
    gaps = subtract([(lo, hi)], busy)
    by_label: dict = {}
    for s, e in gaps:
        label = _label((s + e) / 2.0, annotations)
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]
    return {
        "module": dominant, "steps": steps,
        "window_s": hi - lo, "busy_s": union_length(busy),
        "collective_s": union_length(coll),
        "collective_exposed_s": union_length(subtract(coll, compute)),
        "ops": [[n, t] for n, t in top(per_op)],
        "idle_gaps": [[n, t] for n, t in top(by_label)],
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0),
    }


def _label(t: float, annotations: Sequence[tuple]) -> str:
    """The innermost (shortest) benchmark annotation covering time ``t``."""
    best = None
    for name, s, d in annotations:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host:unannotated"


def read_xplane(path: str) -> dict:
    """``{"devices": {index: {"modules", "ops", "async_ops"}},
    "annotations": [...]}`` with every event as ``(name, start_s,
    duration_s)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    annotations: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = {
                key: [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in lines[line].events] if line in lines else []
                for key, line in (("modules", "XLA Modules"),
                                  ("ops", "XLA Ops"),
                                  ("async_ops", "Async XLA Ops"))}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                annotations += [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in ln.events
                    if e.name.startswith(ANNOTATION_PREFIX)]
    return {"devices": devices, "annotations": annotations}


def reduce_trace(path: str, *, skip_first: int = 2) -> dict:
    """``{"devices": {index: reduce_device(...)}, "busy_s", "window_s"}``:
    per-device reductions, and busy and window seconds averaged over the
    devices that ran anything (what the result line's ``device`` carries).
    ``devices`` is empty for a trace with no TPU plane (a CPU rehearsal)."""
    raw = read_xplane(path)
    devices = {}
    for idx, ev in sorted(raw["devices"].items()):
        red = reduce_device(ev["modules"], ev["ops"], ev["async_ops"],
                            skip_first=skip_first,
                            annotations=raw["annotations"])
        if red is not None:
            devices[idx] = red
    n = max(1, len(devices))
    return {"devices": devices,
            "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
            "window_s": sum(d["window_s"] for d in devices.values()) / n}


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir`` (the profiler writes
    ``plugins/profile/<time>/<host>.xplane.pb``)."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None
