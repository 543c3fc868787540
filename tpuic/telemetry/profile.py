"""Device-time attribution: per-op-class waterfall + roofline verdicts.

The telemetry layer attributes every *host*-side millisecond (goodput
buckets, step breakdown, fleet skew) — but ``device_ms``, the dominant
bucket at MFU 0.31 (builder, 2026-08-01), stayed an opaque residual.  The
"MFU 0.31 → 0.5+" roadmap item cannot be earned without knowing which
ops are compute-bound vs HBM-bound; the 15-minute-ImageNet line
(arXiv 1711.04325) and every TPU scaling paper start from exactly this
per-op accounting.  This module is that accounting:

- **Trace analyzer** (:func:`parse_trace`): parses captured
  ``jax.profiler`` artifacts (the Chrome-trace ``*.trace.json[.gz]``
  every capture writes) into per-op-class device time — matmul/conv vs
  elementwise vs reduce vs copy/transpose vs collective — with a
  per-layer rollup from the ``jax.named_scope``/flax module paths in
  each op's metadata.  Device-side per-op events exist on TPU/GPU
  captures; a CPU capture carries none, and the analyzer says so
  (returns None) instead of fabricating a waterfall.
- **HLO cost model** (:func:`hlo_waterfall`): where the runtime exposes
  it, the already-AOT-lowered executables (train/step.py warmup,
  serve/engine.py buckets) yield ``compiled.as_text()`` +
  ``compiled.cost_analysis()``; the model classifies every entry-
  computation instruction, charges it HBM bytes from its operand/output
  shapes (a fusion's *boundary* bytes — interior traffic never reaches
  HBM, which is the point of fusing) and FLOPs apportioned from the
  compiler's total, and models its time as
  ``max(flops/peak, bytes/bandwidth)`` — the roofline.  Works on every
  backend, CPU CI included.
- **Attribution** (:func:`attribute_device_time`): the modeled class
  times are mapped onto the *measured* telemetry device bucket — the
  best (minimum) observed step is the program-time anchor, the
  mean-over-best excess books to the ``overhead`` class as ``stall_ms``
  (host time the step breakdown charges to the device residual: drains,
  injected sleeps, contention).  By construction the per-class times sum
  to the measured mean device bucket — the same "buckets sum to wall"
  invariant the goodput ledger carries, one level down.
- **Verdicts**: every class carries a roofline verdict
  (compute-bound / hbm-bound / overhead) from the shared
  ``goodput.roofline_intensity`` formula against the PEAK_FLOPS +
  HBM_GBPS tables.

Wiring (docs/observability.md, "Device-time attribution"):
``CaptureAnalyzer`` subscribes to ``step`` events, runs on every
triggered-trace capture (``TraceTrigger(on_capture=...)``) and once at
fit() end, and publishes a ``profile`` event (JSONL / TensorBoard /
``device_time_ms{op_class}`` prom rows on both expositions).  The
committed ``perf/roofline_baseline.json`` extends the PR-6 regression
gate: a silent shift of device time into copy/overhead fails CI the
same way a latency regression does::

    python -m tpuic.telemetry.profile --trace traces/trace-0000-...
    python -m tpuic.telemetry.profile --step-waterfall --model resnet50
    python -m tpuic.telemetry.profile --check          # CI roofline gate
    python -m tpuic.telemetry.profile --check --inject slow_step \
        --expect-fail                                  # prove it fires
    python -m tpuic.telemetry.profile --write-baseline

Analysis is strictly off the hot path: the analyzer runs in the capture
/ finalize hooks, never per step, and a failure publishes an error
field instead of killing the run (the tracing.py discipline).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import statistics
import sys
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from tpuic.telemetry.goodput import (check_flops_drift, hbm_bandwidth,
                                     peak_flops, ridge_intensity,
                                     roofline_intensity, roofline_verdict)

# The op-class vocabulary.  'overhead' additionally absorbs the measured
# stall (mean-over-best device time) during attribution.
OP_CLASSES = ("matmul", "elementwise", "reduce", "copy", "collective",
              "overhead")

_MATMUL_OPS = frozenset({
    "dot", "convolution", "custom-call", "cholesky", "triangular-solve",
    "fft"})
_REDUCE_OPS = frozenset({
    "reduce", "reduce-window", "select-and-scatter", "sort", "topk",
    "reduce-precision"})
_COPY_OPS = frozenset({
    "copy", "copy-start", "copy-done", "transpose", "reshape", "bitcast",
    "concatenate", "slice", "dynamic-slice", "dynamic-update-slice",
    "gather", "scatter", "pad", "reverse", "broadcast"})
_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-reduce-start", "all-reduce-done", "all-gather",
    "all-gather-start", "all-gather-done", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "collective-broadcast", "send", "recv",
    "send-done", "recv-done"})
_OVERHEAD_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "after-all",
    "add-dependency", "opt-barrier", "partition-id", "replica-id",
    "infeed", "outfeed", "call", "conditional", "while", "domain"})

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}


def classify_op(opcode: str, category: Optional[str] = None) -> str:
    """HLO opcode (``fusion.3`` → ``fusion``) or profiler ``hlo_category``
    hint → op class.  The category hint (TPU traces label fusions e.g.
    'convolution fusion' / 'loop fusion') wins when present, because a
    trace event's bare name carries no called-computation to look into."""
    if category:
        c = category.lower()
        if any(k in c for k in ("conv", "dot", "gemm", "matmul", "einsum")):
            return "matmul"
        if "reduc" in c or "scan" in c or "sort" in c:
            return "reduce"
        if any(k in c for k in ("copy", "transpose", "reshape", "memcpy",
                                "data formatting")):
            return "copy"
        if any(k in c for k in ("all-", "all_", "collective", "permute",
                                "send", "recv")):
            return "collective"
        if "fusion" in c or "elementwise" in c or "loop" in c:
            return "elementwise"
    base = opcode.lstrip("%").split(".")[0].strip().lower()
    if base in _MATMUL_OPS:
        return "matmul"
    if base in _REDUCE_OPS:
        return "reduce"
    if base in _COPY_OPS:
        return "copy"
    if base in _COLLECTIVE_OPS:
        return "collective"
    if base in _OVERHEAD_OPS:
        return "overhead"
    return "elementwise"


def classify_fusion(called_opcodes: Sequence[str]) -> str:
    """A fusion is classified by the strongest op it contains: any
    dot/conv makes it matmul-class, else any reduce makes it
    reduce-class, else it is the elementwise/copy loop it lowered from
    (majority of movement ops → copy)."""
    bases = [o.lstrip("%").split(".")[0].lower() for o in called_opcodes]
    if any(b in _MATMUL_OPS for b in bases):
        return "matmul"
    if any(b in _REDUCE_OPS for b in bases):
        return "reduce"
    real = [b for b in bases if b not in _OVERHEAD_OPS]
    if real and sum(b in _COPY_OPS for b in real) > len(real) / 2:
        return "copy"
    return "elementwise"


# -- scope / layer attribution ------------------------------------------------
# Two wrapper families in jax scope paths: staging wrappers whose
# payload is a FUNCTION name (``jit(train_step)``, ``jit(main)``) —
# dropped whole, the payload is not a layer — and autodiff/remat
# wrappers whose payload is the scope the op belongs to
# (``transpose(jvp(Classifier))``) — unwrapped, so forward and backward
# ops of the same layer land in the same bucket (the backward's extra
# time is part of that layer's cost).
_DROP_WRAPPERS = re.compile(r"^(jit|pjit|xla_call|vmap|pmap|shard_map|"
                            r"while|body|cond)\b")
_UNWRAP_WRAPPERS = re.compile(r"^(transpose|jvp|vjp|remat|checkpoint|"
                              r"rematted_computation|custom_jvp|"
                              r"custom_vjp|named)\b")


def scope_segments(op_name: str) -> List[str]:
    """Meaningful scope segments of an HLO metadata ``op_name`` (or a
    trace event's long name); see the wrapper-family note above."""
    out: List[str] = []
    for seg in str(op_name).split("/"):
        seg = seg.strip()
        if not seg:
            continue
        while True:
            m = re.match(r"^([\w\-.]+)\((.*)\)$", seg)
            if m is None:
                break
            if _DROP_WRAPPERS.match(m.group(1)):
                seg = ""
                break
            if _UNWRAP_WRAPPERS.match(m.group(1)):
                seg = m.group(2)
            else:
                break
        if not seg or _DROP_WRAPPERS.match(seg) \
                or _UNWRAP_WRAPPERS.match(seg):
            continue
        out.append(seg)
    return out


def layer_of(op_name: str, depth: int = 3) -> str:
    """Rollup key of an op's scope path: the first ``depth`` meaningful
    segments minus the trailing primitive name — e.g.
    ``jit(train_step)/Classifier/backbone/layer2_0/conv2/conv`` →
    ``Classifier/backbone/layer2_0`` at depth 3.  Unattributed ops roll
    up under ``(unattributed)``."""
    segs = scope_segments(op_name)
    if len(segs) > 1:
        segs = segs[:-1]  # drop the primitive leaf
    segs = segs[:max(1, depth)]
    return "/".join(segs) if segs else "(unattributed)"


# -- chrome-trace parsing (real captures) -------------------------------------
def _trace_files(path: str) -> List[str]:
    """Trace JSON files of a capture: accepts the session dir a
    TraceTrigger wrote (``trace-NNNN-<ts>/``), the ``plugins`` parent, or
    a direct ``*.trace.json[.gz]`` file."""
    if os.path.isfile(path):
        return [path]
    pats = (os.path.join(path, "plugins", "profile", "*", "*.trace.json*"),
            os.path.join(path, "*", "*.trace.json*"),
            os.path.join(path, "*.trace.json*"))
    for pat in pats:
        hits = sorted(glob.glob(pat))
        if hits:
            return hits
    return []


def _load_trace_events(path: str) -> List[dict]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    if isinstance(data, dict):
        return list(data.get("traceEvents") or ())
    return list(data) if isinstance(data, list) else []


def parse_trace(path: str, layer_depth: int = 3) -> Optional[dict]:
    """Per-op-class device time from a jax.profiler capture.

    Selects processes whose ``process_name`` names a device (contains
    ``/device:`` — the TPU/GPU op-timeline convention; the ``/host:CPU``
    python/runtime timelines are never device time) and sums complete
    ('X') event durations per op class and per layer.  Returns None when
    the capture carries **no device op events at all** — a CPU capture —
    so callers fall back to the HLO cost model instead of reading an
    empty waterfall as "zero device time"."""
    files = _trace_files(path)
    if not files:
        return None
    classes: Dict[str, float] = {}
    layers: Dict[str, float] = {}
    n_ops = 0
    for f in files:
        try:
            events = _load_trace_events(f)
        except (OSError, ValueError):
            continue
        device_pids = set()
        for e in events:
            if (e.get("ph") == "M" and e.get("name") == "process_name"
                    and "/device:" in str(
                        (e.get("args") or {}).get("name", ""))):
                device_pids.add(e.get("pid"))
        if not device_pids:
            continue
        for e in events:
            if e.get("ph") != "X" or e.get("pid") not in device_pids:
                continue
            dur_us = float(e.get("dur", 0.0))
            if dur_us <= 0:
                continue
            args = e.get("args") or {}
            cls = classify_op(str(e.get("name", "")),
                              category=args.get("hlo_category"))
            classes[cls] = classes.get(cls, 0.0) + dur_us / 1000.0
            n_ops += 1
            scope = next((str(v) for k in ("long_name", "tf_op", "op_name",
                                           "name")
                          if "/" in str(args.get(k, ""))
                          for v in (args[k],)), None)
            if scope:
                key = layer_of(scope, depth=layer_depth)
                layers[key] = layers.get(key, 0.0) + dur_us / 1000.0
    if not classes:
        return None
    total = sum(classes.values())
    return {"source": "trace", "device_ms_total": round(total, 3),
            "ops": n_ops,
            "classes": {k: round(v, 3) for k, v in sorted(classes.items())},
            "layers": {k: round(v, 3) for k, v in sorted(
                layers.items(), key=lambda kv: -kv[1])}}


# -- HLO text cost model ------------------------------------------------------
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*?\)|[\w\[\]{},]+)\s+"
    r"([\w\-]+)\(")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')


def _shape_stats(text: str) -> Tuple[float, float]:
    """(bytes, elems) summed over every shape literal in ``text``."""
    total_b = total_e = 0.0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1.0
        for d in dims.split(","):
            if d:
                elems *= int(d)
        total_e += elems
        total_b += elems * _DTYPE_BYTES[dtype]
    return total_b, total_e


def _parse_hlo(hlo_text: str):
    """(entry_instructions, computations): each instruction is a dict
    ``{op, out_bytes, out_elems, opnd_bytes, opnd_elems, op_name,
    calls}``; ``computations`` maps computation name → list of opcodes
    (for fusion classification)."""
    comps: Dict[str, List[str]] = {}
    entry: List[dict] = []
    cur: Optional[List[str]] = None
    cur_entry = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("->" in stripped
                                       or stripped.startswith("ENTRY")):
            name = stripped.split()[1] if stripped.startswith("ENTRY") \
                else stripped.split()[0]
            cur = comps.setdefault(name.lstrip("%").split("(")[0], [])
            cur_entry = stripped.startswith("ENTRY")
            continue
        if stripped == "}":
            cur, cur_entry = None, False
            continue
        m = _INSTR_RE.match(line)
        if m is None or cur is None:
            continue
        out_type, opcode = m.group(1), m.group(2)
        cur.append(opcode)
        if not cur_entry:
            continue
        rest = line[m.end():]
        out_b, out_e = _shape_stats(out_type)
        opnd_b, opnd_e = _shape_stats(rest.split(", metadata=")[0]
                                      .split(", calls=")[0])
        nm = _OPNAME_RE.search(line)
        calls = _CALLS_RE.search(line)
        entry.append({"op": opcode, "out_bytes": out_b, "out_elems": out_e,
                      "opnd_bytes": opnd_b, "opnd_elems": opnd_e,
                      "op_name": nm.group(1) if nm else "",
                      "calls": calls.group(1) if calls else None})
    return entry, comps


def hlo_waterfall(hlo_text: str, *, total_flops: Optional[float] = None,
                  peak: float = 1e12, hbm_bytes_per_s: float = 50e9,
                  layer_depth: int = 3) -> dict:
    """Analytic per-op-class waterfall of one compiled program.

    Every ENTRY-computation instruction is classified (fusions by their
    called computation's contents), charged its **boundary** HBM bytes
    (operand + output shapes — a fusion's interior traffic never reaches
    HBM, which is exactly the benefit of fusing), and given a FLOPs
    share: elementwise ops ~1 flop/output element, reduces ~1
    flop/input element, and the matmul class takes the remainder of the
    compiler's ``cost_analysis()['flops']`` total apportioned by output
    size — matmul/conv is where the flops live, by definition.  Modeled
    time per instruction is the roofline ``max(flops/peak, bytes/bw)``;
    classes and layers are rollups of the same per-instruction model, so
    the two views always agree."""
    entry, comps = _parse_hlo(hlo_text)
    # First pass: classify + cheap flop estimates.
    ew_flops = red_flops = mm_out = 0.0
    for ins in entry:
        cls = (classify_fusion(comps.get(ins["calls"], ()))
               if ins["op"] == "fusion" else classify_op(ins["op"]))
        ins["class"] = cls
        if cls in ("overhead",):
            # Parameters/tuples move no HBM bytes at runtime.
            ins["opnd_bytes"] = ins["out_bytes"] = 0.0
        if cls == "elementwise":
            ins["flops"] = ins["out_elems"]
            ew_flops += ins["flops"]
        elif cls == "reduce":
            ins["flops"] = ins["opnd_elems"]
            red_flops += ins["flops"]
        else:
            ins["flops"] = 0.0
            if ins["class"] == "matmul":
                mm_out += ins["out_elems"]
    mm_flops = max(0.0, float(total_flops or 0.0) - ew_flops - red_flops)
    for ins in entry:
        if ins["class"] == "matmul" and mm_out > 0:
            ins["flops"] = mm_flops * ins["out_elems"] / mm_out
        ins["bytes"] = ins["opnd_bytes"] + ins["out_bytes"]
        ins["ms"] = 1000.0 * max(ins["flops"] / max(peak, 1.0),
                                 ins["bytes"] / max(hbm_bytes_per_s, 1.0))
    classes: Dict[str, dict] = {}
    layers: Dict[str, float] = {}
    for ins in entry:
        c = classes.setdefault(ins["class"], {"ms": 0.0, "flops": 0.0,
                                              "bytes": 0.0, "ops": 0})
        c["ms"] += ins["ms"]
        c["flops"] += ins["flops"]
        c["bytes"] += ins["bytes"]
        c["ops"] += 1
        # Layer rollup over ops that cost something: parameters/tuples
        # carry argument-path metadata, not layer scopes.
        if ins["op_name"] and ins["ms"] > 0 and ins["class"] != "overhead":
            key = layer_of(ins["op_name"], depth=layer_depth)
            layers[key] = layers.get(key, 0.0) + ins["ms"]
    total_ms = sum(c["ms"] for c in classes.values())
    for name, c in classes.items():
        c["ms"] = round(c["ms"], 4)
        c["frac"] = round(c["ms"] / total_ms, 4) if total_ms > 0 else 0.0
        inten = roofline_intensity(c["flops"], c["bytes"])
        c["intensity"] = round(inten, 3) if inten is not None else None
        c["verdict"] = ("overhead" if name == "overhead" else
                        roofline_verdict(c["flops"], c["bytes"], peak,
                                         hbm_bytes_per_s))
    return {"source": "hlo_cost_model",
            "modeled_ms_total": round(total_ms, 4),
            "peak_flops": peak, "hbm_bytes_per_s": hbm_bytes_per_s,
            "ridge_intensity": round(ridge_intensity(peak, hbm_bytes_per_s),
                                     3),
            "total_flops": float(total_flops or 0.0),
            "classes": classes,
            # Top layers only: the event must stay a bounded record, not
            # a whole-program dump (the full HLO is one --step-waterfall
            # away).
            "layers": {k: round(v, 4) for k, v in sorted(
                layers.items(), key=lambda kv: -kv[1])[:48]}}


def attribute_device_time(model_wf: dict,
                          device_ms_steps: Sequence[float]) -> dict:
    """Map a modeled waterfall onto the measured telemetry device bucket.

    The best (minimum) observed step is the closest observable to pure
    program time (the noise-robust statistic every calibration here
    uses); modeled class times are scaled onto it, and the mean-over-
    best excess — host stalls the step breakdown books to the device
    residual — lands in the ``overhead`` class as ``stall_ms``.  The
    per-class times therefore **sum to the measured mean device bucket
    by construction** (the acceptance invariant the CI profile smoke
    asserts), and a fault that stalls *some* steps shifts the class
    distribution toward overhead — which is what the roofline gate
    fires on."""
    steps = [float(s) for s in device_ms_steps if s > 0]
    if not steps:
        return dict(model_wf)
    best = min(steps)
    mean = statistics.fmean(steps)
    stall = max(0.0, mean - best)
    modeled_total = sum(c["ms"] for c in model_wf["classes"].values())
    scale = best / modeled_total if modeled_total > 0 else 0.0
    out = {k: v for k, v in model_wf.items() if k not in ("classes",
                                                          "layers")}
    out["source"] = model_wf.get("source", "hlo_cost_model") + "+measured"
    out["steps"] = len(steps)
    out["device_ms_best"] = round(best, 3)
    out["device_ms_per_step"] = round(mean, 3)
    out["stall_ms"] = round(stall, 3)
    out["model_scale"] = round(scale, 4)
    classes = {}
    for name, c in model_wf["classes"].items():
        classes[name] = dict(c)
        classes[name]["ms"] = round(c["ms"] * scale, 4)
    oh = classes.setdefault("overhead", {"ms": 0.0, "flops": 0.0,
                                         "bytes": 0.0, "ops": 0,
                                         "verdict": "overhead",
                                         "intensity": None})
    oh["ms"] = round(oh["ms"] + stall, 4)
    total = sum(c["ms"] for c in classes.values())
    for c in classes.values():
        c["frac"] = round(c["ms"] / total, 4) if total > 0 else 0.0
    out["classes"] = classes
    out["layers"] = {k: round(v * scale, 4)
                     for k, v in model_wf.get("layers", {}).items()}
    return out


def waterfall_summary(wf: dict) -> str:
    """One log line: per-class ms + verdict initials."""
    parts = []
    for name in OP_CLASSES:
        c = wf.get("classes", {}).get(name)
        if c is None:
            continue
        v = {"compute-bound": "C", "hbm-bound": "M",
             "overhead": "-"}.get(c.get("verdict"), "?")
        parts.append(f"{name} {c['ms']:.1f}ms[{v}]")
    head = wf.get("device_ms_per_step") or wf.get("device_ms_total") \
        or wf.get("modeled_ms_total")
    return f"device {head}ms/step: " + ", ".join(parts)


# -- the capture analyzer (bus wiring) ----------------------------------------
class CaptureAnalyzer:
    """Runs the analyzer on every triggered-trace capture and once at
    run end, publishing ``profile`` events.

    Subscribes to ``step`` events (host-side floats only — the zero-
    syncs/zero-compiles discipline is test-asserted on-vs-off);
    ``on_capture`` is handed to :class:`tpuic.telemetry.tracing.
    TraceTrigger`, ``finalize()`` runs from TrainTelemetry.flush().  The
    HLO provider (Trainer wires the real train step's AOT lowering) is
    called lazily ONCE and cached — compiling for analysis is off the
    hot path by construction, and on CPU it is a persistent-cache hit.
    Every failure publishes a ``profile`` event with an ``error`` field
    and stands down: observability must never kill the run."""

    def __init__(self, *, hlo_provider: Optional[Callable] = None,
                 peak: float = 1e12, hbm_bytes_per_s: float = 50e9,
                 bus=None, window: int = 1024, warmup_steps: int = 2,
                 model_name: str = "", image_size: int = 0,
                 global_batch: int = 0, n_devices: int = 1,
                 layer_depth: int = 3) -> None:
        if bus is None:
            from tpuic.telemetry.events import bus as _global_bus
            bus = _global_bus
        self.bus = bus
        self.hlo_provider = hlo_provider
        self.peak = float(peak)
        self.hbm = float(hbm_bytes_per_s)
        self.warmup_steps = int(warmup_steps)
        self.layer_depth = int(layer_depth)
        self.model_name = model_name
        self.image_size = int(image_size)
        self.global_batch = int(global_batch)
        self.n_devices = max(1, int(n_devices))
        self._device_ms: deque = deque(maxlen=max(16, int(window)))
        self._model_wf: Optional[dict] = None
        self._model_err: Optional[str] = None
        self._drift: Optional[float] = None
        self._tracing = False      # a profiler window is open
        self._taint_next = 0       # steps to skip after a window closes
        self._finalized = False
        self.tainted_steps = 0
        self.last: Optional[dict] = None
        self.analyses = 0

    # -- bus hooks -----------------------------------------------------
    def on_event(self, ev) -> None:
        if ev.kind == "step":
            if self._tracing or self._taint_next > 0:
                # Observer effect: steps measured while a profiler
                # window is open (and the step whose span absorbed the
                # stop/serialize) are not representative of steady-state
                # device time — on CPU the python tracer alone is a
                # 10-100x slowdown.  Excluded, and counted so the
                # exclusion is visible in the published event.
                self._taint_next = max(0, self._taint_next - 1)
                self.tainted_steps += 1
                return
            self._device_ms.append(float(ev.data.get("device_ms", 0.0)))
        elif ev.kind == "trace":
            action = ev.data.get("action")
            if action == "started":
                self._tracing = True
            elif action in ("stopped", "error"):
                if self._tracing:
                    self._taint_next = 1
                self._tracing = False

    def on_capture(self, trace_path: str) -> None:
        self._analyze(trace_path=trace_path, final=False)

    def finalize(self) -> None:
        """The run-end analysis over the full step window (published
        with ``final: true`` — the record the roofline gate reads).
        Idempotent: the Trainer finalizes BEFORE its final goodput
        event (so the last --prom-dump refresh carries the waterfall)
        and flush() calls it again as the backstop for other callers —
        only the first call publishes."""
        if self._finalized:
            return
        self._finalized = True
        self._analyze(trace_path=None, final=True)

    # -- internals -----------------------------------------------------
    def _model(self) -> Optional[dict]:
        if self._model_wf is not None or self._model_err is not None:
            return self._model_wf
        if self.hlo_provider is None:
            self._model_err = "no HLO provider wired"
            return None
        try:
            hlo_text, cost = self.hlo_provider()
            flops = float(cost.get("flops", 0.0)) if cost else 0.0
            self._model_wf = hlo_waterfall(
                hlo_text, total_flops=flops, peak=self.peak,
                hbm_bytes_per_s=self.hbm, layer_depth=self.layer_depth)
            if self.model_name and flops > 0:
                # Ride-along cross-check: the analytic MFU table vs the
                # compiler's count — loud warning on >10% drift.  Under
                # SPMD the compiled program (and its cost analysis) is
                # PER-DEVICE, so the analytic side is scaled to the
                # per-device batch slice — comparing global analytic
                # FLOPs against one shard read as a false n_devices-x
                # drift (caught on the 8-device CPU mesh).
                self._drift = check_flops_drift(
                    self.model_name, self.image_size,
                    max(1, self.global_batch // self.n_devices), flops)
        except Exception as e:  # analysis must never kill the run
            self._model_err = str(e)[:200]
        return self._model_wf

    def _steps_window(self) -> List[float]:
        steps = [s for s in self._device_ms if s > 0]
        if len(steps) > self.warmup_steps + 2:
            steps = steps[self.warmup_steps:]
        return steps

    def _analyze(self, trace_path: Optional[str], final: bool) -> None:
        try:
            wf = None
            trace_wf = (parse_trace(trace_path, layer_depth=self.layer_depth)
                        if trace_path else None)
            model = self._model()
            if trace_wf is not None:
                # Real per-op device timings: the measured waterfall,
                # enriched with the model's verdicts where classes match.
                wf = {**trace_wf, "final": final}
                wf["classes"] = {
                    k: {"ms": v,
                        "frac": round(v / trace_wf["device_ms_total"], 4)
                        if trace_wf["device_ms_total"] else 0.0,
                        **({f: model["classes"][k][f]
                            for f in ("verdict", "intensity", "flops",
                                      "bytes")}
                           if model and k in model.get("classes", {}) else
                           {"verdict": "overhead" if k == "overhead"
                            else "unmodeled", "intensity": None})}
                    for k, v in trace_wf["classes"].items()}
            elif model is not None:
                steps = self._steps_window()
                wf = attribute_device_time(model, steps) if steps \
                    else dict(model)
                wf["final"] = final
            if wf is None:
                self.bus.publish("profile", final=final,
                                 trace_path=trace_path,
                                 error=self._model_err
                                 or "no device ops in trace and no model")
                return
            if trace_path:
                wf["trace_path"] = trace_path
            if self._drift is not None:
                wf["analytic_flops_drift"] = round(self._drift, 4)
            if self.tainted_steps:
                wf["tainted_steps_excluded"] = self.tainted_steps
            self.last = wf
            self.analyses += 1
            self.bus.publish("profile", **wf)
        except Exception as e:
            self.bus.publish("profile", final=final, trace_path=trace_path,
                             error=str(e)[:200])


# -- roofline regression gate -------------------------------------------------
# Gate specs in telemetry/regress.py's vocabulary (direction, kind,
# floor): class fractions are machine-independent ratios; the absolute
# per-step device bucket is calibration-scaled time.  frac_overhead's
# floor is wide — on a quiet run it is min-vs-mean jitter — but the
# seeded stall shifts it several-fold past any band.
PROFILE_SPECS = {
    "profile.frac_matmul":        ("higher", "ratio", 0.30),
    "profile.frac_copy":          ("lower", "ratio", 0.60),
    "profile.frac_overhead":      ("lower", "ratio", 1.00),
    "profile.device_ms_per_step": ("lower", "time", 0.90),
}

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(_REPO, "perf", "roofline_baseline.json")
WORKLOAD_STEPS = 12
# Stall mid-run loop steps 4-8 only: a PARTIAL stall, so the tail steps
# stay fast and anchor the best-step program time — the injected time
# then lands in the overhead class, shifting the op-class distribution
# (what the roofline gate exists to catch; a uniform slowdown is the
# PR-6 regression gate's slow_step case instead).  Steps 0-3 are inside
# the forced trace window and excluded as tainted anyway.
_INJECT_FAULTS = {"slow_step": "slow_step@4-8#0.4"}


def metrics_from_event(ev: dict) -> Dict[str, float]:
    """Gate metrics distilled from one final ``profile`` event."""
    out: Dict[str, float] = {}
    classes = ev.get("classes") or {}
    for name in ("matmul", "copy", "overhead"):
        c = classes.get(name)
        if c is not None and c.get("frac") is not None:
            out[f"profile.frac_{name}"] = float(c["frac"])
    out.setdefault("profile.frac_overhead", 0.0)
    out.setdefault("profile.frac_copy", 0.0)
    if ev.get("device_ms_per_step") is not None:
        out["profile.device_ms_per_step"] = float(ev["device_ms_per_step"])
    return out


def profile_workload(steps: int = WORKLOAD_STEPS, *, faults: str = "",
                     keep_dir: Optional[str] = None) -> Tuple[Dict[str,
                                                                   float],
                                                              dict]:
    """The pinned CPU roofline workload: a real ``train.py`` run with a
    forced trace window (``TPUIC_TRACE``) and ``--trace-analyze``, so the
    metrics come from the REAL wiring end to end — trigger → capture →
    on_capture → ``profile`` events in the metrics JSONL.  Returns
    (gate metrics, the final waterfall event)."""
    import shutil
    import subprocess
    import tempfile

    from tpuic.data.synthetic import make_synthetic_imagefolder
    from tpuic.telemetry.events import read_jsonl
    work = keep_dir or tempfile.mkdtemp(prefix="tpuic_roofline_")
    try:
        data = os.path.join(work, "data")
        if not os.path.isdir(data):
            make_synthetic_imagefolder(data, classes=("a", "b", "c"),
                                       per_class=8, size=32)
        jsonl = os.path.join(work, "events.jsonl")
        if os.path.exists(jsonl):
            os.unlink(jsonl)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TF_CPP_MIN_LOG_LEVEL="3",
                   TPUIC_TRACE=os.path.join(work, "traces"))
        if faults:
            env["TPUIC_FAULTS"] = faults
        else:
            env.pop("TPUIC_FAULTS", None)
        cmd = [sys.executable, os.path.join(_REPO, "train.py"),
               "--datadir", data, "--model", "resnet18-cifar",
               "--resize", "32", "--batchsize", "2",
               "--epochs", str(steps // 12 + 1),
               "--optimizer", "adam", "--lr", "1e-3",
               "--no-class-weights", "--log-every-steps", "1",
               "--ckpt-dir", os.path.join(work, "cp"),
               "--steps", str(steps), "--metrics-jsonl", jsonl,
               "--trace-analyze"]
        proc = subprocess.run(cmd, cwd=_REPO, env=env, text=True,
                              capture_output=True, timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(
                f"roofline workload exited {proc.returncode}:\n"
                f"{proc.stdout[-1500:]}\n{proc.stderr[-1500:]}")
        recs = read_jsonl(jsonl)
        finals = [r for r in recs
                  if r["event"] == "profile" and r.get("final")
                  and not r.get("error")]
        if not finals:
            errs = [r for r in recs if r["event"] == "profile"]
            raise RuntimeError(
                "roofline workload produced no final profile event "
                f"(profile events seen: {errs[-2:]})")
        return metrics_from_event(finals[-1]), finals[-1]
    finally:
        if keep_dir is None:
            shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m tpuic.telemetry.profile", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument("--trace", default="",
                      help="analyze a captured jax.profiler trace dir")
    mode.add_argument("--step-waterfall", action="store_true",
                      help="cost-model waterfall of the real AOT-lowered "
                           "train step on this backend")
    mode.add_argument("--check", action="store_true",
                      help="run the pinned roofline workload and compare "
                           "against the committed baseline; exit 2 on "
                           "regression")
    mode.add_argument("--write-baseline", action="store_true")
    p.add_argument("--baseline", default=DEFAULT_BASELINE)
    p.add_argument("--report", default="",
                   help="write the comparison / waterfall JSON here")
    p.add_argument("--trials", type=int, default=3)
    p.add_argument("--steps", type=int, default=WORKLOAD_STEPS)
    p.add_argument("--model", default="resnet18-cifar",
                   help="--step-waterfall only (the gate workload is "
                        "pinned)")
    p.add_argument("--image-size", type=int, default=32,
                   help="--step-waterfall only")
    p.add_argument("--batch", type=int, default=2,
                   help="--step-waterfall only")
    p.add_argument("--layer-depth", type=int, default=3)
    p.add_argument("--inject", default="",
                   help="seed 'slow_step' (a partial stall) — the "
                        "gate-can-fire proof")
    p.add_argument("--expect-fail", action="store_true",
                   help="with --check: exit 0 IFF the comparison "
                        "regressed")
    args = p.parse_args(argv)

    def _dump(obj) -> None:
        text = json.dumps(obj, indent=2, sort_keys=True)
        print(text)
        if args.report:
            with open(args.report, "w") as f:
                f.write(text + "\n")

    if args.trace:
        wf = parse_trace(args.trace, layer_depth=args.layer_depth)
        if wf is None:
            print(f"[profile] no device op events in {args.trace} "
                  "(CPU captures carry none; use --step-waterfall for "
                  "the cost-model view)", file=sys.stderr)
            return 1
        _dump(wf)
        return 0

    if args.step_waterfall:
        wf = train_step_waterfall(args.model, args.image_size, args.batch,
                                  layer_depth=args.layer_depth)
        print(f"[profile] {waterfall_summary(wf)}", file=sys.stderr)
        _dump(wf)
        return 0

    if (args.model, args.image_size, args.batch) != \
            ("resnet18-cifar", 32, 2):
        # Scope guard: the roofline gate runs a PINNED workload — the
        # committed baseline would silently gate the wrong model if
        # these flags were accepted and ignored.
        p.error("--model/--image-size/--batch apply to --step-waterfall "
                "only; the --check/--write-baseline workload is pinned "
                "(resnet18-cifar @32, batch 2)")

    # --check / --write-baseline share regress.py's noise machinery:
    # calibration scaling + the tolerance ladder (one gate discipline).
    from tpuic.telemetry import regress

    inject = tuple(s.strip() for s in args.inject.split(",") if s.strip())
    unknown = set(inject) - set(_INJECT_FAULTS)
    if unknown:
        p.error(f"--inject: unknown fault(s) {sorted(unknown)} "
                f"(supported: {sorted(_INJECT_FAULTS)})")
    faults = ",".join(_INJECT_FAULTS[i] for i in inject)

    if args.write_baseline:
        cal = regress.calibration_s()
        trials, last_wf = [], None
        for i in range(max(1, args.trials)):
            print(f"[profile] baseline trial {i + 1}/{args.trials} ...",
                  flush=True)
            metrics, last_wf = profile_workload(args.steps)
            trials.append(metrics)
        baseline = regress.make_baseline(
            trials, cal, {"train_steps": args.steps,
                          "model": "resnet18-cifar", "image_size": 32,
                          "global_batch": 2})
        baseline["waterfall"] = last_wf
        os.makedirs(os.path.dirname(args.baseline) or ".", exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[profile] roofline baseline ({len(baseline['metrics'])} "
              f"metrics, {args.trials} trials) -> {args.baseline}")
        print(f"[profile] {waterfall_summary(last_wf)}")
        return 0

    # --check
    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except OSError as e:
        print(f"[profile] cannot read baseline {args.baseline}: {e}\n"
              f"[profile] run --write-baseline first", file=sys.stderr)
        return 3
    if faults:
        print(f"[profile] seeding fault(s): {faults}")
    cal = regress.calibration_s()
    fresh, wf = profile_workload(args.steps, faults=faults)
    report = regress.compare(baseline, fresh, cal, specs=PROFILE_SPECS)
    report["fresh_metrics"] = fresh
    report["waterfall"] = wf
    report["injected"] = list(inject)
    print(f"[profile] {waterfall_summary(wf)}")
    regress._print_report(report)
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[profile] comparison -> {args.report}")
    if args.expect_fail:
        if report["regressed"]:
            print("[profile] expected failure observed — the roofline "
                  "gate can fire (bidirectional proof OK)")
            return 0
        print("[profile] ERROR: seeded stall did NOT trip the roofline "
              "gate — the gate is decoration", file=sys.stderr)
        return 2
    return 2 if report["regressed"] else 0


def train_step_waterfall(model_name: str, image_size: int,
                         global_batch: int, *,
                         layer_depth: int = 3) -> dict:
    """Cost-model waterfall of the REAL train step, AOT-lowered on the
    current backend — the ``--step-waterfall`` CLI and the
    cost-analysis-extraction test both go through here."""
    import jax

    from tpuic.config import ModelConfig, OptimConfig
    from tpuic.telemetry.goodput import cost_analysis_dict
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import create_train_state
    from tpuic.train.step import make_train_step

    from tpuic.models import create_model
    mcfg = ModelConfig(name=model_name, num_classes=10, dtype="float32")
    ocfg = OptimConfig(optimizer="sgd", learning_rate=0.1,
                       class_weights=(), milestones=())
    model = create_model(mcfg.name, mcfg.num_classes, dtype=mcfg.dtype)
    state = create_train_state(
        model, make_optimizer(ocfg), jax.random.key(0),
        (global_batch, image_size, image_size, 3))
    sds = jax.ShapeDtypeStruct
    import numpy as np
    batch = {"image": sds((global_batch, image_size, image_size, 3),
                          np.float32),
             "label": sds((global_batch,), np.int32),
             "mask": sds((global_batch,), np.float32)}
    step = make_train_step(ocfg, mcfg, None, donate=False)
    compiled = step.lower(state, batch).compile()
    try:
        cost = cost_analysis_dict(compiled)
    except Exception:
        cost = {}
    dev = jax.devices()[0]
    wf = hlo_waterfall(compiled.as_text(),
                       total_flops=float(cost.get("flops", 0.0)),
                       peak=peak_flops(dev),
                       hbm_bytes_per_s=hbm_bandwidth(dev),
                       layer_depth=layer_depth)
    wf["model"] = model_name
    if cost.get("flops"):
        drift = check_flops_drift(model_name, image_size, global_batch,
                                  float(cost["flops"]))
        if drift is not None:
            wf["analytic_flops_drift"] = round(drift, 4)
    return wf


if __name__ == "__main__":
    sys.exit(main())
