"""Device-time attribution: per-op-class waterfall + roofline verdicts.

The telemetry layer attributes every *host*-side millisecond (goodput
buckets, step breakdown, fleet skew) — but ``device_ms``, the dominant
bucket at MFU 0.31 (builder, 2026-08-01), stayed an opaque residual.  The
"MFU 0.31 → 0.5+" roadmap item cannot be earned without knowing which
ops are compute-bound vs HBM-bound; the 15-minute-ImageNet line
(arXiv 1711.04325) and every TPU scaling paper start from exactly this
per-op accounting.  This module is that accounting:

- **Trace reader** (:func:`parse_trace`): reads the ``.xplane.pb`` a
  ``jax.profiler`` capture writes and gives a device's time a step, on
  the device's clock, by program, by scope (the ``jax.named_scope`` and
  flax module paths in each op's HLO metadata, from the scope maps of the
  executables the run registered: :data:`programs`) and by op class —
  each busy instant charged once, to the innermost op, so the parts sum
  to the busy time — and its idle time by the host's ``tpuic.*``
  annotation over each gap.  A CPU capture has no device plane, and the
  reader says so (returns None) instead of fabricating a waterfall.
- **HLO cost model** (:func:`hlo_waterfall`), the fallback where a
  capture has no device plane: where the runtime exposes
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

import bisect
import glob
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
                            r"while|body|cond|closed_call|branch_\d+_fun)\b")
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


def scope_path(op_name: str) -> List[str]:
    """The scopes an op lies under: its metadata ``op_name`` less the
    primitive it ends in, through :func:`scope_segments`, each scope once
    (a rematerialised backward names its forward's path a second time:
    ``transpose(jvp(Classifier))/backbone/jvp(Classifier)/backbone/...``)."""
    out: List[str] = []
    for seg in scope_segments("/".join(str(op_name).split("/")[:-1])):
        if seg not in out:
            out.append(seg)
    return out


# -- the programs a run dispatches --------------------------------------------
# The device trace names each op by its HLO instruction and each execution
# by its module; what the instruction was in the program (its scope, its
# opcode) is in the compiled executable's text. So the Trainer registers
# the programs it dispatches every step, by role, with the abstract
# arguments of their first dispatch, and a reader asks for the map of an
# executable only after the window: lowering the same function on the same
# abstract arguments finds JAX's in-memory executable, so nothing compiles.
UNSCOPED = "(unscoped)"
UNLABELLED = "(unlabelled)"


def abstract(tree):
    """``tree`` with every array replaced by a ``jax.ShapeDtypeStruct`` of
    its shape, dtype and weak type, and the sharding of a committed array
    (an uncommitted one was dispatched without one). Abstract leaves and
    anything else pass through. Holds no array."""
    import jax
    import numpy as np

    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.aval.weak_type,
                sharding=x.sharding if x.committed else None)
        if isinstance(x, (np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x
    return jax.tree.map(leaf, tree)


class _Program:
    __slots__ = ("fn", "specs", "map")

    def __init__(self, fn, specs) -> None:
        self.fn, self.specs, self.map = fn, specs, None


class Programs:
    """The jitted programs of a run, by role (``"step"``,
    ``"input_prep"``): each the callable and the abstract arguments of its
    first dispatch, no array and no owner."""

    def __init__(self) -> None:
        self._held: Dict[str, _Program] = {}

    def note(self, role: str, fn, args: Sequence) -> None:
        """Register ``fn`` under ``role`` unless it is already there. Meant
        for every dispatch: a second call with the same callable costs a
        dict lookup. A callable that cannot be lowered is not a program
        (a stand-in that wraps the step) and is skipped."""
        held = self._held.get(role)
        if held is not None and held.fn is fn:
            return
        if not callable(getattr(fn, "lower", None)):
            return
        self._held[role] = _Program(fn, abstract(tuple(args)))

    def roles(self) -> List[str]:
        return list(self._held)

    def compiled(self, role: str):
        """The executable registered under ``role`` (JAX's cached one), or
        None."""
        held = self._held.get(role)
        return (None if held is None
                else held.fn.lower(*held.specs).compile())

    def scope_map(self, role: str) -> Optional[dict]:
        """:func:`hlo_scope_map` of the executable under ``role``, built
        once, when first asked for."""
        held = self._held.get(role)
        if held is None:
            return None
        if held.map is None:
            held.map = hlo_scope_map(self.compiled(role).as_text())
        return held.map

    def scope_maps(self) -> Dict[str, dict]:
        """``{role: scope map}`` of every registered program."""
        return {role: self.scope_map(role) for role in self.roles()}


programs = Programs()


def note(role: str, fn, args: Sequence) -> None:
    programs.note(role, fn, args)


def scope_map(role: str) -> Optional[dict]:
    return programs.scope_map(role)


_MODULE_RE = re.compile(r"^\s*HloModule\s+([\w.\-]+)")
_INSTR_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")


def _skip_type(rest: str) -> str:
    """``rest`` after the result type it starts with (a tuple type nests
    parentheses and holds spaces; any other type is one word)."""
    if not rest.startswith("("):
        return rest.split(" ", 1)[-1] if " " in rest else ""
    depth = 0
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return rest[i + 1:]
    return ""


def hlo_opcode(instruction: str) -> Optional[str]:
    """Opcode of one HLO instruction's text (``%x = f32[] add(...)`` ->
    ``add``); None for a line that is not an instruction."""
    m = _INSTR_NAME_RE.match(instruction)
    if m is None:
        return None
    op = _OPCODE_RE.match(_skip_type(instruction[m.end():]))
    return op.group(1) if op else None


def _shared(paths: List[List[str]]) -> List[str]:
    """The scopes every one of ``paths`` starts with."""
    out: List[str] = []
    for segs in zip(*paths):
        if any(seg != segs[0] for seg in segs):
            break
        out.append(segs[0])
    return out


def hlo_scope_map(hlo_text: str) -> dict:
    """``{"module": name, "ops": {instruction: (op_name, opcode,
    op_class)}}`` over the instructions of every computation of a compiled
    module (a ``while`` body's and a ``conditional`` branch's ops run as
    events of their own); ``op_name`` is the metadata's, and a fusion's
    class is :func:`classify_fusion`'s of the computation it calls. An
    instruction whose metadata names no scope (or is missing: a
    multi-output fusion's root is a tuple; a conditional the compiler
    cloned) and that runs computations is charged to the scopes that all
    of their instructions under a scope share (a routed sum's branches:
    ``routed_experts``), or where they share none to the scopes of the
    last of them, the nearest the root (an optimizer's update fused with
    the gradient it reads and the non-finite guard's select: the
    update's), given as ``<scopes>/<opcode>``. "" where there is none (the
    compiler's own copies and their ``*-done``). ``inferred`` names each
    instruction whose scopes came so, by the rule that gave them:
    ``shared`` or ``last``."""
    module = ""
    rows: List[tuple] = []
    comps: Dict[str, list] = {}         # name -> [opcodes, scope paths]
    current: Optional[list] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not module:
            m = _MODULE_RE.match(line)
            if m:
                module = m.group(1)
                continue
        if stripped.endswith("{") and ("->" in stripped
                                       or stripped.startswith("ENTRY")):
            name = stripped.split()[1] if stripped.startswith("ENTRY") \
                else stripped.split()[0]
            current = comps.setdefault(name.lstrip("%").split("(")[0],
                                       [[], []])
            continue
        if stripped == "}":
            current = None
            continue
        opcode = hlo_opcode(line) if current is not None else None
        if opcode is None:
            continue
        name = _INSTR_NAME_RE.match(line).group(1)
        op_name = _OPNAME_RE.search(line)
        op_name = op_name.group(1) if op_name else ""
        called = [n for one, many in _CALLED_RE.findall(line)
                  for n in ([one] if one else many.replace("%", "")
                            .split(", "))]
        current[0].append(opcode)
        if scope_path(op_name):
            current[1].append(scope_path(op_name))
        rows.append((name, op_name, opcode, called))

    ops: Dict[str, tuple] = {}
    inferred: Dict[str, str] = {}
    for name, op_name, opcode, called in rows:
        if not scope_path(op_name):
            paths = [p for c in called for p in comps.get(c, ([], []))[1]]
            shared = _shared(paths)
            scopes = shared or (paths[-1] if paths else [])
            if scopes:
                op_name = "/".join(scopes + [opcode])
                inferred[name] = "shared" if shared else "last"
        ops[name] = (op_name, opcode,
                     classify_fusion(comps[called[0]][0])
                     if opcode == "fusion" and called and called[0] in comps
                     else classify_op(opcode))
    return {"module": module, "ops": ops, "inferred": inferred}


# -- device time by scope, from the profiler's trace --------------------------
# What a TPU trace holds (benchmark/trace_reduce.py reads the same file):
# a plane ``/device:TPU:<n>`` per chip with a line ``XLA Modules`` (an
# event per execution, ``jit_<fn>(<fingerprint>)``) and a line ``XLA Ops``
# (an event per op, named by its HLO text); a ``while`` or ``conditional``
# op's event encloses the events of the ops its body or branch runs. Host
# threads are lines of ``/host:`` planes, where every
# ``jax.profiler.TraceAnnotation`` appears on the same clock.
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_DISPATCH = re.compile(r"^PjitFunction\((\w+)\)$")
ANNOTATION_PREFIX = "tpuic."
# the compiler's own data movement: with no metadata it stays (unscoped)
_COMPILER_MOVES = frozenset({"copy", "copy-start", "copy-done",
                             "async-start", "async-done"})


def find_xplane(path: str) -> Optional[str]:
    """``path`` if it is a file, else the newest ``.xplane.pb`` under it
    (the profiler writes ``plugins/profile/<time>/<host>.xplane.pb``)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read_xplane(path: str) -> dict:
    """``{"devices": {index: {"modules", "ops"}}, "annotations",
    "dispatches"}``, every event ``(name, start_s, duration_s)``:
    annotations are the host's ``tpuic.*`` ones, dispatches JAX's own
    ``PjitFunction(<fn>)`` calls, named by the module they run
    (``jit_<fn>``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    annotations: list = []
    dispatches: set = set()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = {
                key: [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in lines[line].events] if line in lines else []
                for key, line in (("modules", "XLA Modules"),
                                  ("ops", "XLA Ops"))}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    at = (e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    sent = _DISPATCH.match(e.name)
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((e.name,) + at)
                    elif sent:      # one event on two lines: a set
                        dispatches.add(("jit_" + sent.group(1),) + at)
    return {"devices": devices, "annotations": annotations,
            "dispatches": sorted(dispatches, key=lambda e: e[1])}


def _window(modules: Sequence[tuple], ops: Sequence[tuple],
            skip_first: int) -> Tuple[float, float, int]:
    """``(lo, hi, steps)``: from the start of the dominant program's
    execution after the first ``skip_first`` to the start of its last
    (``benchmark/trace_reduce.py::reduce_device``'s window)."""
    per_module: Dict[str, float] = {}
    for name, _, dur in modules:
        per_module[name] = per_module.get(name, 0.0) + dur
    dominant = max(per_module, key=per_module.get) if per_module else None
    starts = sorted(s for name, s, _ in modules if name == dominant)
    if len(starts) - skip_first >= 2:
        starts = starts[skip_first:]
    if len(starts) >= 2:
        return starts[0], starts[-1], len(starts) - 1
    return (min(s for _, s, _ in ops), max(s + d for _, s, d in ops),
            len(starts))


def _innermost(intervals: Sequence[tuple]):
    """Yield ``(key, start, end)`` pieces of ``(start, end, key)``
    intervals, each instant of their union once, charged to the innermost
    interval covering it: the last to start of those still open (an
    interval that lies inside another is its child)."""
    stack: List[tuple] = []                 # (end, key), innermost last
    at = float("-inf")
    for start, end, key in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack:
            top_end, top_key = stack[-1]
            upto = min(top_end, start)
            if upto > at:
                yield top_key, at, upto
                at = upto
            if top_end > start:
                break
            stack.pop()
        at = max(at, start)
        stack.append((end, key))
    while stack:
        top_end, top_key = stack.pop()
        if top_end > at:
            yield top_key, at, top_end
            at = top_end


def _label(t: float, annotations: Sequence[tuple]) -> str:
    """The innermost (shortest) annotation covering time ``t``."""
    best = None
    for name, s, d in annotations:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else UNLABELLED


def attribute_device(modules: Sequence[tuple], ops: Sequence[tuple],
                     annotations: Sequence[tuple] = (),
                     maps: Optional[Dict[str, dict]] = None,
                     skip_first: int = 2,
                     dispatches: Sequence[tuple] = ()) -> Optional[dict]:
    """Device time a step of one device, by program, scope and op class.

    Events are ``(name, start_s, duration_s)``; ``maps`` are
    :func:`hlo_scope_map`'s by role. Each instant the device is busy in the
    window is charged once, to the innermost op covering it (a ``while`` or
    ``conditional`` keeps its self time), and that op is keyed by the
    program whose execution encloses it (its role where a map names the
    module), its scope path from the map (:func:`scope_path`; an op with no
    metadata or no map is ``(unscoped)``, but for one that a compiler
    pass made inside a ``while`` or ``conditional``, which takes that op's
    scope; the compiler's copies stay) and its class. ``partition``
    (program, then scope path) sums to the busy time, and so does
    ``programs``; ``scopes`` gives each op's time to every scope of its
    path, so a scope holds its children; ``inferred`` holds the part of
    each scope charged to ops whose own metadata named none, by the rule
    that gave them their scopes (``shared`` and ``last``,
    :func:`hlo_scope_map`'s; ``encloser``, the op around it), so that a
    change of attribution can be told from a change of time. An idle gap
    whose midpoint lies inside an execution is ``inside <program>``; one
    before an execution that the host had dispatched (``dispatches``:
    ``(module, start_s, duration_s)``) by the time the gap began is
    ``queued <program>``; any other is labelled by the innermost
    ``tpuic.*`` host annotation over its midpoint, ``(unlabelled)`` where
    there is none. ``unmapped`` names, by program, the ops its map lacks.
    None where no op ran."""
    if not ops:
        return None
    lo, hi, steps = _window(modules, ops, skip_first)
    by_module = {m["module"]: (role, m["ops"], m.get("inferred", {}))
                 for role, m in (maps or {}).items() if m}
    runs = sorted((s, s + d, name.split("(", 1)[0]) for name, s, d in modules)
    run_starts = [s for s, _, _ in runs]
    keyed: Dict[tuple, tuple] = {}          # (program, op) -> keys
    unmapped: Dict[str, List[str]] = {}

    def keys(name: str, start: float, parent: Optional[tuple]) -> tuple:
        i = bisect.bisect_right(run_starts, start) - 1
        module = runs[i][2] if i >= 0 and start < runs[i][1] else "(none)"
        role, table, inferred = by_module.get(module, (module, None, {}))
        op = name.split(" = ", 1)[0].strip().lstrip("%")
        if (role, op) not in keyed:
            row = table.get(op) if table is not None else None
            how = None
            if row is None:
                unmapped.setdefault(role, []).append(op)
                path = []
                opcode = hlo_opcode(name) or op
                cls = classify_op(opcode)
            else:
                path, cls = scope_path(row[0]), row[2]
                how = inferred.get(op)
                if (not path and row[1] not in _COMPILER_MOVES
                        and parent is not None and parent[0] == role):
                    # an op a compiler pass made without metadata (a
                    # ragged dot's custom call, a broadcast) inside a
                    # while or a conditional: the scope of that op
                    path, how = list(parent[3]), "encloser"
            keyed[(role, op)] = (role, op, "/".join(path) or UNSCOPED,
                                 tuple(path), cls, how if path else None)
        return keyed[(role, op)]

    intervals = []
    open_: List[tuple] = []                 # (end, key) of enclosing ops
    for n, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        if d <= 0:
            continue
        while open_ and open_[-1][0] <= s:
            open_.pop()
        key = keys(n, s, open_[-1][1] if open_ else None)
        intervals.append((s, s + d, key))
        open_.append((s + d, key))
    per_op: Dict[tuple, float] = {}
    busy = []
    for key, s, e in _innermost(intervals):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            per_op[key] = per_op.get(key, 0.0) + (e - s)
            busy.append((s, e))
    ms = 1e3 / max(steps, 1)
    programs_ms: Dict[str, float] = {}
    partition: Dict[str, Dict[str, float]] = {}
    scopes: Dict[str, float] = {}
    classes: Dict[str, float] = {}
    inferred_ms: Dict[str, Dict[str, float]] = {}
    for (role, _, path, segs, cls, how), sec in per_op.items():
        programs_ms[role] = programs_ms.get(role, 0.0) + sec * ms
        part = partition.setdefault(role, {})
        part[path] = part.get(path, 0.0) + sec * ms
        for seg in segs:
            scopes[seg] = scopes.get(seg, 0.0) + sec * ms
            if how:
                rule = inferred_ms.setdefault(how, {})
                rule[seg] = rule.get(seg, 0.0) + sec * ms
        classes[cls] = classes.get(cls, 0.0) + sec * ms
    busy_s = sum(e - s for s, e in busy)

    # each execution's dispatch by the host, matched from the last back:
    # a capture ends after what was dispatched has run, and may begin
    # after the first executions in it were dispatched
    queued: Dict[int, float] = {}
    for module in {m for _, _, m in runs}:
        mine = [i for i, r in enumerate(runs) if r[2] == module]
        sent = [s + d for name, s, d in dispatches if name == module]
        queued.update(zip(reversed(mine), reversed(sent)))

    def role(module: str) -> str:
        return by_module.get(module, (module,))[0]

    def label(start: float, end: float) -> str:
        # inside an execution the program itself waited (a launch between
        # two of its ops, a transfer); before one the host had already
        # dispatched, the device was launching it: no host phase caused
        # either
        t = (start + end) / 2.0
        i = bisect.bisect_right(run_starts, t) - 1
        if i >= 0 and t < runs[i][1]:
            return "inside " + role(runs[i][2])
        j = bisect.bisect_right(run_starts, end) - 1
        if j > i and queued.get(j, end) <= start:
            return "queued " + role(runs[j][2])
        return _label(t, annotations)

    idle: Dict[str, float] = {}
    gaps: List[list] = []
    edge = lo
    for s, e in sorted(busy) + [(hi, hi)]:
        if s > edge:
            name = label(edge, s)
            idle[name] = idle.get(name, 0.0) + (s - edge) * ms
            gaps.append([1e3 * (edge - lo), 1e3 * (s - edge), name])
        edge = max(edge, e)
    desc = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
    return {
        "source": "xplane", "steps": steps,
        "window_s": hi - lo, "busy_s": busy_s,
        "device_ms_per_step": busy_s * ms,
        "programs": desc(programs_ms),
        "partition": {k: desc(v) for k, v in partition.items()},
        "scopes": desc(scopes), "classes": desc(classes),
        "inferred": {how: desc(v) for how, v in inferred_ms.items()},
        "idle": desc(idle),
        "unmapped": {role: sorted(ops) for role, ops in unmapped.items()},
        # the longest gaps: ms from the window's start, ms long, label
        "gaps": sorted(gaps, key=lambda g: -g[1])[:16],
        "ops": [[role, op, path, sec * ms] for (role, op, path, *_), sec
                in sorted(per_op.items(), key=lambda kv: -kv[1])],
    }


def parse_trace(path: str, maps: Optional[Dict[str, dict]] = None,
                device: Optional[int] = None,
                skip_first: int = 2) -> Optional[dict]:
    """:func:`attribute_device` of one device of the ``.xplane.pb`` at
    ``path`` (or the newest under it): ``device``, else the
    lowest-numbered device that ran an op. ``maps`` default to those of
    the programs registered in this process (:data:`programs`). None where
    the capture has no device plane with ops (every CPU capture) or no
    trace at all."""
    found = find_xplane(path) if path else None
    if found is None:
        return None
    raw = read_xplane(found)
    ran = sorted(i for i, d in raw["devices"].items() if d["ops"])
    if device is None and ran:
        device = ran[0]
    if device not in ran:
        return None
    dev = raw["devices"][device]
    out = attribute_device(dev["modules"], dev["ops"], raw["annotations"],
                           programs.scope_maps() if maps is None else maps,
                           skip_first=skip_first,
                           dispatches=raw["dispatches"])
    out["device"] = device
    return out


# -- HLO text cost model ------------------------------------------------------
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*?\)|[\w\[\]{},]+)\s+"
    r"([\w\-]+)\(")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
# every computation an instruction runs: a fusion's, an async op's, a
# while's body and condition, a conditional's branches
_CALLED_RE = re.compile(r"(?:calls|body|condition|true_computation|"
                        r"false_computation)=%?([\w.\-]+)|"
                        r"branch_computations=\{([^}]*)\}")
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
def _registered_step() -> Tuple[str, dict]:
    """(optimized HLO text, cost_analysis dict) of the registered step."""
    from tpuic.telemetry.goodput import cost_analysis_dict
    compiled = programs.compiled("step")
    if compiled is None:
        raise LookupError("no step program registered yet")
    try:
        cost = cost_analysis_dict(compiled)
    except Exception:
        cost = {}
    return compiled.as_text(), cost


class CaptureAnalyzer:
    """Runs the analyzer on every triggered-trace capture and once at
    run end, publishing ``profile`` events.

    Subscribes to ``step`` events (host-side floats only — the zero-
    syncs/zero-compiles discipline is test-asserted on-vs-off);
    ``on_capture`` is handed to :class:`tpuic.telemetry.tracing.
    TraceTrigger`, ``finalize()`` runs from TrainTelemetry.flush().  A
    capture with a device plane is published as measured
    (:func:`parse_trace` with the registered programs' scope maps); one
    without (every CPU capture, and the run-end analysis) falls back to
    the HLO cost model of the step the Trainer registered
    (:data:`programs`, role ``"step"``; ``hlo_provider`` stands in for it),
    called lazily ONCE and cached — JAX's own executable, nothing
    compiles.  Every failure publishes a ``profile`` event with an
    ``error`` field and stands down: observability must never kill the
    run."""

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
        try:
            hlo_text, cost = (self.hlo_provider or _registered_step)()
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
            trace_wf = parse_trace(trace_path) if trace_path else None
            model = self._model()
            if trace_wf is not None:
                # Real per-op device timings: the measured attribution
                # (bounded: the largest scopes and ops), enriched with the
                # model's verdicts where classes match.
                total = trace_wf["device_ms_per_step"]
                wf = {**trace_wf, "final": final,
                      "scopes": dict(list(trace_wf["scopes"].items())[:48]),
                      "partition": {k: dict(list(v.items())[:24])
                                    for k, v in trace_wf["partition"].items()},
                      "ops": trace_wf["ops"][:24]}
                wf["classes"] = {
                    k: {"ms": v, "frac": round(v / total, 4) if total else 0.0,
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
        wf = parse_trace(args.trace)
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
