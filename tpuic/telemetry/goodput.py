"""MFU + goodput accounting: where did the wall time go?

The large-scale training literature attributes its wins to exactly this
bookkeeping — FireCaffe (arXiv 1511.00175) and the 15-minute ImageNet
run (arXiv 1711.04325) both measure, then shrink, the comm/input
fraction of step time.  This module owns:

- the **peak-FLOPs table** and **analytic per-model FLOPs** that
  bench.py previously kept private (bench.py now imports them back, so
  the bench headline and the in-band MFU share one formula);
- a **GoodputTracker** that subscribes to the event bus and classifies
  wall time into buckets::

      productive   device/dispatch time of non-skipped train steps
      input        loader wait (the input-bound fraction)
      compile      jaxpr/MLIR/backend compile (jax.monitoring bridge),
                   subtracted from the step/eval span it stalled
      checkpoint   checkpoint stage + commit spans
      skip         estimated time of guard-skipped (non-finite) steps
      rollback     checkpoint-restore spans after a non-finite streak
      eval         validation epochs
      other        wall - all of the above (setup, logging gaps)

  ``report()`` returns the buckets, their fractions, the accounted
  fraction (tier-1 CI asserts the named buckets sum to ~100% of wall on
  a synthetic run), and running MFU when the model's FLOPs are known.

Accounting notes (documented, not hidden):

- Skip time is an **estimate**: the skip streak is only observed at the
  deferred drain (the price of a sync-free hot path), so skipped steps
  are charged at the rolling mean step time and moved out of
  ``productive``.  At ``log_every_steps=1`` the estimate is exact.
- MFU counts only productive (non-skipped) steps: a guard-skipped step
  runs the FLOPs but trains nothing, so counting it would inflate the
  number goodput exists to keep honest.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

# bf16 peak FLOP/s per chip by device kind (public spec sheets).
# Moved from bench.py (which imports it back) — single source of truth
# for every MFU number this repo reports.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "cpu": 1e12,             # nominal, keeps the metric finite in CI
}

# f32 peak FLOP/s: TPU MXUs run f32 matmuls at half the bf16 rate
# (spec-sheet convention — the same systolic array issues one f32 or
# two bf16 MACs per cell per cycle).  An f32 run judged against the
# bf16 roofline would under-report MFU by exactly 2x, which is how a
# "bf16 doubled our MFU" claim lies: same math, different denominator.
# The cpu entry stays nominal — CI only needs the metric finite.
PEAK_FLOPS_F32 = {k: (v / 2.0 if k != "cpu" else v)
                  for k, v in PEAK_FLOPS.items()}

_DTYPE_PEAKS = {"bf16": PEAK_FLOPS, "bfloat16": PEAK_FLOPS,
                "f32": PEAK_FLOPS_F32, "float32": PEAK_FLOPS_F32}


def _by_kind(table: dict, device, what: str) -> float:
    """``table``'s row for a jax device's ``device_kind`` (``None`` reads
    the nominal ``cpu`` row).  A device the table does not know is an
    error, not a default: a utilization against a made-up peak is no
    number at all."""
    kind = getattr(device, "device_kind", "cpu") if device is not None else "cpu"
    for k, v in table.items():
        if str(kind).lower().startswith(k.lower()):
            return v
    raise ValueError(f"{what}: unknown device kind {kind!r}; add its "
                     f"spec-sheet row (known: {sorted(table)})")


def peak_flops(device, dtype: str = "bf16") -> float:
    """Peak FLOP/s for a jax device at ``dtype`` ('bf16' default, 'f32'
    for the half-rate f32 roofline); raises on an unknown device kind."""
    table = _DTYPE_PEAKS.get(str(dtype).lower())
    if table is None:
        raise ValueError(f"peak_flops: unknown dtype {dtype!r} "
                         "(want 'bf16' or 'f32')")
    return _by_kind(table, device, "peak_flops")


# HBM bandwidth in GB/s per chip by device kind (public spec sheets) —
# the second axis of the roofline every device-time verdict in
# telemetry/profile.py is judged against.  Golden-value-pinned in
# tests/test_profile.py exactly like PEAK_FLOPS above: an MFU claim and
# a "this op class is HBM-bound" claim must come from the same tables.
HBM_GBPS = {
    "TPU v5 lite": 819,     # v5e
    "TPU v5e": 819,
    "TPU v5": 2765,         # v5p
    "TPU v4": 1228,
    "TPU v6 lite": 1640,    # v6e / Trillium
    "cpu": 50,              # nominal DDR-class; keeps the metric finite in CI
}


def hbm_bandwidth(device) -> float:
    """HBM bytes/s for a jax device; raises on an unknown device kind."""
    return _by_kind(HBM_GBPS, device, "hbm_bandwidth") * 1e9


def roofline_intensity(flops: float, bytes_accessed: float) -> Optional[float]:
    """Arithmetic intensity (FLOPs per HBM byte), or None when no bytes
    move.  THE shared formula: telemetry/profile.py's per-class verdicts
    and bench.py's detail both call this instead of growing two."""
    if not bytes_accessed or bytes_accessed <= 0:
        return None
    return float(flops) / float(bytes_accessed)


def ridge_intensity(peak: float, hbm_bytes_per_s: float) -> float:
    """The roofline ridge point (FLOPs/byte): below it a kernel at peak
    bandwidth cannot reach peak FLOPs — it is HBM-bound by arithmetic."""
    return float(peak) / max(1.0, float(hbm_bytes_per_s))


def roofline_verdict(flops: float, bytes_accessed: float, peak: float,
                     hbm_bytes_per_s: float) -> str:
    """'compute-bound' | 'hbm-bound' | 'overhead' for a (FLOPs, bytes)
    workload on a (peak, bandwidth) machine.  'overhead' means neither
    axis is exercised (no flops AND no bytes — control flow, tuples,
    host stalls booked to the device bucket)."""
    if (not flops or flops <= 0) and (not bytes_accessed
                                      or bytes_accessed <= 0):
        return "overhead"
    inten = roofline_intensity(flops, bytes_accessed)
    if inten is None:  # flops but no bytes: register-resident compute
        return "compute-bound"
    return ("compute-bound"
            if inten >= ridge_intensity(peak, hbm_bytes_per_s)
            else "hbm-bound")


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` normalized to one flat dict.

    jax returns a list of per-device dicts on some backends (CPU) and a
    plain dict on others; every consumer here (bench.py, profile.py,
    serve/engine.py) wants the first device's view.  Raises whatever the
    runtime raises when cost analysis is unsupported — callers guard."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


def check_flops_drift(model_name: str, image_size: int, global_batch: int,
                      compiled_flops: float, *, train: bool = True,
                      tol: float = 0.10, warn=None) -> Optional[float]:
    """Cross-check the analytic FLOPs table against the compiler's count.

    Returns the relative drift ``|analytic - compiled| / compiled`` (None
    when the model is unknown or the compiled count is unusable) and
    WARNS — loudly, never raises — when it exceeds ``tol``: the analytic
    table feeding every in-band MFU number silently mis-reports once the
    models or the table drift apart, and until now nothing compared them
    where both are available (bench.py and the profile analyzer do now).
    """
    if not compiled_flops or compiled_flops <= 0:
        return None
    analytic = analytic_flops_per_step(model_name, image_size, global_batch,
                                       train=train)
    if analytic is None:
        return None
    drift = abs(analytic - float(compiled_flops)) / float(compiled_flops)
    if drift > tol:
        import warnings
        (warn or warnings.warn)(
            f"analytic FLOPs table drifts {100.0 * drift:.1f}% from the "
            f"compiler's count for model={model_name!r} "
            f"(analytic {analytic:.3e} vs cost_analysis "
            f"{float(compiled_flops):.3e} per step): MFU numbers derived "
            "from the table are off by the same factor — update "
            "FWD_FLOPS_PER_IMAGE in tpuic/telemetry/goodput.py")
    return drift


# Analytic forward GFLOPs per image at a canonical resolution
# (2x the published per-model GMAC figures; prefix-matched so
# '-s2d'/'-cifar' variants inherit the family figure unless listed).
# The training step is fwd + bwd ~= 3x forward.
#
# Every entry is cross-checked against the compiler's own count by
# tests/test_flops_zoo.py (forward-only compile at the canonical shape,
# drift must stay under check_flops_drift's 10% warning threshold).
# That sweep is what caught the table's original sin TWICE: the 0.56e9
# resnet18-cifar entry (PR 10, 43% drift) and then the ENTIRE rest of
# the zoo (PR 16) were literature GMAC counts pasted as FLOPs — 2x low
# across the board, flattering-halving every analytic-table MFU number.
# The vit-tiny entry was worse: the DeiT-Ti literature figure pasted
# onto this repo's test-scale ViT (patch 4, hidden 64, depth 2), a
# model with ~5x that cost at 224px (patch-4 token counts make the
# quadratic attention term dominate); its entry is the compiled count.
FWD_FLOPS_PER_IMAGE = {
    # 1.11e9 = 2 * 0.56 GMACs (CIFAR-ResNet18).  Fwd-only drift vs the
    # compiler is ~15% (compiled fwd ~0.97e9/img at 32px) — the one
    # entry tests/test_flops_zoo.py carries a documented wider bound
    # for; the profile smoke's train-side drift stays ~7% because the
    # compiled bwd runs ~2.7x fwd, absorbing the overshoot.
    "resnet18-cifar": (1.11e9, 32),
    "resnet18": (3.64e9, 224),
    "resnet34": (7.34e9, 224),
    "resnet50": (8.2e9, 224),
    "resnet101": (15.6e9, 224),
    "resnet152": (23.0e9, 224),
    "inceptionv3": (11.4e9, 299),
    "efficientnet-b0": (0.78e9, 224),
    "efficientnet-b3": (3.6e9, 300),
    "efficientnet-b7": (74e9, 600),
    "vit-tiny": (6.3e9, 224),
    "vit-s16": (9.2e9, 224),
    "vit-b16": (35.2e9, 224),
    "vit-b32": (8.8e9, 224),
    "vit-l16": (123.2e9, 224),
    "vit-l32": (30.8e9, 224),
}


def analytic_flops_per_step(model_name: str, image_size: int,
                            global_batch: int,
                            train: bool = True) -> Optional[float]:
    """Analytic FLOPs of one step, or None for an unknown model.

    Longest-prefix match over FWD_FLOPS_PER_IMAGE, scaled by
    ``(image_size / canonical)^2`` (conv/attention cost is ~quadratic in
    side length; an approximation, stated as such in
    docs/observability.md — XLA's compiled cost analysis, when
    available, stays the bench headline's preferred source).
    """
    if not model_name or not global_batch:
        return None
    name = model_name.lower()
    best = None
    for key, (gf, base) in FWD_FLOPS_PER_IMAGE.items():
        if name.startswith(key) and (best is None or len(key) > len(best[0])):
            best = (key, gf, base)
    if best is None:
        return None
    _, gf, base = best
    scale = (float(image_size) / base) ** 2 if image_size else 1.0
    fwd = gf * scale * global_batch
    return 3.0 * fwd if train else fwd


_BUCKETS = ("productive", "input", "compile", "checkpoint", "skip",
            "rollback", "eval", "restart")


class GoodputTracker:
    """Wall-time classifier over bus events (see module docstring).

    Thread-safe: ``compile`` events arrive from whatever thread compiled
    (the serve batcher included) while ``step`` events come from the
    train loop.
    """

    def __init__(self, flops_per_step: Optional[float] = None,
                 peak_flops: float = 1e12, global_batch: int = 0,
                 compute_dtype: str = "") -> None:
        self._lock = threading.Lock()
        self.flops_per_step = flops_per_step
        self.peak = max(1.0, float(peak_flops))
        self.compute_dtype = str(compute_dtype)
        self.global_batch = int(global_batch)
        self._t0: Optional[float] = None
        self.buckets = {k: 0.0 for k in _BUCKETS}
        self.steps = 0
        self.skipped_est = 0.0   # estimated skipped steps (from streaks)
        self.compiles = 0        # backend_compile count
        self.restarts = 0        # supervisor restart count of this run
        self._pending_compile = 0.0
        self._step_total_s = 0.0  # for the rolling mean (skip estimate)
        self.ckpt_async_s = 0.0   # deferred commits (overlapped, not wall)

    # -- event intake --------------------------------------------------
    def start(self) -> None:
        """Open the measurement window (idempotent: first call wins, so
        a resumed fit() keeps its original origin)."""
        with self._lock:
            if self._t0 is None:
                self._t0 = time.monotonic()

    def on_event(self, ev) -> None:
        kind, d = ev.kind, ev.data
        with self._lock:
            if self._t0 is None:
                self._t0 = time.monotonic()
            if kind == "step":
                total = float(d.get("total_ms", 0.0)) / 1000.0
                data = min(float(d.get("data_ms", 0.0)) / 1000.0, total)
                attr = total - data
                c = min(self._pending_compile, attr)
                self._pending_compile -= c
                self.buckets["compile"] += c
                self.buckets["productive"] += attr - c
                self.buckets["input"] += data
                self.steps += 1
                self._step_total_s += total
            elif kind == "compile":
                dur = float(d.get("duration_s", 0.0))
                self._pending_compile += dur
                if str(d.get("key", "")).startswith("backend_compile"):
                    self.compiles += 1
            elif kind == "eval":
                dur = float(d.get("duration_s", 0.0))
                c = min(self._pending_compile, dur)
                self._pending_compile -= c
                self.buckets["compile"] += c
                self.buckets["eval"] += dur - c
            elif kind == "drain":
                # Post-loop blocking drain (break paths): device time of
                # the final dispatched step, after its step event closed
                # — productive, the same work just billed late.
                dur = float(d.get("duration_s", 0.0))
                c = min(self._pending_compile, dur)
                self._pending_compile -= c
                self.buckets["compile"] += c
                self.buckets["productive"] += dur - c
            elif kind == "checkpoint_commit":
                # A deferred (async) commit ran concurrently with compute
                # — it consumed no wall clock the step loop could have
                # used, so charging it to the 'checkpoint' bucket would
                # double-book seconds already in 'productive'.  Tracked
                # separately so report() still shows the overlapped work.
                if d.get("blocking", True):
                    self.buckets["checkpoint"] += float(
                        d.get("duration_s", 0.0))
                else:
                    self.ckpt_async_s += float(d.get("duration_s", 0.0))
            elif kind == "rollback":
                self.buckets["rollback"] += float(d.get("duration_s", 0.0))
            elif kind == "restart":
                # Supervised restart (runtime/supervisor.py): the
                # downtime — previous child's death through backoff,
                # respawn, re-init, restore — happened BEFORE this
                # process's measurement window opened. Extend the window
                # back over it and book it to 'restart', so a run that
                # lost 40s to a crash reports frac_restart instead of a
                # wall clock that silently forgot the outage.
                down = max(0.0, float(d.get("downtime_s", 0.0)))
                self.restarts = int(d.get("restart", self.restarts + 1))
                self._t0 -= down
                self.buckets["restart"] += down
            elif kind == "skip":
                # Streak delta observed at the deferred drain; charge the
                # skipped steps at the rolling mean step time and move
                # them out of 'productive' (they were booked there when
                # their step events arrived).
                delta = max(0, int(d.get("delta", 0)))
                if delta and self.steps:
                    est = delta * (self._step_total_s / self.steps)
                    est = min(est, self.buckets["productive"])
                    self.buckets["productive"] -= est
                    self.buckets["skip"] += est
                    self.skipped_est += delta

    # -- reads ---------------------------------------------------------
    def mfu(self, wall_s: Optional[float] = None) -> Optional[float]:
        """Running MFU: productive-step FLOPs / (peak * wall)."""
        if not self.flops_per_step:
            return None
        if wall_s is None:
            wall_s = (time.monotonic() - self._t0) if self._t0 else 0.0
        if wall_s <= 0:
            return None
        productive_steps = max(0.0, self.steps - self.skipped_est)
        return self.flops_per_step * productive_steps / (self.peak * wall_s)

    def report(self, step: Optional[int] = None) -> dict:
        """Snapshot: buckets (s), fractions of wall, accounted fraction,
        and MFU.  ``accounted_frac`` ~ 1.0 means the named buckets cover
        the wall clock (the tier-1 acceptance gate); the gap is reported
        honestly as ``other_s`` (setup, logging, epoch turnaround)."""
        with self._lock:
            wall = (time.monotonic() - self._t0) if self._t0 else 0.0
            named = sum(self.buckets.values()) + self._pending_compile
            out = {"wall_s": round(wall, 3), "steps": self.steps}
            if step is not None:
                out["step"] = int(step)
            buckets = dict(self.buckets)
            # Compile time not yet absorbed by a step/eval span (e.g. a
            # warmup compile before the loop) is still compile time.
            buckets["compile"] += self._pending_compile
            for k in _BUCKETS:
                out[f"{k}_s"] = round(buckets[k], 3)
            out["other_s"] = round(max(0.0, wall - named), 3)
            if wall > 0:
                for k in _BUCKETS:
                    out[f"frac_{k}"] = round(buckets[k] / wall, 4)
                out["frac_other"] = round(max(0.0, wall - named) / wall, 4)
                out["accounted_frac"] = round(min(named / wall, 1.0), 4)
            if self.global_batch:
                out["images"] = self.steps * self.global_batch
            out["skipped_steps_est"] = round(self.skipped_est, 1)
            out["compiles"] = self.compiles
            out["restarts"] = self.restarts
            out["checkpoint_async_s"] = round(self.ckpt_async_s, 3)
            if self.compute_dtype:
                out["compute_dtype"] = self.compute_dtype
            m = self.mfu(wall)
            if m is not None:
                out["mfu"] = round(m, 4)
            return out

    def summary_line(self) -> str:
        """One epoch-log line: the headline fractions."""
        r = self.report()
        parts = [f"wall {r['wall_s']:.1f}s"]
        for k in _BUCKETS + ("other",):
            f = r.get(f"frac_{k}")
            if f:
                parts.append(f"{k} {100.0 * f:.1f}%")
        if r.get("mfu") is not None:
            parts.append(f"mfu {r['mfu']:.4f}")
        return ", ".join(parts)
