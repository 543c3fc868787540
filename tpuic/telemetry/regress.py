"""Noise-aware perf-regression gate: speed as a tested invariant.

PR 3 made every millisecond attributable (goodput buckets, MFU, serve
latency percentiles); nothing *enforced* any of it — a PR that halved
MFU or doubled serve p99 still merged green.  This module is the
enforcement: a pinned CPU smoke workload (train via ``train.py --steps``
on a synthetic ImageFolder, serve via the real InferenceEngine), a
committed baseline (``perf/regression_baseline.json``), and a comparison
that fails CI when a gated metric regresses past its tolerance::

    python -m tpuic.telemetry.regress --check            # CI gate
    python -m tpuic.telemetry.regress --write-baseline   # refresh baseline
    python -m tpuic.telemetry.regress --check \
        --inject slow_step,hang_device --expect-fail     # prove it fires

Noise discipline (CPU CI jitters; the gate must catch a 2x regression
without flaking on a 20% wobble):

- **Calibration scaling.**  Every run times a pinned single-thread numpy
  workload; absolute-time metrics are compared against ``baseline *
  (fresh_calibration / baseline_calibration)`` (rates against the
  inverse), so a CI runner that is simply 2x slower than the dev box
  that wrote the baseline does not read as a 2x regression.  The scale
  is clamped to [1/4, 4] — beyond that the machines are not comparable
  and the gate refuses to judge: a typed ``environment_mismatch``
  verdict (exit 3, distinct from regression exit 2), because on such a
  host every absolute-time row fails identically at seed and tip and a
  "REGRESSED" verdict would be noise wearing a gate's uniform.
- **Tolerance ladder.**  Per metric: ``tol = max(floor, NOISE_MULT x
  noise)`` where ``noise`` is the relative trial spread recorded at
  baseline-write time (the same spread discipline bench.py records) and
  ``floor`` is a per-metric-class minimum — ratio metrics (goodput
  fractions, pad efficiency) are machine-independent and get tight
  floors; single-run tail latencies get wide ones.
- **Exact counters** (steady-state serve compiles) tolerate nothing:
  one new compile in steady state IS the regression.

The ``--inject`` flag seeds the same deterministic faults the chaos
harness uses (``slow_step`` into the train child via TPUIC_FAULTS,
``hang_device`` into the in-process serve engine), which is how CI
proves the gate is *bidirectional*: the clean workload must pass AND the
seeded-slowdown workload must fail naming the regressed metric — a gate
that cannot fire is decoration (docs/observability.md,
"Perf-regression gate").
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, Optional, Sequence

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCHEMA = 1
NOISE_MULT = 4.0
CAL_CLAMP = 4.0

# name -> (direction, kind, floor_tolerance)
#   direction: "higher" = bigger is better, "lower" = smaller is better
#   kind: "ratio" machine-independent fraction — no calibration scaling;
#         "time"  absolute ms — scaled by fresh/base calibration;
#         "rate"  throughput-like — scaled by the inverse;
#         "count" exact counter — floor is an ABSOLUTE allowance, not
#                 relative (0.0 = any increase regresses).
METRIC_SPECS = {
    "train.mfu":              ("higher", "rate", 0.50),
    "train.step_p50_ms":      ("lower", "time", 0.50),
    "train.step_p99_ms":      ("lower", "time", 0.90),
    "train.frac_productive":  ("higher", "ratio", 0.30),
    "train.accounted_frac":   ("higher", "ratio", 0.05),
    "serve.latency_p50_ms":   ("lower", "time", 0.70),
    "serve.latency_p99_ms":   ("lower", "time", 1.00),
    # The quantized serve ladder's latency rows (docs/performance.md,
    # "Quantized serving"): a regression in a bf16/int8 rung — a
    # dequant fusion lost, a per-dtype executable falling out of the
    # AOT cache — must fail CI even when the fp32 rung stays fast.
    # Compiles of every rung fold into serve.steady_compiles (exact).
    "serve.bf16_latency_p50_ms": ("lower", "time", 0.70),
    "serve.bf16_latency_p99_ms": ("lower", "time", 1.00),
    "serve.int8_latency_p50_ms": ("lower", "time", 0.70),
    "serve.int8_latency_p99_ms": ("lower", "time", 1.00),
    "serve.throughput_images_per_sec": ("higher", "rate", 0.50),
    "serve.pad_efficiency":   ("higher", "ratio", 0.20),
    "serve.steady_compiles":  ("lower", "count", 0.0),
}


# -- machine-speed calibration ------------------------------------------------
def calibration_s(reps: int = 5, n: int = 2_000_000) -> float:
    """Seconds to ``np.sort`` a pinned random array, best of ``reps``
    (min is the noise-robust statistic for a lower-bounded timing).  The
    common-mode machine-speed reference absolute-time comparisons are
    normalized by.  Sort, not matmul, deliberately: numpy's sort is
    single-threaded everywhere, so the number does not swing with BLAS
    thread scheduling the way a matmul chain measurably does."""
    import numpy as np
    rng = np.random.default_rng(0)
    a = rng.standard_normal(n).astype(np.float32)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.sort(a)
        best = min(best, time.perf_counter() - t0)
    return best


# -- the pinned workloads -----------------------------------------------------
def train_workload(steps: int = 8, *, faults: str = "",
                   keep_dir: Optional[str] = None) -> Dict[str, float]:
    """Run ``train.py --steps N`` on a synthetic ImageFolder in a
    subprocess (CPU pinned) and distill the gated train metrics from its
    telemetry JSONL.  ``faults`` seeds the child's TPUIC_FAULTS (the
    bidirectional proof).  Step percentiles skip the first two steps —
    compile/cache warmup is the goodput tracker's business, not a
    steady-state regression signal.  The scratch dir (dataset +
    checkpoints + JSONL) is removed afterwards unless the caller pins it
    with ``keep_dir`` (repeat runs reuse the dataset)."""
    work = keep_dir or tempfile.mkdtemp(prefix="tpuic_regress_train_")
    try:
        return _train_workload_in(work, steps, faults)
    finally:
        if keep_dir is None:
            shutil.rmtree(work, ignore_errors=True)


def _train_workload_in(work: str, steps: int,
                       faults: str) -> Dict[str, float]:
    from tpuic.data.synthetic import make_synthetic_imagefolder
    from tpuic.metrics.meters import quantiles
    data = os.path.join(work, "data")
    if not os.path.isdir(data):
        make_synthetic_imagefolder(data, classes=("a", "b", "c"),
                                   per_class=8, size=32)
    jsonl = os.path.join(work, "events.jsonl")
    if os.path.exists(jsonl):
        os.unlink(jsonl)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    env.pop("TPUIC_TRACE", None)
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
           "--steps", str(steps), "--metrics-jsonl", jsonl]
    proc = subprocess.run(cmd, cwd=_REPO, env=env, text=True,
                          capture_output=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"train workload exited {proc.returncode}:\n"
            f"{proc.stdout[-1500:]}\n{proc.stderr[-1500:]}")
    # The shared tolerant reader (telemetry/events.read_jsonl): a child
    # killed by the timeout can leave a torn tail line; the gate should
    # then fail on its own "telemetry incomplete" diagnosis below, not
    # on a JSONDecodeError.
    from tpuic.telemetry.events import read_jsonl
    recs = read_jsonl(jsonl)
    step_evs = [r for r in recs if r["event"] == "step"]
    finals = [r for r in recs if r["event"] == "goodput" and r.get("final")]
    if len(finals) != 1 or len(step_evs) < 4:
        raise RuntimeError(
            f"train workload telemetry incomplete: {len(step_evs)} step "
            f"events, {len(finals)} final goodput reports")
    rep = finals[0]
    steady = [r["total_ms"] for r in step_evs[2:]]
    qs = quantiles(steady, (50, 99))
    out = {
        "train.step_p50_ms": qs["p50"],
        "train.step_p99_ms": qs["p99"],
        "train.frac_productive": rep.get("frac_productive"),
        "train.accounted_frac": rep.get("accounted_frac"),
        "train.mfu": rep.get("mfu"),
    }
    return {k: float(v) for k, v in out.items() if v is not None}


def serve_workload(requests: int = 48, *, size: int = 16,
                   buckets: Sequence[int] = (1, 4, 8),
                   max_wait_ms: float = 2.0, seed: int = 0,
                   forward_fn=None) -> Dict[str, float]:
    """Drive the real InferenceEngine with the pinned mixed-size request
    stream and distill the gated serve metrics.

    Two passes: an as-fast pass measures throughput, then a paced pass
    at HALF that throughput measures latency/pad efficiency — pacing
    relative to the machine's own capacity keeps the latency numbers
    comparable across machine speeds (the calibration scale covers the
    rest).  The real-model path then repeats the paced pass once per
    quantized ladder rung (bf16/int8 via tpuic.quant) for the
    ``serve.<dtype>_latency_*`` rows, with every rung's compiles folded
    into the exact ``serve.steady_compiles`` counter.  ``forward_fn``
    overrides the default small-model forward (tests use a stub to stay
    fast — the stub path skips the ladder rows, which then compare as
    'missing' rather than regressed)."""
    import numpy as np

    from tpuic.serve import InferenceEngine, loadgen

    variants = {}
    if forward_fn is None:
        import jax
        import jax.numpy as jnp

        from tpuic import quant
        from tpuic.models import create_model
        from tpuic.serve import make_forward
        model = create_model("resnet18-cifar", 10, dtype="float32")
        variables = model.init(jax.random.key(0),
                               jnp.zeros((1, size, size, 3), jnp.float32),
                               train=False)
        forward, fwd_vars = make_forward(model, normalize=True), variables
        variants = {k: v for k, v in quant.serve_variants(
            model, variables, ("fp32", "bf16", "int8"),
            normalize=True).items() if k != "fp32"}
    else:
        forward, fwd_vars = forward_fn, {}
    rng = np.random.default_rng(seed)
    reqs = [rng.integers(0, 256, (int(rng.integers(1, buckets[-1] + 1)),
                                  size, size, 3), np.uint8)
            for _ in range(requests)]
    engine = InferenceEngine(
        forward_fn=forward, variables=fwd_vars, image_size=size,
        input_dtype=np.uint8, buckets=tuple(buckets),
        max_wait_ms=max_wait_ms, queue_size=max(64, requests),
        variants=variants)
    try:
        # Shared warmup helper (tpuic/compiled/) — same registry-backed
        # AOT path bench_serve.py warms through.
        from tpuic.compiled import warm_engine
        warm_engine(engine)

        def run(rate: float, dtype=None) -> dict:
            # The shared bench/gate driver (tpuic/serve/loadgen.py): the
            # gate measures with exactly the harness bench_serve.py uses.
            offsets = ([i / rate for i in range(len(reqs))]
                       if rate > 0 else None)
            items = (reqs if dtype is None
                     else [(r, {"dtype": dtype}) for r in reqs])
            wall, _, snap = loadgen.run_stream(engine, items,
                                               offsets_s=offsets)
            snap["_wall_s"] = wall
            return snap

        fast = run(0.0)
        images = sum(r.shape[0] for r in reqs)
        throughput = images / fast["_wall_s"]
        paced_rate = max(1.0, 0.5 * (len(reqs) / fast["_wall_s"]))
        paced = run(paced_rate)
        # stats.reset() zeroes the compile counter per pass, so this is
        # exactly "executables built AFTER warmup" — the AOT contract.
        steady_compiles = fast["compiles"] + paced["compiles"]
        out = {
            "serve.latency_p50_ms": float(paced["latency_ms"]["p50"]),
            "serve.latency_p99_ms": float(paced["latency_ms"]["p99"]),
            "serve.throughput_images_per_sec": round(throughput, 2),
            "serve.pad_efficiency": float(paced["pad_efficiency"]),
        }
        for tag in sorted(variants):
            rung = run(paced_rate, dtype=tag)
            steady_compiles += rung["compiles"]
            out[f"serve.{tag}_latency_p50_ms"] = \
                float(rung["latency_ms"]["p50"])
            out[f"serve.{tag}_latency_p99_ms"] = \
                float(rung["latency_ms"]["p99"])
        out["serve.steady_compiles"] = float(steady_compiles)
        return out
    finally:
        engine.close()


def run_workloads(*, steps: int = 8, requests: int = 48,
                  inject: Sequence[str] = (), skip_train: bool = False,
                  skip_serve: bool = False,
                  serve_forward_fn=None) -> Dict[str, float]:
    """One fresh measurement of every gated metric.  ``inject`` seeds
    deterministic faults: ``slow_step`` (train child, 0.3 s/step) and
    ``hang_device`` (in-process serve engine, 0.25 s/dispatch) — each
    sized to overwhelm its metric's tolerance by a wide margin, so the
    bidirectional proof tests the gate, not the jitter."""
    from tpuic.runtime import faults

    metrics: Dict[str, float] = {}
    if not skip_train:
        train_faults = "slow_step#0.3" if "slow_step" in inject else ""
        metrics.update(train_workload(steps, faults=train_faults))
    if not skip_serve:
        armed = "hang_device" in inject
        if armed:
            faults.arm("hang_device", param=0.25)
        try:
            metrics.update(serve_workload(requests,
                                          forward_fn=serve_forward_fn))
        finally:
            if armed:
                faults.disarm("hang_device")
    return metrics


# -- baseline + comparison ----------------------------------------------------
def make_baseline(trials: Sequence[Dict[str, float]],
                  calibration: float, workload: dict) -> dict:
    """Median value + relative spread per metric across trials."""
    names = sorted({k for t in trials for k in t})
    metrics = {}
    for name in names:
        vals = sorted(t[name] for t in trials if name in t)
        if not vals:
            continue
        med = vals[len(vals) // 2]
        spread = ((vals[-1] - vals[0]) / abs(med)) if med else 0.0
        metrics[name] = {"value": med, "noise": round(spread, 4)}
    return {"schema": SCHEMA, "written_at_unix": int(time.time()),
            "calibration_s": round(calibration, 6),
            "trials": len(trials), "workload": workload,
            "metrics": metrics}


def compare(baseline: dict, fresh: Dict[str, float],
            fresh_calibration: float, specs: Optional[dict] = None) -> dict:
    """Fresh metrics vs the committed baseline under the tolerance
    ladder.  Returns a report dict; ``report["regressed"]`` is the gate
    verdict and each regressed row names its metric — the CI failure
    message is the report, not a bare exit code.

    ``specs`` overrides the gated-metric table (same shape as
    METRIC_SPECS) — the roofline gate (telemetry/profile.py) runs its
    op-class metrics through this exact machinery instead of growing a
    second calibration/tolerance implementation."""
    base_cal = float(baseline.get("calibration_s") or 0.0)
    scale = 1.0
    mismatch = None
    cal_note = "no baseline calibration — absolute comparison"
    if base_cal > 0 and fresh_calibration > 0:
        scale = fresh_calibration / base_cal
        cal_note = (f"machine speed scale {scale:.3f} "
                    f"(fresh {fresh_calibration * 1e3:.1f} ms / baseline "
                    f"{base_cal * 1e3:.1f} ms)")
        if 0.75 <= scale <= 1.33:
            # Same-machine band: the two calibrations agree within their
            # own noise, so scaling by their ratio would only inject that
            # noise into every expectation.  Snap to 1.
            scale = 1.0
            cal_note += " — within same-machine band, snapped to 1.0"
        elif not (1.0 / CAL_CLAMP <= scale <= CAL_CLAMP):
            # Beyond the comparability clamp the machines are NOT
            # comparable: every absolute-time row would fail (or pass)
            # identically at seed and tip, which reads as a regression
            # verdict but means nothing.  Typed environment_mismatch
            # verdict instead (main() exits 3, distinct from regression
            # exit 2); the rows below are still computed with the
            # clamped scale for the report's diagnostic value.
            mismatch = {"scale": round(scale, 4), "clamp": CAL_CLAMP,
                        "fresh_calibration_s": round(fresh_calibration, 6),
                        "baseline_calibration_s": round(base_cal, 6)}
            scale = min(max(scale, 1.0 / CAL_CLAMP), CAL_CLAMP)
            cal_note += f" — CLAMPED to {scale:.3f}: machines barely comparable"
    rows = []
    for name, (direction, kind, floor) in (specs or METRIC_SPECS).items():
        b = (baseline.get("metrics") or {}).get(name)
        f = fresh.get(name)
        if b is None or f is None:
            rows.append({"metric": name, "status": "missing",
                         "baseline": None if b is None else b["value"],
                         "fresh": f})
            continue
        base_v, noise = float(b["value"]), float(b.get("noise", 0.0))
        if kind == "time":
            expected = base_v * scale
        elif kind == "rate":
            expected = base_v / scale
        else:
            expected = base_v
        if kind == "count":
            # Exact counter: absolute allowance, no noise band.
            regressed = f > base_v + floor
            tol, ratio = floor, f - base_v
        else:
            tol = max(floor, NOISE_MULT * noise)
            ratio = (f / expected) if expected else float("inf")
            if direction == "lower":
                regressed = f > expected * (1.0 + tol)
            else:
                regressed = f < expected * (1.0 - tol)
        rows.append({"metric": name, "status":
                     "REGRESSED" if regressed else "ok",
                     "baseline": base_v, "expected": round(expected, 4),
                     "fresh": round(f, 4), "ratio": round(ratio, 4),
                     "tolerance": round(tol, 4), "direction": direction,
                     "kind": kind, "noise": noise})
    bad = [r for r in rows if r["status"] == "REGRESSED"]
    out = {"regressed": bool(bad),
           "regressed_metrics": [r["metric"] for r in bad],
           "calibration": cal_note, "scale": round(scale, 4),
           "rows": rows}
    if mismatch is not None:
        out["environment_mismatch"] = mismatch
    return out


def verdict_exit(report: dict, expect_fail: bool = False) -> int:
    """The gate's exit code for a :func:`compare` report.

    3 — ``environment_mismatch``: the host is outside the ``CAL_CLAMP``
        comparability clamp, so pass/fail would be identical at seed
        and tip; the typed verdict REFUSES to judge (and overrides
        ``--expect-fail``: a gate that cannot fire meaningfully cannot
        prove it fires either).  Distinct from regression exit 2, so CI
        and humans can tell "this PR is slow" from "this host is".
    2 — a gated metric regressed (or, under ``expect_fail``, the seeded
        slowdown failed to trip the gate).
    0 — clean (or, under ``expect_fail``, the expected failure fired).
    """
    if report.get("environment_mismatch"):
        return 3
    if expect_fail:
        return 0 if report["regressed"] else 2
    return 2 if report["regressed"] else 0


def _print_report(report: dict) -> None:
    print(f"[regress] {report['calibration']}")
    for r in report["rows"]:
        if r["status"] == "missing":
            print(f"[regress]   {r['metric']:<36} MISSING "
                  f"(baseline={r['baseline']}, fresh={r['fresh']})")
            continue
        arrow = "v" if r["direction"] == "lower" else "^"
        print(f"[regress]   {r['metric']:<36} {r['status']:<9} "
              f"base={r['baseline']:<10g} expected={r['expected']:<10g} "
              f"fresh={r['fresh']:<10g} ratio={r['ratio']:<7g} "
              f"tol={r['tolerance']:g} ({arrow} better)")
    if report["regressed"]:
        print(f"[regress] REGRESSION in: "
              f"{', '.join(report['regressed_metrics'])}")
    else:
        print("[regress] clean: no gated metric regressed")


def _force_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()


DEFAULT_BASELINE = os.path.join(_REPO, "perf", "regression_baseline.json")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpuic.telemetry.regress", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument("--check", action="store_true",
                      help="run the pinned workload and compare against "
                           "the committed baseline; exit 2 on regression, "
                           "3 when the host is outside the calibration "
                           "comparability clamp (environment_mismatch — "
                           "no verdict, not a regression)")
    mode.add_argument("--write-baseline", action="store_true",
                      help="run --trials trials of the workload and "
                           "(re)write the baseline file")
    p.add_argument("--baseline", default=DEFAULT_BASELINE)
    p.add_argument("--report", default="",
                   help="write the fresh-vs-baseline comparison JSON "
                        "here (the CI artifact)")
    p.add_argument("--trials", type=int, default=3,
                   help="trials for --write-baseline (noise bands)")
    p.add_argument("--steps", type=int, default=8,
                   help="train workload optimizer steps")
    p.add_argument("--requests", type=int, default=48,
                   help="serve workload request count")
    p.add_argument("--inject", default="",
                   help="comma list of faults to seed (slow_step, "
                        "hang_device) — the gate-can-fire proof")
    p.add_argument("--expect-fail", action="store_true",
                   help="with --check: exit 0 IFF the comparison "
                        "regressed (inverted gate, for CI to prove the "
                        "gate fires under --inject)")
    p.add_argument("--skip-train", action="store_true")
    p.add_argument("--skip-serve", action="store_true")
    args = p.parse_args(argv)

    _force_cpu()
    inject = tuple(s.strip() for s in args.inject.split(",") if s.strip())
    unknown = set(inject) - {"slow_step", "hang_device"}
    if unknown:
        p.error(f"--inject: unknown fault(s) {sorted(unknown)} "
                "(supported: slow_step, hang_device)")
    workload_desc = {"train_steps": args.steps,
                     "serve_requests": args.requests,
                     "serve_size": 16, "serve_buckets": [1, 4, 8]}

    if args.write_baseline:
        cal = calibration_s()
        trials = []
        for i in range(max(1, args.trials)):
            print(f"[regress] baseline trial {i + 1}/{args.trials} ...",
                  flush=True)
            trials.append(run_workloads(
                steps=args.steps, requests=args.requests,
                skip_train=args.skip_train, skip_serve=args.skip_serve))
        baseline = make_baseline(trials, cal, workload_desc)
        os.makedirs(os.path.dirname(args.baseline) or ".", exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[regress] baseline ({len(baseline['metrics'])} metrics, "
              f"{args.trials} trials, calibration "
              f"{cal * 1e3:.1f} ms) -> {args.baseline}")
        return 0

    # --check
    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except OSError as e:
        print(f"[regress] cannot read baseline {args.baseline}: {e}\n"
              f"[regress] run --write-baseline first", file=sys.stderr)
        return 3
    if inject:
        print(f"[regress] seeding fault(s): {', '.join(inject)}")
    cal = calibration_s()
    fresh = run_workloads(steps=args.steps, requests=args.requests,
                          inject=inject, skip_train=args.skip_train,
                          skip_serve=args.skip_serve)
    report = compare(baseline, fresh, cal)
    report["fresh_metrics"] = fresh
    report["injected"] = list(inject)
    report["expect_fail"] = bool(args.expect_fail)
    _print_report(report)
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[regress] comparison -> {args.report}")
    code = verdict_exit(report, expect_fail=args.expect_fail)
    if code == 3:
        em = report["environment_mismatch"]
        print(f"[regress] ENVIRONMENT MISMATCH: this host's calibration "
              f"is {em['scale']:g}x the baseline's — beyond the "
              f"{em['clamp']:g}x comparability clamp. Seed and tip would "
              f"fail identically here; refusing a pass/fail verdict "
              f"(exit 3, distinct from regression exit 2). Re-baseline "
              f"on this host class or gate on a comparable runner.",
              file=sys.stderr)
    elif args.expect_fail:
        if code == 0:
            print("[regress] expected failure observed — the gate can "
                  "fire (bidirectional proof OK)")
        else:
            print("[regress] ERROR: seeded slowdown did NOT trip the "
                  "gate — the gate is decoration", file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main())
