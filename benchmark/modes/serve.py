"""Mode ``serve``: open-loop Poisson load into the in-process engine.

The system under test is the warmed ``InferenceEngine`` that
``tpuic.serve.__main__.build_engine`` returns under ``--synthetic-init``,
driven through ``engine.submit`` with uint8 images: the object behind a
user's ``python -m tpuic.serve``, without the wire. Load comes from this
one process's main thread at a rate fixed in the traffic file; the engine's
own batcher thread does the rest.

A traced run measures ``--seconds`` less the slice un-profiled (that part
gives the host-clock and counter metrics), then offers the same traffic
for the slice with the profiler on; no request is due while the profiler
starts or stops.
"""

from __future__ import annotations

import argparse
import os
import queue
import time

import numpy as np

from benchmark import harness, loadgen
from benchmark.stats import percentile


def build(ctx):
    """The engine, as ``python -m tpuic.serve --synthetic-init ...`` builds
    and warms it (``build_engine`` takes the parsed flags of that CLI)."""
    from tpuic.serve.__main__ import build_engine
    mix, flags = ctx.traffic, ctx.config["serve_flags"]
    # build_engine turns the compile cache on itself, at the program's own
    # threshold: its eager model.init compiles again in every run.
    ns = argparse.Namespace(
        synthetic_init=True, model=flags["model"],
        num_classes=int(flags["num_classes"]), resize=int(flags["resize"]),
        buckets=mix["buckets"], serve_dtypes=mix["serve_dtypes"],
        max_wait_ms=float(mix["max_wait_ms"]),
        queue_size=int(mix["queue_size"]), compile_cache_dir="",
        ckpt_dir=os.path.join(ctx.work_dir, "ckpt"), track="latest",
        init_from="")
    engine, resize, _, _ = build_engine(ns)
    return engine, int(resize)


def make_stream(mix: dict, size: int, rate: float, duration_s: float,
                seed: int):
    """(requests, due times): a seeded stream for ``duration_s``."""
    rng = np.random.default_rng(seed)
    due = loadgen.poisson_offsets(rate, duration_s, rng)
    rows = loadgen.request_sizes(len(due), mix["size_mix"], rng)
    pool = loadgen.image_pool(int(mix["pool_images"]), size, rng)
    starts = rng.integers(0, len(pool) - int(rows.max()) + 1, size=len(due))
    return [pool[s:s + n] for s, n in zip(starts, rows)], due


def offer(engine, mix: dict, requests, due) -> loadgen.Outcome:
    from tpuic.serve.admission import AdmissionError
    dtype = mix["request_dtype"]

    def submit(images):
        with harness.annotation("submit"):
            # timeout=0: a full queue refuses at once instead of blocking
            # the generator, and the refusal is counted.
            return engine.submit(images, dtype=dtype, timeout=0)
    return loadgen.drive(submit, requests, due,
                         rejected=(AdmissionError, queue.Full),
                         settle_s=float(mix["settle_s"]),
                         rows_of=lambda result: int(result[0].shape[0]))


def summarize(out: loadgen.Outcome, window_s: float) -> dict:
    """End-to-end numbers of one drive; latencies in ms from the due time,
    an unanswered, rejected or failed request infinitely late."""
    lat_ms = out.latency * 1e3
    in_window = np.isfinite(out.done) & (out.done <= window_s)
    return {
        "serve_p50_ms": percentile(lat_ms, 50),
        "serve_p99_ms": percentile(lat_ms, 99),
        "serve_images_per_s": float(out.rows[in_window].sum()) / window_s,
        "offered": len(out.status),
        "answered": out.status.count("ok"),
        "rejected": out.status.count("rejected"),
        "failed": out.status.count("failed"),
        "unanswered": out.status.count("unanswered"),
        "wrong_rows": int(np.sum((out.rows_back != out.rows)
                                 & np.isfinite(out.latency))),
        "late_ms_p99": percentile(out.late * 1e3, 99),
        "beyond_p99": len(lat_ms) - int(np.ceil(0.99 * len(lat_ms))),
    }


def warm(engine, mix: dict, size: int) -> None:
    """One answered request of every size in the mix and one burst that
    fills the largest bucket, so no executable or staging buffer is met
    for the first time inside the window."""
    pool = np.zeros((max(engine.buckets), size, size, 3), np.uint8)
    futs = [engine.submit(pool[:int(n)], dtype=mix["request_dtype"])
            for n in mix["size_mix"]]
    futs += [engine.submit(pool[:1], dtype=mix["request_dtype"])
             for _ in range(max(engine.buckets))]
    for f in futs:
        f.result(timeout=120)


def _reference_check(ctx, engine, size: int) -> list:
    """Seeded requests answered by the engine outside the window against
    the plain float32 reference on the engine's own weights: the
    log-probabilities must agree up to rounding in the rung's dtype."""
    import jax
    from tpuic.data.transforms import IMAGENET_MEAN, IMAGENET_STD
    ref = harness.load_reference(ctx.config["reference"])
    mix = ctx.traffic
    variables = getattr(engine, "_variables", None)
    if variables is None:
        return ["the engine exposes no variables to run the reference on"]
    variables = harness.plain_variables(variables)
    reqs, _ = make_stream(mix, size, 1000.0, 1.0, ctx.seed + 1)
    reqs = reqs[:int(mix["reference_requests"])]
    answers = [engine.submit(r, dtype=mix["request_dtype"]).result(
        timeout=120) for r in reqs]
    rows = sum(len(r) for r in reqs)
    # One fixed shape whatever the seed drew, so that the reference's
    # program is compiled once and found in the cache ever after.
    padded = np.zeros((len(reqs) * max(int(n) for n in mix["size_mix"]),
                       size, size, 3), np.float32)
    padded[:rows] = np.concatenate(reqs)
    images = (padded / 255.0 - np.asarray(IMAGENET_MEAN, np.float32)) \
        / np.asarray(IMAGENET_STD, np.float32)
    want = jax.nn.log_softmax(jax.jit(
        lambda v, x: ref.forward(v, x, ctx.config))(variables, images))[:rows]
    got = np.log(np.concatenate([np.asarray(a[0]) for a in answers]))
    err = harness.centred_error(got, want)
    tol = float(ctx.config["reference_tolerance"])
    ctx.say(f"reference: {len(reqs)} requests ({rows} images) on "
            f"the {mix['request_dtype']} rung vs plain float32: centred "
            f"relative error of log-probabilities {err:.5f} "
            f"(tolerance {tol})")
    if not err <= tol:
        return [f"the engine's answers differ from the plain float32 "
                f"reference by {err:.5f} > {tol}"]
    return []


def run(ctx) -> harness.ModeResult:
    mix = ctx.traffic
    rate = float(mix["rate_req_per_s"])
    engine, size = build(ctx)
    try:
        warm(engine, mix, size)
        slice_s = min(float(mix["trace_max_s"]), 0.25 * ctx.seconds) \
            if ctx.trace else 0.0
        window_s = ctx.seconds - slice_s
        requests, due = make_stream(mix, size, rate, window_s, ctx.seed)
        extra = make_stream(mix, size, rate, slice_s, ctx.seed + 2) \
            if ctx.trace else None
        engine.stats.reset()
        ctx.open_window()
        with harness.annotation("drive"):
            out = offer(engine, mix, requests, due)
        ctx.close_window()
        # A future resolves before the batcher books it: let the counters
        # catch up with the answers.
        t_settle = time.perf_counter() + 2.0
        while (engine.stats.snapshot()["requests"] < out.status.count("ok")
               and time.perf_counter() < t_settle):
            time.sleep(0.01)
        stats = engine.stats.snapshot()
        spans = ctx.base_spans()    # before the slice: its counts are apart
        e2e = summarize(out, window_s)
        ctx.say(f"window: {window_s:.1f} s at {rate:g} req/s: "
                + ", ".join(f"{k}={v:.4g}" if isinstance(v, float)
                            else f"{k}={v}" for k, v in e2e.items()))
        trace = None
        if ctx.trace:
            prof = harness.ProfiledSlice(ctx)
            prof.start()
            try:
                with harness.annotation("drive"):
                    offer(engine, mix, *extra)
            finally:
                prof.stop()
            trace = prof.reduce()
        problems = _reference_check(ctx, engine, size)
    finally:
        engine.close()

    failed = e2e["rejected"] + e2e["failed"] + e2e["unanswered"]
    if e2e["offered"] != e2e["answered"] + failed:
        problems.append("offered != answered + rejected + failed + "
                        f"unanswered: {e2e}")
    if stats["requests"] != e2e["answered"]:
        problems.append(f"the engine counted {stats['requests']} requests, "
                        f"the generator {e2e['answered']} answers")
    if e2e["wrong_rows"]:
        problems.append(f"{e2e['wrong_rows']} answers with another row "
                        "count than their request")
    if e2e["late_ms_p99"] > float(mix["late_limit_ms"]):
        problems.append(f"the generator ran late: p99 {e2e['late_ms_p99']:.2f}"
                        f" ms > {mix['late_limit_ms']} ms; the run is void")
    if spans["compiles_in_window"] or stats["compiles"]:
        problems.append(f"compiles inside the window: "
                        f"{spans['compiles_in_window']} by jax.monitoring, "
                        f"{stats['compiles']} by the engine")
    spans.update(late_ms_p99=e2e["late_ms_p99"],
                 device_kind=ctx.devices[0].device_kind,
                 latency_samples=e2e["offered"],
                 samples_beyond_p99=e2e["beyond_p99"])
    return harness.ModeResult(
        end_to_end={k: e2e[k] for k in ("serve_p50_ms", "serve_p99_ms",
                                        "serve_images_per_s")},
        attempted=e2e["offered"], failed=failed, problems=problems,
        obs=harness.Observations(step_events=[], engine_stats=stats,
                                 trace=trace, spans=spans))
