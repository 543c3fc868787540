#!/usr/bin/env python3
"""Find the knee of a serve cell: the highest offered rate it sustains.

    python3 benchmark/find_knee.py --workload <serve cell>

Builds the cell's engine once, then offers the cell's own traffic (its size
mix, rung and seed arithmetic, through the benchmark's own generator) at
each rate of a geometric ladder, one window per rate. A rate is sustained
when at least ``--attain`` of its requests were answered within
``--limit-ms`` of their due time (a rejected or unanswered request never
is) and the backlog did not grow: the median latency of the window's last
quarter is no more than twice that of its first quarter plus 5 ms. The knee
is the highest sustained rate below the first that is not; the sweep stops
after two rates in a row that are not.

The benchmark never searches for a rate inside a run: a steady cell is
fixed at 0.8 x the knee found here, an overload cell above it, and the
number goes into the traffic file by hand. Run this again, with the same
code, when an accepted change has moved the knee.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def sustained(out, limit_ms: float, attain: float) -> dict:
    import numpy as np
    from benchmark.stats import median, percentile
    lat = out.latency * 1e3
    n = len(lat)
    order = np.argsort(out.due)
    first, last = lat[order[:n // 4]], lat[order[-(n // 4):]]
    share = float(np.mean(lat <= limit_ms))
    m_first, m_last = median(first), median(last)
    return {"requests": n, "within_limit": share,
            "p50_ms": percentile(lat, 50), "p99_ms": percentile(lat, 99),
            "first_quarter_p50_ms": m_first, "last_quarter_p50_ms": m_last,
            "late_ms_p99": percentile(out.late * 1e3, 99),
            "rejected": out.status.count("rejected"),
            "unanswered": out.status.count("unanswered"),
            "sustained": bool(share >= attain
                              and m_last <= 2.0 * m_first + 5.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec", default="BENCHMARK.json",
                    help="the file that defines the cell, relative to the "
                         "checkout (benchmark/candidates.json: cells built "
                         "and measured but not admitted)")
    ap.add_argument("--start", type=float, default=300.0,
                    help="first rate of the ladder, requests/s")
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--rates", type=int, default=12,
                    help="at most this many rungs")
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="window per rate")
    ap.add_argument("--limit-ms", type=float, default=50.0)
    ap.add_argument("--attain", type=float, default=0.99)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from benchmark import harness
    from benchmark.modes import serve
    resolved = harness.resolve_cell(harness.load_spec(args.spec),
                                    args.workload, tiny=args.tiny)
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.tiny:
        print(f"find_knee: no TPU ({platform}); a knee from another "
              "platform means nothing", file=sys.stderr)
        return 3
    ctx = harness.Context(resolved, seed=args.seed, seconds=args.seconds,
                          trace=False, tiny=args.tiny, t_start=T_START)
    mix = ctx.traffic
    engine, size = serve.build(ctx)
    table, misses, rate = [], 0, args.start
    try:
        serve.warm(engine, mix, size)
        for rung in range(args.rates):
            reqs, due = serve.make_stream(mix, size, rate, args.seconds,
                                          args.seed + rung)
            engine.stats.reset()
            out = serve.offer(engine, mix, reqs, due)
            row = {"rate_req_per_s": round(rate, 1),
                   **sustained(out, args.limit_ms, args.attain),
                   "pad_efficiency":
                       engine.stats.snapshot()["pad_efficiency"]}
            table.append(row)
            ctx.say(json.dumps(row))
            misses = 0 if row["sustained"] else misses + 1
            if misses >= 2:
                break
            rate *= args.factor
    finally:
        engine.close()
        ctx.compiles.close()
    knee = None
    for row in table:
        if not row["sustained"]:
            break
        knee = row["rate_req_per_s"]
    dev = jax.devices()[0]
    result = {"workload": args.workload, "knee_req_per_s": knee,
              "steady_rate_req_per_s": None if knee is None
              else round(0.8 * knee, 1),
              "limit_ms": args.limit_ms, "attain": args.attain,
              "seconds_per_rate": args.seconds, "table": table,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()}}
    out_dir = os.path.join(REPO, "chiprun_out")
    if os.path.isdir(out_dir) and dev.platform == "tpu":
        with open(os.path.join(out_dir, "find_knee.json"), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
