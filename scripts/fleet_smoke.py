#!/usr/bin/env python
"""Fleet observability smoke (ISSUE 9 acceptance; runs in tier-1 CI).

End-to-end proof of the per-rank fleet view (docs/observability.md,
"Fleet view"): TWO real ``train.py`` processes run the same pinned CPU
workload as a rank-identified fleet, rank 1 seeded slow via the
existing ``slow_step#`` fault point (runtime/faults.py), and the
offline aggregator (``python -m tpuic.telemetry.fleet``) must attribute
the straggler to the correct rank:

- every event in each rank's JSONL stream carries ``rank``/``ranks``
  fields, and the streams land side by side as ``events.jsonl`` /
  ``events.rank1.jsonl`` (the per-rank naming convention);
- the aggregator's skew ledger sees the seeded slowdown: per-step
  cross-rank spread at least half the injected stall, rank 1 slowest in
  (nearly) every step, and the straggler verdict — asserted through the
  real CLI (``--expect-straggler 1``), the same invocation an operator
  would run against a pod's shared metrics directory.

Rank identity rides the ``TPUIC_FLEET_RANK(S)`` launcher override: this
container's CPU jax implements no multiprocess collectives (the
tests/test_multiprocess caveat), so the two ranks train independently —
which is exactly what the skew math wants anyway (host walls free of
cross-rank equalization; see the fleet module docstring's measurement
caveat).  On a real pod the tag comes from runtime/distributed.py and
the same aggregator runs unchanged.

Exit 0 on success.   python scripts/fleet_smoke.py [--keep] [-v]
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

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

RANKS = 2
SLOW_RANK = 1
STEPS = 8
WARMUP = 2  # compile/cache warmup steps excluded from the skew math


def _train_cmd(data: str, work: str, rank: int) -> list:
    return [sys.executable, os.path.join(_REPO, "train.py"),
            "--datadir", data, "--model", "resnet18-cifar",
            "--resize", "24", "--batchsize", "2",
            "--epochs", "1", "--optimizer", "sgd", "--lr", "0.01",
            "--no-class-weights", "--no-pack",
            # Free-running hosts: per-step drains (log_every 1) would
            # equalize host step walls across a synchronized fleet; the
            # production cadence keeps the skew visible per step.
            "--log-every-steps", "999",
            "--workers", "2", "--save-period", "99",
            "--steps", str(STEPS),
            "--ckpt-dir", os.path.join(work, f"cp{rank}"),
            "--metrics-jsonl", os.path.join(work, "events.jsonl")]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--slow-s", type=float, default=0.5,
                   help="seeded per-step stall on the straggler rank")
    p.add_argument("--keep", action="store_true")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args()

    t0 = time.monotonic()
    work = tempfile.mkdtemp(prefix="tpuic_fleet_")
    failures: list = []

    def check(ok: bool, msg: str) -> None:
        print(("  ok  " if ok else "  FAIL") + f" {msg}")
        if not ok:
            failures.append(msg)

    try:
        from tpuic.data.synthetic import make_synthetic_imagefolder
        data = os.path.join(work, "data")
        # 2 classes x 16 / batch 2 = 16 steps/epoch; --steps 8 stops
        # mid-epoch (train-only — no val, no checkpoint churn).
        make_synthetic_imagefolder(data, classes=("a", "b"), per_class=16,
                                   size=24)
        base_env = dict(os.environ, JAX_PLATFORMS="cpu",
                        TF_CPP_MIN_LOG_LEVEL="3", XLA_FLAGS="",
                        TPUIC_FLEET_RANKS=str(RANKS))
        # Both ranks compile the same program: share the persistent cache
        # so the second compile is a hit. A directory given from outside wins.
        base_env.setdefault("JAX_COMPILATION_CACHE_DIR",
                            os.path.join(work, "jax_cache"))
        sink = None if args.verbose else subprocess.DEVNULL
        print(f"[fleet_smoke] launching {RANKS} ranks "
              f"(rank {SLOW_RANK} seeded slow_step#{args.slow_s:g})")
        procs = []
        for rank in range(RANKS):
            env = dict(base_env, TPUIC_FLEET_RANK=str(rank))
            if rank == SLOW_RANK:
                env["TPUIC_FAULTS"] = f"slow_step#{args.slow_s}"
            procs.append(subprocess.Popen(
                _train_cmd(data, work, rank), cwd=_REPO, env=env,
                stdout=sink, stderr=sink))
        for rank, proc in enumerate(procs):
            rc = proc.wait(timeout=900)
            check(rc == 0, f"rank {rank} train.py exited cleanly (got {rc})")
        if failures:
            return 1

        # Per-rank streams, rank-tagged events.
        from tpuic.telemetry.events import read_jsonl
        from tpuic.telemetry.fleet import rank_stream_path
        streams = {}
        for rank in range(RANKS):
            path = rank_stream_path(os.path.join(work, "events.jsonl"), rank)
            recs = read_jsonl(path)
            streams[rank] = recs
            steps = [r for r in recs if r.get("event") == "step"]
            check(len(steps) == STEPS,
                  f"rank {rank} stream has {len(steps)} step events "
                  f"(want {STEPS}) in {os.path.basename(path)}")
            check(all(r.get("rank") == rank and r.get("ranks") == RANKS
                      for r in recs),
                  f"every rank-{rank} event carries rank={rank}/"
                  f"ranks={RANKS}")
            mems = [r for r in recs if r.get("event") == "memory"]
            check(len(mems) >= STEPS and all(
                      m.get("bytes_in_use", 0) > 0 for m in mems),
                  f"rank {rank} sampled device memory at step boundaries "
                  f"({len(mems)} samples)")

        # The aggregator verdict, through the REAL CLI — the operator
        # invocation, not a private API.
        report_path = os.path.join(work, "fleet_report.json")
        cli = subprocess.run(
            [sys.executable, "-m", "tpuic.telemetry.fleet", work,
             "--warmup", str(WARMUP), "--json", report_path,
             "--expect-straggler", str(SLOW_RANK)],
            cwd=_REPO, env=base_env, text=True, capture_output=True,
            timeout=120)
        print(cli.stdout, end="")
        check(cli.returncode == 0,
              f"aggregator CLI attributed the straggler to rank "
              f"{SLOW_RANK} (exit {cli.returncode}; stderr: "
              f"{cli.stderr.strip()[-200:]})")
        rep = json.load(open(report_path)) if os.path.exists(report_path) \
            else {}
        common = rep.get("steps_common", 0)
        check(common == STEPS - WARMUP,
              f"{common} common steps entered the skew math "
              f"(want {STEPS - WARMUP})")
        spread = (rep.get("spread_ms") or {}).get("p50", 0.0)
        check(spread >= 1000.0 * args.slow_s * 0.5,
              f"p50 cross-rank spread {spread:g} ms reflects the seeded "
              f"{1000 * args.slow_s:g} ms stall")
        strag = rep.get("straggler") or {}
        check(strag.get("slowest_step_frac", 0.0) >= 0.8,
              f"straggler rank was slowest in "
              f"{100 * strag.get('slowest_step_frac', 0):g}% of steps")
        wait_ms = (rep.get("per_rank", {}).get(str(SLOW_RANK), {})
                   .get("est_collective_wait_ms", 0.0))
        check(wait_ms >= (STEPS - WARMUP) * 1000 * args.slow_s * 0.5,
              f"rank {SLOW_RANK} est collective wait {wait_ms:g} ms "
              f"covers the injected stall")

        took = time.monotonic() - t0
        if failures:
            print(f"\nFAIL: {len(failures)} assertion(s) in {took:.1f}s")
            for f in failures:
                print(f"  - {f}")
            return 1
        print(f"\nOK: fleet smoke green in {took:.1f}s — rank "
              f"{SLOW_RANK} attributed as straggler "
              f"({strag.get('excess_share', 0):.0%} of fleet excess, "
              f"spread p50 {spread:g} ms)")
        return 0
    finally:
        if args.keep:
            print(f"workdir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
