#!/usr/bin/env python
"""Rollout soak: the zero-downtime model-lifecycle CI gate
(docs/serving.md, "Model lifecycle: hot-swap, canary, rollback").

Two REAL engine replicas behind the stdlib router, a shared-harness
Poisson storm at the committed knee flowing the whole time, and THREE
lifecycle arms — the gate is bidirectional like every gate in this
repo:

1. **Clean canary promotes.**  A real committed checkpoint (CRC
   manifest and all) rolls out 50% → 100% behind the canary driver and
   promotes.  Asserted: verdict ``promoted``; BOTH replicas' live pongs
   report the candidate digest; the ledger is exact (resolved +
   typed-rejected == offered, zero untyped errors, zero duplicates,
   every outcome hook fired); and **zero steady-state compiles across
   the swap** — each replica's scraped ``tpuic_serve_compiles_total``
   is flat from pre-rollout to post-promote (the aval-matched swap
   reuses the AOT executables), and the soak process itself runs under
   ``assert_compiles_flat``.
2. **Corrupt artifact refused at the gate.**  A copy of the candidate
   with one payload file bit-flipped (``faults.corrupt_file`` — the
   manifest now lies about the bytes) is offered to the same fleet:
   the canary's swap gate must refuse it with the typed
   ``swap_corrupt`` verdict, BEFORE any traffic stage — no split, no
   digest change, and the follow-up wave is still exact.
3. **Degraded canary auto-rolls-back on SLO burn.**  A second fleet is
   spawned with ``canary_degrade`` armed (fires only on non-boot
   weights — exactly the canary, runtime/faults.py): the candidate
   gates clean, goes live on the canary, serves slow, burns the error
   budget, and the driver rolls back.  Asserted: verdict
   ``rolled_back`` (reason ``slo_burn``); the canary's pong is back on
   the boot digest; the ledger is exact through the whole storm (the
   degraded requests RESOLVE — slow, never dropped); and a
   post-rollback wave is healthy and exact.

Artifacts for CI upload on failure: both router state dirs (ledgers
include the ``rollout`` events), the per-replica logs, and the verdict
JSON.

    python scripts/rollout_soak.py --workdir rollout-soak-work
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _force_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()


def _committed_knee() -> float:
    try:
        with open(os.path.join(_REPO, "perf", "bench_serve.json")) as f:
            return float(json.load(f)["open_loop_knee_req_per_sec"] or 0.0)
    except (OSError, ValueError, KeyError, TypeError):
        return 0.0


def _scrape_counter(port, name: str) -> float:
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2.0) as resp:
            text = resp.read().decode("utf-8", "replace")
    except Exception:
        return float("nan")
    for ln in text.splitlines():
        if ln.startswith(name) and not ln.startswith("#"):
            try:
                return float(ln.rsplit(None, 1)[1])
            except (ValueError, IndexError):
                pass
    return float("nan")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workdir", default="rollout-soak-work")
    p.add_argument("--model", default="resnet18-cifar")
    p.add_argument("--size", type=int, default=24)
    p.add_argument("--buckets", default="1,4")
    p.add_argument("--requests", type=int, default=700,
                   help="storm length per rollout arm")
    p.add_argument("--storm-factor", type=float, default=0.8,
                   help="drive = factor x per-replica capacity anchor "
                        "— at the committed knee, NOT past it: the "
                        "lifecycle proof wants mostly-resolved traffic "
                        "feeding the canary's SLO window")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--spawn-timeout-s", type=float, default=600.0)
    args = p.parse_args(argv)

    _force_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuic.analysis.runtime import assert_compiles_flat
    from tpuic.checkpoint.manager import CheckpointManager
    from tpuic.config import OptimConfig
    from tpuic.models import create_model
    from tpuic.runtime import faults
    from tpuic.serve import InferenceEngine, make_forward
    from tpuic.serve.loadgen import probe_unbatched_rps, run_stream
    from tpuic.serve.rollout import CanaryRollout
    from tpuic.serve.router import Router
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import create_train_state

    workdir = os.path.abspath(args.workdir)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    failures = []
    verdicts = {}

    def fail(msg: str) -> None:
        failures.append(msg)
        print(f"[rollout_soak] FAIL: {msg}", file=sys.stderr)

    # ---- capacity anchor + hot compile cache (router_soak discipline)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    model = create_model(args.model, 10, dtype="float32")
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, args.size, args.size, 3),
                                     jnp.float32), train=False)
    probe_engine = InferenceEngine(
        forward_fn=make_forward(model, normalize=True),
        variables=variables, image_size=args.size, input_dtype=np.uint8,
        buckets=buckets, max_wait_ms=5.0, queue_size=256)
    probe_engine.warmup()
    rng = np.random.default_rng(args.seed)
    reqs = [rng.integers(0, 256, (1, args.size, args.size, 3), np.uint8)
            for _ in range(max(args.requests, 400))]
    local_rps, service_s, _, _ = probe_unbatched_rps(probe_engine, reqs)
    probe_engine.close()
    anchor = max(_committed_knee(), local_rps)
    drive_rps = args.storm_factor * anchor

    # ---- the candidate artifact: a REAL committed checkpoint --------
    # Same architecture, different weights (seed 1) — the hot-swap
    # case: aval-identical, so the flip must reuse every executable.
    ckpt_clean = os.path.join(workdir, "ckpt_candidate")
    ocfg = OptimConfig(optimizer="adam", learning_rate=1e-3,
                       class_weights=(), milestones=())
    cand_state = create_train_state(
        model, make_optimizer(ocfg), jax.random.key(1),
        (1, args.size, args.size, 3))
    mgr = CheckpointManager(ckpt_clean, args.model)
    mgr.save_latest(cand_state, epoch=0, best_score=0.0)
    mgr.wait()
    # The corrupt twin: same artifact, one payload file bit-flipped
    # AFTER the manifest was committed — the manifest now lies.
    ckpt_corrupt = os.path.join(workdir, "ckpt_corrupt")
    shutil.copytree(ckpt_clean, ckpt_corrupt)
    track_dir = os.path.join(ckpt_corrupt, args.model, "latest")
    victim, size = None, -1
    for dirpath, _, files in os.walk(track_dir):
        for fn in files:
            fp = os.path.join(dirpath, fn)
            if os.path.getsize(fp) > size:
                victim, size = fp, os.path.getsize(fp)
    faults.corrupt_file(victim)

    replica_cmd = [
        sys.executable, "-m", "tpuic.serve",
        "--synthetic-init", "--model", args.model, "--num-classes", "10",
        "--resize", str(args.size), "--buckets", args.buckets,
        "--max-wait-ms", "5", "--queue-size", "256",
        "--listen", "127.0.0.1:0", "--prom-port", "-1",
        "--drain-timeout", "10",
    ]
    candidate = {"ckpt_dir": ckpt_clean, "track": "latest"}
    incumbent = {"synthetic_seed": 0}

    def storm(router, n, on_done=None):
        """Shared-harness Poisson storm in a thread; returns a join()
        that yields the settled snapshot."""
        items = [(r, {"timeout": 0}) for r in reqs[:n]]
        offsets = np.cumsum(rng.exponential(1.0 / drive_rps, size=n))
        box = {}

        def run():
            box["out"] = run_stream(router, items, offsets_s=offsets,
                                    result_timeout_s=240.0,
                                    on_done=on_done)

        t = threading.Thread(target=run, daemon=True)
        t.start()

        def join(timeout=600.0):
            t.join(timeout=timeout)
            if t.is_alive():
                raise TimeoutError("storm never settled")
            return box["out"]

        return join

    def check_ledger(arm, snap, offered, outcomes=None):
        if snap["requests"] + snap["rejected"] != offered \
                or snap["errors"] != 0:
            fail(f"{arm}: ledger violation — {snap['requests']} resolved"
                 f" + {snap['rejected']} rejected (+{snap['errors']} "
                 f"untyped) != {offered} offered")
        if snap["duplicates"] or snap["wire_errors"]:
            fail(f"{arm}: at-most-once violated — {snap['duplicates']} "
                 f"duplicates, {snap['wire_errors']} wire errors")
        if outcomes is not None and len(outcomes) != offered:
            fail(f"{arm}: outcome hook fired {len(outcomes)}/{offered} "
                 "— some request neither resolved nor got a verdict")

    def wait_digest(router, name, digest, timeout=30.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for rep in router.replicas:
                if rep.name == name and rep.digest == digest:
                    return True
            time.sleep(0.05)
        return False

    # The canary-scoped SLO: machine-relative threshold off the probe
    # (the overload-soak discipline) with a 0.9 target, so a healthy
    # canary holds burn << the 2.0 rollback trigger while a degraded
    # one saturates it.
    thresh_ms = max(250.0, 12_000.0 * service_s)
    slo = f"serve_latency:p99<={thresh_ms:.0f}ms@0.9"
    degrade_s = 3.0 * thresh_ms / 1000.0
    print(f"[rollout_soak] anchors: drive {drive_rps:.1f} req/s, slo "
          f"{slo}, degrade {degrade_s:.2f}s/batch", file=sys.stderr)

    def rollout_for(router, cand):
        return CanaryRollout(
            router, cand, incumbent, objective=slo,
            stages=(0.5, 1.0), hold_s=2.0, min_samples=15,
            burn_rollback=2.0, rollback_after=2, poll_s=0.1,
            stage_timeout_s=120.0, swap_timeout_s=180.0)

    # ================= fleet 1: clean promote + corrupt refusal ======
    router = Router(
        replica_cmd=replica_cmd, n_replicas=2,
        state_dir=os.path.join(workdir, "router"),
        knee_rps=anchor, breaker_threshold=3, breaker_cooldown_s=0.5,
        ping_interval_s=0.1, ping_timeout_s=3.0, wedge_timeout_s=60.0,
        spawn_timeout_s=args.spawn_timeout_s, respawn_backoff_s=0.2,
        grace_s=15.0, drain_timeout_s=30.0)
    router.start(timeout_s=args.spawn_timeout_s)
    try:
        boot_digest = router.fleet_digest
        ports = [r.prom_port for r in router.replicas]
        # warm the socket path, then pin compiles across the WHOLE
        # promote arm (storm + gate + swap + post-promote traffic).
        warm_join = storm(router, 50)
        warm_join()
        compiles0 = [_scrape_counter(pt, "tpuic_serve_compiles_total")
                     for pt in ports]

        outcomes = []
        join = storm(router, args.requests,
                     on_done=lambda i, ok, s: outcomes.append(ok))
        with assert_compiles_flat(0, what="rollout soak promote arm "
                                          "(soak process)"):
            v1 = rollout_for(router, candidate).run()
        _, _, snap1 = join()
        verdicts["promote"] = v1
        if v1.get("verdict") != "promoted":
            fail(f"promote arm: verdict {v1}")
        else:
            cand_digest = v1["digest"]
            if cand_digest == boot_digest:
                fail("promote arm: candidate digest equals boot digest "
                     "— the swap proved nothing")
            for rep in router.replicas:
                if not wait_digest(router, rep.name, cand_digest):
                    fail(f"promote arm: {rep.name} never reported the "
                         f"candidate digest {cand_digest}")
            if router.fleet_digest != cand_digest:
                fail("promote arm: fleet digest not promoted")
        check_ledger("promote arm", snap1, args.requests, outcomes)
        compiles1 = [_scrape_counter(pt, "tpuic_serve_compiles_total")
                     for pt in ports]
        for name, c0, c1 in zip(("r0", "r1"), compiles0, compiles1):
            if c0 != c0 or c1 != c1:
                fail(f"promote arm: {name} compile counter unscrapable")
            elif c1 != c0:
                fail(f"promote arm: {name} compiled {c1 - c0:g} "
                     "executable(s) across the swap — the aval-matched "
                     "hot-swap must reuse the AOT cache")

        # ---- corrupt arm: refused at the gate, pre-traffic ----------
        join = storm(router, 150)
        v2 = rollout_for(router,
                         {"ckpt_dir": ckpt_corrupt,
                          "track": "latest"}).run()
        _, _, snap2 = join()
        verdicts["corrupt"] = v2
        if v2.get("verdict") != "refused" \
                or v2.get("cause") != "swap_corrupt":
            fail(f"corrupt arm: expected a swap_corrupt refusal, got "
                 f"{v2}")
        if router.fleet_digest != verdicts["promote"].get("digest"):
            fail("corrupt arm: fleet digest moved on a refused swap")
        if router.snapshot()["traffic_split"] is not None:
            fail("corrupt arm: a refused candidate left a traffic split")
        check_ledger("corrupt arm", snap2, 150)
        events = []
        try:
            with open(router.ledger_path) as f:
                events = [json.loads(ln) for ln in f if ln.strip()]
        except OSError:
            fail("router ledger unreadable")
        ro = [e for e in events if e.get("event") == "rollout"]
        if not any(e.get("action") == "promote" for e in ro):
            fail("ledger: no rollout promote event")
        refusal = [e for e in ro if e.get("action") == "refused"]
        if not refusal or refusal[-1].get("cause") != "swap_corrupt":
            fail(f"ledger: corrupt refusal not recorded ({refusal})")
        n_stages = [e for e in ro if e.get("action") == "stage"]
        if len(n_stages) != 2:
            fail(f"ledger: expected exactly 2 stage events (the clean "
                 f"arm's), got {len(n_stages)} — a refused candidate "
                 "must never get a traffic stage")
    finally:
        router.close()

    # ================= fleet 2: degraded canary auto-rollback ========
    os.environ["TPUIC_FAULTS"] = f"canary_degrade#{degrade_s:.3f}"
    try:
        router2 = Router(
            replica_cmd=replica_cmd, n_replicas=2,
            state_dir=os.path.join(workdir, "router2"),
            knee_rps=anchor, breaker_threshold=3,
            breaker_cooldown_s=0.5, ping_interval_s=0.1,
            ping_timeout_s=3.0, wedge_timeout_s=60.0,
            spawn_timeout_s=args.spawn_timeout_s,
            respawn_backoff_s=0.2, grace_s=15.0, drain_timeout_s=30.0)
        router2.start(timeout_s=args.spawn_timeout_s)
    finally:
        os.environ.pop("TPUIC_FAULTS", None)
    try:
        boot2 = router2.fleet_digest
        outcomes3 = []
        join = storm(router2, args.requests,
                     on_done=lambda i, ok, s: outcomes3.append(ok))
        v3 = rollout_for(router2, candidate).run()
        _, _, snap3 = join()
        verdicts["degrade"] = v3
        if v3.get("verdict") != "rolled_back" \
                or v3.get("reason") != "slo_burn":
            fail(f"degrade arm: expected slo_burn rollback, got {v3}")
        if v3.get("swap_back_failed"):
            fail(f"degrade arm: rollback swap-back failed on "
                 f"{v3['swap_back_failed']}")
        check_ledger("degrade arm", snap3, args.requests, outcomes3)
        canary = v3.get("canary", "r0")
        if not wait_digest(router2, canary, boot2):
            fail(f"degrade arm: canary {canary} never returned to the "
                 f"boot digest {boot2} after rollback")
        if router2.fleet_digest != boot2:
            fail("degrade arm: fleet digest moved on a rolled-back "
                 "candidate")
        # post-rollback wave: the fault stood down (boot weights), the
        # fleet is healthy and the ledger exact.
        join = storm(router2, 150)
        _, _, snap4 = join()
        check_ledger("post-rollback wave", snap4, 150)
        if snap4["requests"] == 0:
            fail("post-rollback wave: nothing resolved")
        events2 = []
        try:
            with open(router2.ledger_path) as f:
                events2 = [json.loads(ln) for ln in f if ln.strip()]
        except OSError:
            fail("router2 ledger unreadable")
        ro2 = [e for e in events2 if e.get("event") == "rollout"]
        rb = [e for e in ro2 if e.get("action") == "rollback"]
        if not rb or rb[-1].get("reason") != "slo_burn":
            fail(f"ledger2: rollback event missing/wrong ({rb})")
        if not any(e.get("action") == "digest_disallow"
                   for e in events2):
            fail("ledger2: candidate digest never disallowed on "
                 "rollback")
    finally:
        router2.close()

    verdict = {
        "anchors": {"drive_rps": round(drive_rps, 2),
                    "slo": slo,
                    "degrade_s_per_batch": round(degrade_s, 3),
                    "probe_service_s": round(service_s, 5)},
        "verdicts": verdicts,
        "failures": failures,
    }
    with open(os.path.join(workdir, "rollout_soak_verdict.json"),
              "w") as f:
        json.dump(verdict, f, indent=2, default=str)
    print(json.dumps(verdict, indent=2, default=str))

    if failures:
        for msg in failures:
            print(f"[rollout_soak] FAIL: {msg}", file=sys.stderr)
        return 1
    print("[rollout_soak] OK: clean canary promoted with zero dropped "
          "requests and compiles flat across the swap; corrupt "
          "artifact refused swap_corrupt pre-traffic; degraded canary "
          "rolled back on SLO burn with the ledger exact both arms",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
