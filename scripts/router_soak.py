#!/usr/bin/env python
"""Router soak: the replica-fleet resilience layer's end-to-end CI gate
(docs/serving.md, "Replica routing and failover").

Two REAL engine replicas (``python -m tpuic.serve --listen`` processes,
synthetic-init so every replica carries identical seeded weights) behind
the stdlib-only router, driven by the SHARED loadgen harness with a
Poisson storm anchored at the committed latency knee
(``perf/bench_serve.json``, floored by fresh local capacity probes —
the overload-soak anchoring discipline).  Mid-storm, one replica is
**SIGKILLed** the instant it holds in-flight requests.  Asserted:

- **zero client timeouts**: every offered request either resolves or
  gets a typed verdict inside the generous result window — the router
  sheds and fails over instead of letting clients hang;
- **in-flight failover**: the victim's in-flight requests requeue to
  the survivor under the retry budget (surfaced through run_stream's
  ``on_retry`` outcome hook), unreplayables resolve ``replica_lost``;
- **breaker cycle**: the victim's circuit breaker trips **open** at the
  kill, goes **half-open** once the respawned replica (the ``_Child``
  ladder; warmed from the shared persistent compile cache) reconnects,
  and **closes** when the probe request succeeds — in that order, read
  from the router ledger;
- **exact ledger**, both waves: ``resolved + typed-rejected ==
  offered``, zero untyped errors, zero duplicate deliveries
  (at-most-once);
- **zero steady-state compiles** on the post-respawn fleet: each
  replica's scraped ``tpuic_serve_compiles_total`` is flat across the
  second wave (warmup is the only compile window), and the soak
  process itself runs the wave under ``assert_compiles_flat``.

Artifacts for CI upload on failure: the router ledger (breaker
transition log included), per-replica logs/heartbeats/stack dumps under
``<workdir>/router/r*/``, and the verdict JSON.

    python scripts/router_soak.py --workdir router-soak-work
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
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
    """One counter from a replica's /metrics (0.0 when unreachable —
    the caller decides whether that is fatal)."""
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
    p.add_argument("--workdir", default="router-soak-work")
    p.add_argument("--model", default="resnet18-cifar")
    p.add_argument("--size", type=int, default=24)
    p.add_argument("--buckets", default="1,4,8")
    p.add_argument("--requests", type=int, default=600,
                   help="storm length (wave 1)")
    p.add_argument("--requests-rejoin", type=int, default=200,
                   help="post-respawn wave length (wave 2: the rejoin "
                        "probe + compiles-flat window)")
    p.add_argument("--storm-factor", type=float, default=1.0,
                   help="drive = factor x max(committed knee, local "
                        "capacity anchor) — 'a Poisson storm at the "
                        "committed knee': half the 2-replica fleet's "
                        "headroom, so the kill makes the survivor "
                        "carry the whole knee")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--spawn-timeout-s", type=float, default=600.0)
    args = p.parse_args(argv)

    _force_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuic.analysis.runtime import assert_compiles_flat
    from tpuic.models import create_model
    from tpuic.serve import InferenceEngine, make_forward
    from tpuic.serve.loadgen import probe_unbatched_rps, run_stream
    from tpuic.serve.router import Router

    workdir = os.path.abspath(args.workdir)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    failures = []

    # ---- local capacity anchors (the overload-soak discipline) --------
    # Built FIRST so the shared persistent compile cache is hot before
    # any replica spawns: replica warmup (and the respawn mid-soak)
    # then loads executables from disk instead of recompiling — which
    # is also what makes the compiles-flat assertion meaningful.
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
    n_cap = min(400, len(reqs))
    t_cap = time.perf_counter()
    run_stream(probe_engine, reqs[:n_cap])
    batched_rps = n_cap / max(time.perf_counter() - t_cap, 1e-9)
    probe_engine.close()
    knee = _committed_knee()
    # Per-replica capacity anchor: the knee, floored by the local
    # batched probe discounted for socket/JSON transport overhead.
    anchor = max(knee, local_rps, 0.5 * batched_rps)
    drive_rps = args.storm_factor * anchor

    # ---- the fleet ----------------------------------------------------
    replica_cmd = [
        sys.executable, "-m", "tpuic.serve",
        "--synthetic-init", "--model", args.model, "--num-classes", "10",
        "--resize", str(args.size), "--buckets", args.buckets,
        "--max-wait-ms", "5", "--queue-size", "256",
        "--listen", "127.0.0.1:0", "--prom-port", "-1",
        "--drain-timeout", "10",
    ]
    router = Router(
        replica_cmd=replica_cmd, n_replicas=2,
        state_dir=os.path.join(workdir, "router"),
        knee_rps=anchor,            # spill limit: Little's law at the knee
        retry_ratio=0.1, retry_cap=32.0, max_attempts=3,
        breaker_threshold=3, breaker_cooldown_s=0.5,
        ping_interval_s=0.1, ping_timeout_s=3.0,
        wedge_timeout_s=60.0, spawn_timeout_s=args.spawn_timeout_s,
        respawn_backoff_s=0.2, grace_s=15.0, drain_timeout_s=30.0)
    print(f"[router_soak] anchors: knee={knee:g} unbatched="
          f"{local_rps:.1f} batched={batched_rps:.1f} -> drive "
          f"{drive_rps:.1f} req/s over 2 replicas", file=sys.stderr)
    router.start(timeout_s=args.spawn_timeout_s)

    try:
        victim = router.replicas[0]
        victim_pid = victim.child.pid
        kill_stamp = {"t": None, "inflight": 0}

        # ---- wave 1: Poisson storm + SIGKILL mid-storm ----------------
        import threading

        def killer() -> None:
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if len(victim.inflight) >= 1 and router.stats.snapshot()[
                        "offered"] >= args.requests // 4:
                    break
                time.sleep(0.001)
            kill_stamp["inflight"] = len(victim.inflight)
            kill_stamp["t"] = time.time()
            os.kill(victim_pid, signal.SIGKILL)

        retried, outcomes = [], []
        items = [(r, {"timeout": 0}) for r in reqs[:args.requests]]
        offsets = np.cumsum(rng.exponential(1.0 / drive_rps,
                                            size=args.requests))
        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        wall1, _, snap1 = run_stream(
            router, items, offsets_s=offsets, result_timeout_s=120.0,
            on_done=lambda i, ok, s: outcomes.append(ok),
            on_retry=lambda i, n: retried.append((i, n)))
        kt.join(timeout=70.0)
        if kill_stamp["t"] is None:
            failures.append("the killer thread never fired — the storm "
                            "kept the victim idle; nothing was proven")

        # zero client timeouts: run_stream returning at all means no
        # future hit the 120 s result window, and the settled ledger
        # must account for every offered request exactly.
        if len(outcomes) != args.requests:
            failures.append(
                f"outcome hook fired {len(outcomes)}/{args.requests} "
                "times — some request neither resolved nor got a "
                "typed verdict (a client would have timed out)")
        if snap1["requests"] + snap1["rejected"] != args.requests \
                or snap1["errors"] != 0:
            failures.append(
                f"wave-1 ledger violation: {snap1['requests']} resolved "
                f"+ {snap1['rejected']} rejected (+{snap1['errors']} "
                f"untyped errors) != {args.requests} offered")
        if snap1["duplicates"] or snap1["wire_errors"]:
            # The kill window is the ONLY place an original response
            # can race a failover replay, and a SIGKILLed replica can
            # send nothing after its EOF — at-most-once must hold with
            # zero duplicate deliveries and zero torn-framing lines.
            failures.append(
                f"at-most-once violated in wave 1: "
                f"{snap1['duplicates']} duplicate response(s), "
                f"{snap1['wire_errors']} wire error(s)")
        bad_causes = set(snap1["rejected_by"]) - {
            "queue_full", "deadline", "replica_lost"}
        if bad_causes:
            failures.append(f"unexpected reject causes: {bad_causes}")
        if kill_stamp["inflight"] >= 1 and snap1["failovers"] < 1:
            failures.append(
                f"victim died holding {kill_stamp['inflight']} "
                "request(s) but no failover was recorded")
        if snap1["retries"] and not retried:
            failures.append("router recorded replays but the loadgen "
                            "on_retry hook never fired — the one-"
                            "harness contract broke")

        # ---- respawn + rejoin ----------------------------------------
        deadline = time.monotonic() + args.spawn_timeout_s
        while time.monotonic() < deadline:
            if victim.state == "up":
                break
            time.sleep(0.1)
        else:
            failures.append("victim never respawned to 'up' within the "
                            "spawn timeout")
        new_pid = victim.child.pid if victim.child else None
        if new_pid == victim_pid:
            failures.append("victim 'respawn' kept the killed pid — no "
                            "new process was started")

        # settle, then pin compiles across wave 2 (warmup is the only
        # compile window; the respawned replica warmed from the cache)
        time.sleep(1.0)
        ports = [r.prom_port for r in router.replicas]
        compiles_before = [_scrape_counter(
            pt, "tpuic_serve_compiles_total") for pt in ports]
        r0_routed_before = victim.routed  # cumulative: delta proves rejoin
        snap2 = None
        if victim.state == "up":
            with assert_compiles_flat(0, what="router soak wave 2 "
                                              "(soak process)"):
                _, _, snap2 = run_stream(
                    router,
                    [(r, {"timeout": 0})
                     for r in reqs[:args.requests_rejoin]],
                    offsets_s=np.cumsum(rng.exponential(
                        1.0 / drive_rps, size=args.requests_rejoin)),
                    result_timeout_s=120.0)
            if snap2["requests"] + snap2["rejected"] \
                    != args.requests_rejoin or snap2["errors"] != 0:
                failures.append(
                    f"wave-2 ledger violation: {snap2['requests']} + "
                    f"{snap2['rejected']} (+{snap2['errors']} errors) "
                    f"!= {args.requests_rejoin}")
            if snap2["duplicates"] or snap2["wire_errors"]:
                failures.append(
                    f"at-most-once violated in wave 2: "
                    f"{snap2['duplicates']} duplicate response(s), "
                    f"{snap2['wire_errors']} wire error(s)")
            if victim.routed <= r0_routed_before:
                # victim.routed is cumulative across waves — only the
                # DELTA proves wave-2 traffic actually reached the
                # respawned replica (a breaker stuck open would leave
                # it flat while the fleet still answers).
                failures.append("wave 2 never routed to the respawned "
                                "replica — rejoin unproven")
        compiles_after = [_scrape_counter(
            pt, "tpuic_serve_compiles_total") for pt in ports]
        for name, before, after in zip(("r0", "r1"), compiles_before,
                                       compiles_after):
            if before != before or after != after:  # NaN: scrape failed
                failures.append(f"{name}: compile counter unscrapable "
                                "(before/after wave 2)")
            elif after != before:
                failures.append(
                    f"{name}: {after - before:g} steady-state "
                    f"compile(s) during wave 2 — the respawn/rejoin "
                    "path recompiled instead of hitting the cache")

        # ---- breaker cycle from the ledger ----------------------------
        events = []
        try:
            with open(router.ledger_path) as f:
                events = [json.loads(ln) for ln in f if ln.strip()]
        except OSError:
            failures.append("router ledger unreadable")
        b = [e for e in events if e.get("event") == "router_breaker"
             and e.get("replica") == "r0"]
        states = [e["new"] for e in b]
        try:
            i_open = states.index("open")
            i_half = states.index("half_open", i_open)
            states.index("closed", i_half)
        except ValueError:
            failures.append(
                f"breaker cycle open->half_open->closed not observed "
                f"for the killed replica (saw: {states})")
        if not any(e.get("event") == "router_failover"
                   and e.get("replica") == "r0" for e in events):
            failures.append("no router_failover event for the victim "
                            "in the ledger")
        dup = (snap1["duplicates"]
               + (snap2["duplicates"] if snap2 else 0))

        verdict = {
            "anchors": {"committed_knee_rps": knee,
                        "local_unbatched_rps": round(local_rps, 2),
                        "local_batched_rps": round(batched_rps, 2),
                        "drive_rps": round(drive_rps, 2),
                        "probe_service_s": round(service_s, 5)},
            "kill": {"pid": victim_pid,
                     "inflight_at_kill": kill_stamp["inflight"],
                     "respawned_pid": new_pid},
            "wave1": {k: snap1[k] for k in
                      ("offered", "requests", "rejected", "rejected_by",
                       "errors", "retries", "failovers",
                       "failover_requeued", "failover_lost",
                       "duplicates", "wire_errors", "latency_ms")},
            "wave1_wall_s": round(wall1, 2),
            "on_retry_hook_fires": len(retried),
            "wave2": ({k: snap2[k] for k in
                       ("offered", "requests", "rejected", "errors",
                        "duplicates", "wire_errors")}
                      if snap2 else None),
            "wave2_routed_to_respawned": (victim.routed
                                          - r0_routed_before),
            "breaker_r0_states": states,
            "compiles_during_wave2": [
                (a - bfr) if (a == a and bfr == bfr) else None
                for bfr, a in zip(compiles_before, compiles_after)],
            "duplicate_responses": dup,
            "replicas": router.replica_health(),
        }
        with open(os.path.join(workdir, "router_soak_verdict.json"),
                  "w") as f:
            json.dump(verdict, f, indent=2, default=str)
        print(json.dumps(verdict, indent=2, default=str))
    finally:
        router.close()

    if failures:
        for msg in failures:
            print(f"[router_soak] FAIL: {msg}", file=sys.stderr)
        return 1
    print(f"[router_soak] OK: SIGKILL mid-storm at {drive_rps:.0f} "
          f"req/s -> {snap1['failover_requeued']} requeued / "
          f"{snap1['failover_lost']} replica_lost, zero client "
          f"timeouts, breaker open->half_open->closed rejoin, both "
          f"ledgers exact, compiles flat on the post-respawn fleet",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
