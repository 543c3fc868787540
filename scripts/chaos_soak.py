#!/usr/bin/env python
"""Chaos soak (ISSUE 5 acceptance; runs in tier-1 CI).

The end-to-end proof of the whole robustness stack: a REAL supervised
training run (`tpuic.runtime.supervisor.Supervisor` driving the real
`train.py` CLI as a child, CPU, synthetic data) under a seeded
per-attempt fault schedule —

- ``nan_batch``   — in-graph skip guard (fires in every attempt that
                    replays its step, so the trajectory stays bitwise
                    comparable to the baseline, which arms it too)
- ``ckpt_kill``   — process dies mid checkpoint-commit (attempt 0)
- ``hard_crash``  — SIGKILL to self mid-epoch (attempt 1)
- ``hang_step``   — wedged step; the watchdog must SIGQUIT a stack dump,
                    then SIGTERM, then SIGKILL (attempt 2)
- ``sigterm``     — clean preemption flush, exit 43, immediate restart
                    with step-exact resume (attempt 3)

— and an UNDISTURBED baseline run (same config, same ``nan_batch``)
raced in parallel. The soak then asserts the supervised run converged to
the *identical* end state:

- same final global optimizer step (checkpoint meta + max step event),
- same per-epoch eval accuracy (exact float equality — resume is
  bitwise),
- >= 2 automatic restarts observed, zero ledger violations (no step ever
  skipped past the best previously observed step + 1 — nothing lost,
  nothing double-counted),
- the hang produced a non-empty faulthandler stack dump artifact,
- the sigterm attempt exited with the contract's code 43,

plus the crash-loop policy: a child that fails deterministically makes
the supervisor give up with exit 45 after ``crash_loop_k`` no-progress
restarts instead of restarting forever.

Exit 0 on success.   python scripts/chaos_soak.py [--keep] [-v]
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

from tpuic.runtime.supervisor import (EXIT_CRASH_LOOP,  # noqa: E402
                                      EXIT_PREEMPTED, Supervisor)

# Fault keys are host-tracked global step numbers (step0 + loop index,
# 0-based). With 24 train images / global batch 4 there are 6 loop steps
# per epoch; the nan_batch skip at key 2 means the optimizer step counter
# ends at 11 after 2 epochs, and epoch 1's keys are 5..10 (key 5 is
# ambiguous — it is also epoch 0's last — so epoch-1 faults use >= 6).
PER_CLASS = 12          # x2 classes = 24 train images
BATCH = 4               # 6 steps/epoch on the single CPU device
EPOCHS = 2
NAN_SPEC = "nan_batch@2"
CHAOS = [
    NAN_SPEC + ",ckpt_kill*1",   # dies committing epoch 0's best
    NAN_SPEC + ",hard_crash@8",  # SIGKILL mid epoch 1 (replays epoch 0)
    "hang_step@9",               # wedge; watchdog SIGQUIT/SIGTERM/SIGKILL
    "sigterm@10",                # clean flush, exit 43, step-exact resume
    "",                          # fault-free final attempt completes
]


def _train_cmd(data: str, ckpt: str, cache: str, jsonl: str) -> list:
    return [sys.executable, os.path.join(_REPO, "train.py"),
            "--datadir", data, "--model", "resnet18-cifar",
            "--resize", "24", "--batchsize", str(BATCH),
            "--epochs", str(EPOCHS), "--optimizer", "sgd", "--lr", "0.01",
            "--no-class-weights", "--log-every-steps", "1",
            "--save-period", "1", "--workers", "2",
            "--ckpt-dir", ckpt, "--cache-dir", cache,
            "--metrics-jsonl", jsonl]


def _events(path: str) -> list:
    # A SIGKILL fault can tear a JSONL line mid-write, and the next
    # attempt appends its first event onto the fragment; the SHARED
    # tolerant reader (telemetry/events.read_jsonl — also behind the
    # regress gate and the fleet aggregator) skips lines that don't
    # parse rather than crashing the verdict path.
    from tpuic.telemetry.events import read_jsonl
    return read_jsonl(path, on_torn=lambda ln: print(
        f"  [soak] skipping torn jsonl line in {path}: {ln[:80]!r}"))


def _evals(recs: list) -> dict:
    """{epoch: accuracy}, last occurrence wins (replayed epochs re-emit
    the identical value — that identity is itself asserted below)."""
    out = {}
    for r in recs:
        if r["event"] == "eval":
            out[int(r["epoch"])] = r["accuracy"]
    return out


def _final_meta_step(ckpt: str):
    # The optimizer step of the committed checkpoint lives in the commit
    # manifest (the meta sidecar carries only the resume keys). None
    # when the run died before committing one — the verdict path must
    # print its per-assertion diagnosis, not a traceback.
    try:
        man = json.load(open(os.path.join(ckpt, "resnet18-cifar",
                                          "latest.manifest.json")))
        return int(man["step"])
    except (OSError, ValueError, KeyError, TypeError):
        return None


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--watchdog-s", type=float, default=20.0,
                   help="hang-detection window; must exceed the longest "
                        "legitimately silent span (eval execution — "
                        "compiles beat via the jax.monitoring bridge)")
    p.add_argument("--keep", action="store_true",
                   help="keep the temp workdir for inspection")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="stream child stdout/stderr instead of hiding it")
    args = p.parse_args()

    t_start = time.monotonic()
    work = tempfile.mkdtemp(prefix="tpuic_chaos_")
    failures: list = []

    def check(ok: bool, msg: str) -> None:
        print(("  ok  " if ok else "  FAIL") + f" {msg}")
        if not ok:
            failures.append(msg)

    try:
        # -- crash-loop policy (pure stdlib, ~1 s) ----------------------
        print("[soak] crash-loop policy: deterministic failure must make "
              "the supervisor give up, not restart forever")
        sup0 = Supervisor(
            [sys.executable, "-c", "import sys; sys.exit(7)"],
            os.path.join(work, "crashloop"), watchdog_s=30.0,
            startup_grace_s=30.0, poll_s=0.05, max_restarts=10,
            backoff_s=0.05, backoff_max_s=0.1, crash_loop_k=2)
        rc = sup0.run()
        check(rc == EXIT_CRASH_LOOP,
              f"gave up with exit {EXIT_CRASH_LOOP} (got {rc})")
        check(len(sup0.attempts) == 2 and sup0.restarts == 1,
              f"stopped after crash_loop_k=2 no-progress attempts "
              f"({len(sup0.attempts)} attempts, {sup0.restarts} restart)")

        # -- dataset + parallel baseline --------------------------------
        from tpuic.data.synthetic import make_synthetic_imagefolder
        data = os.path.join(work, "data")
        make_synthetic_imagefolder(data, classes=("a", "b"),
                                   per_class=PER_CLASS, size=24)
        # XLA_FLAGS overridden (not popped): the Supervisor builds its
        # child env as os.environ + these overrides, so an inherited
        # fake-device flag would otherwise leak into the supervised run
        # only and desync the two trajectories' device counts. The
        # persistent compile cache is shared by every attempt AND the
        # baseline (identical env => identical trajectories): the 6
        # process startups would otherwise each repay the same XLA
        # compiles. cpu + cache + skip-guard auto-disables state
        # donation (train/step.py's bisected aliasing gate) — same on
        # both sides, so the bitwise comparison holds.
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TF_CPP_MIN_LOG_LEVEL="3", XLA_FLAGS="")
        # A cache directory given from outside wins.
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(work, "jax_cache"))

        base_jsonl = os.path.join(work, "baseline.jsonl")
        base_ckpt = os.path.join(work, "ckpt_base")
        base_cmd = _train_cmd(data, base_ckpt,
                              os.path.join(work, "cache_base"), base_jsonl)
        sink = None if args.verbose else subprocess.DEVNULL
        print("[soak] baseline (undisturbed, nan_batch only) started "
              "in parallel")
        baseline = subprocess.Popen(
            base_cmd, cwd=_REPO, env=dict(env, TPUIC_FAULTS=NAN_SPEC),
            stdout=sink, stderr=sink)

        # -- the supervised chaos run -----------------------------------
        print(f"[soak] supervised run: {len(CHAOS)} scheduled attempts "
              f"({', '.join(s or 'fault-free' for s in CHAOS)})")
        sup_jsonl = os.path.join(work, "supervised.jsonl")
        sup_ckpt = os.path.join(work, "ckpt_sup")
        state_dir = os.path.join(work, "supervise")
        sup = Supervisor(
            _train_cmd(data, sup_ckpt, os.path.join(work, "cache_sup"),
                       sup_jsonl),
            state_dir, watchdog_s=args.watchdog_s, startup_grace_s=600.0,
            quit_wait_s=2.0, grace_s=5.0, poll_s=0.25, max_restarts=8,
            backoff_s=0.25, backoff_max_s=2.0, crash_loop_k=3,
            heartbeat_interval_s=0.2, chaos=CHAOS,
            env=dict(env, PYTHONPATH=_REPO))
        rc = sup.run()
        base_rc = baseline.wait(timeout=900)

        # -- the verdict -------------------------------------------------
        print("[soak] supervised run finished "
              f"(exit {rc}, {len(sup.attempts)} attempts, "
              f"{sup.restarts} restarts, best step {sup.best_step}); "
              f"baseline exit {base_rc}")
        check(rc == 0, "supervised run completed cleanly (exit 0)")
        check(base_rc == 0, "baseline completed cleanly (exit 0)")
        check(sup.restarts >= 2,
              f"{sup.restarts} automatic restarts observed (>= 2)")
        check(sup.violations == 0,
              "zero progress-ledger violations (no step lost or "
              "double-counted)")
        hung = [a for a in sup.attempts if a.hung]
        check(len(hung) == 1, "exactly the hang_step attempt was "
              f"watchdog-killed (got {[a.attempt for a in hung]})")
        if hung:
            dump = os.path.join(state_dir, f"stackdump-{hung[0].attempt}.txt")
            body = open(dump).read() if os.path.exists(dump) else ""
            check("File" in body and len(body) > 50,
                  f"hang produced a faulthandler stack dump ({dump}, "
                  f"{len(body)} bytes)")
            # Flight recorder (telemetry/flight.py): the same SIGQUIT
            # must also have dumped the event timeline leading into the
            # wedge — non-empty, parseable, and every recorded event
            # stamped BEFORE the dump trailer (i.e. before the SIGQUIT
            # was handled): stacks say where, the flight dump says what
            # happened on the way in.
            fdump = os.path.join(state_dir,
                                 f"flightdump-{hung[0].attempt}.jsonl")
            frecs = _events(fdump)
            trailer = frecs[-1] if frecs else {}
            body_evs = [r for r in frecs if r.get("event") != "flight_dump"]
            check(trailer.get("event") == "flight_dump"
                  and trailer.get("reason") == "sigquit",
                  f"flight dump ends with a sigquit trailer ({fdump}, "
                  f"{len(frecs)} records)")
            check(len(body_evs) > 0 and any(
                      r.get("event") == "step" for r in body_evs),
                  f"flight dump carries the event timeline "
                  f"({len(body_evs)} events incl. steps)")
            check(bool(body_evs) and bool(trailer) and all(
                      r.get("t", 1e18) <= trailer.get("t", 0)
                      for r in body_evs),
                  "every flight-dump event precedes the SIGQUIT trailer")
        codes = [a.returncode for a in sup.attempts]
        check(EXIT_PREEMPTED in codes,
              f"sigterm attempt exited {EXIT_PREEMPTED} per the contract "
              f"(attempt codes: {codes})")

        b_recs, s_recs = _events(base_jsonl), _events(sup_jsonl)
        # default=None: a run that died before its first step event must
        # degrade into check() failures below, not a bare-max ValueError
        # that replaces the whole diagnosis with a traceback.
        b_step = max((r["step"] for r in b_recs if r["event"] == "step"),
                     default=None)
        s_step = max((r["step"] for r in s_recs if r["event"] == "step"),
                     default=None)
        b_meta, s_meta = _final_meta_step(base_ckpt), _final_meta_step(sup_ckpt)
        check(b_meta is not None and s_meta == b_meta,
              f"final checkpointed optimizer step matches baseline "
              f"({s_meta} == {b_meta})")
        check(sup.best_step == b_step == s_step,
              f"max step event + supervisor ledger agree with baseline "
              f"(ledger {sup.best_step}, events {s_step}, "
              f"baseline {b_step})")
        b_eval, s_eval = _evals(b_recs), _evals(s_recs)
        check(set(b_eval) == set(s_eval) == set(range(EPOCHS)),
              f"both runs evaluated every epoch (baseline {sorted(b_eval)}, "
              f"supervised {sorted(s_eval)})")
        check(b_eval == s_eval,
              f"per-epoch eval accuracy identical to baseline "
              f"({s_eval} == {b_eval})")
        # Replayed epochs must have re-produced the identical eval value
        # (bitwise resume): every supervised eval event for one epoch
        # carries one accuracy.
        per_epoch: dict = {}
        for r in s_recs:
            if r["event"] == "eval":
                per_epoch.setdefault(int(r["epoch"]), set()).add(r["accuracy"])
        check(all(len(v) == 1 for v in per_epoch.values()),
              f"replayed evals were bitwise identical ({per_epoch})")
        restarts = [r for r in s_recs if r["event"] == "restart"]
        check(len(restarts) == sup.restarts,
              f"every restart announced itself as a 'restart' event "
              f"({len(restarts)} == {sup.restarts})")

        took = time.monotonic() - t_start
        if failures:
            print(f"\nFAIL: {len(failures)} assertion(s) in {took:.1f}s")
            for f in failures:
                print(f"  - {f}")
            return 1
        print(f"\nOK: chaos soak green in {took:.1f}s — "
              f"{len(sup.attempts)} attempts, {sup.restarts} restarts, "
              f"final step {s_meta}, eval metrics identical to the "
              f"undisturbed baseline")
        return 0
    finally:
        if args.keep:
            print(f"workdir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
