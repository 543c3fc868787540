#!/usr/bin/env python
"""Gang soak (ISSUE 10 acceptance; runs in tier-1 CI).

The end-to-end proof of coordinated multi-rank supervision
(`tpuic.runtime.gang.GangSupervisor` driving TWO real `train.py` ranks
as one unit, CPU, synthetic data — independent ranks via the
`TPUIC_FLEET_RANK(S)` launcher override, the `fleet_smoke.py` caveat:
this container's CPU jax implements no multiprocess collectives, and
independent deterministic ranks are exactly what the bitwise verdict
wants anyway), raced against an UNDISTURBED single-process baseline:

- attempt 0 seeds ``rank_crash@8#1`` — rank 1 is SIGKILLed mid epoch 1
  while rank 0 keeps training (``slow_step#`` drags both ranks so the
  survivor is provably mid-flight when the crash lands);
- the gang must tear down as a unit: the SURVIVOR gets its SIGTERM
  flush window and exits 43 (observed in the attempt's per-rank codes)
  with a step-exact checkpoint;
- the coordinated restart resumes on the FLEET-AGREED step: the gang
  ledger's ``gang_resume`` records the newest step every rank's
  committed manifest covers (epoch 0's commit — NOT the survivor's
  newer teardown flush), and each rank's ``restart`` event proves it
  landed there (epoch 1, step 0 — no rank resumed ahead of the fleet);
- exactly ONE coordinated restart happens, zero ledger violations, and
  both ranks' final optimizer step and per-epoch eval accuracies are
  BITWISE identical to the undisturbed baseline;
- the fleet aggregator (`python -m tpuic.telemetry.fleet
  --require-ranks 2`) passes over the per-rank streams and its
  ``duplicate_steps`` surfaces the replay; ``--require-ranks 3`` fails,
  proving the coverage gate is bidirectional;

plus the poison contract on cheap stdlib children: exit 44 from ONE
rank stops the whole gang without restart (the survivor still gets its
flush window).

The zero-added-syncs/zero-compiles half of the acceptance (the gang env
wiring — per-rank heartbeat, fleet tag, resume cap — adds no device
work) is checker-asserted in tier-1
(tests/test_gang.py::test_gang_env_wiring_zero_syncs_zero_compiles).

Exit 0 on success.   python scripts/gang_soak.py [--keep] [-v]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from tpuic.runtime.gang import GangSupervisor  # noqa: E402
from tpuic.runtime.supervisor import (EXIT_POISON,  # noqa: E402
                                      EXIT_PREEMPTED)

RANKS = 2
CRASH_RANK = 1
# 2 classes x 12 / global batch 4 = 6 steps/epoch; 2 epochs, no skipped
# steps -> the final optimizer step is 12. rank_crash@8 SIGKILLs rank 1
# at host step key 8 (epoch 1, loop index 2); slow_step#0.3 drags BOTH
# ranks so rank 0 is provably mid-epoch when the teardown TERM lands
# (sleeps never change the math — the baseline runs full speed).
PER_CLASS = 12
BATCH = 4
EPOCHS = 2
STEPS_PER_EPOCH = (2 * PER_CLASS) // BATCH
FINAL_STEP = EPOCHS * STEPS_PER_EPOCH
CHAOS = [f"rank_crash@8#{CRASH_RANK},slow_step#0.3", ""]


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
    from tpuic.telemetry.events import read_jsonl
    return read_jsonl(path, on_torn=lambda ln: print(
        f"  [soak] skipping torn jsonl line in {path}: {ln[:80]!r}"))


def _evals(recs: list) -> dict:
    out = {}
    for r in recs:
        if r["event"] == "eval":
            out[int(r["epoch"])] = r["accuracy"]
    return out


def _final_meta_step(ckpt_model_dir: str):
    try:
        man = json.load(open(os.path.join(ckpt_model_dir,
                                          "latest.manifest.json")))
        return int(man["step"])
    except (OSError, ValueError, KeyError, TypeError):
        return None


def _poison_phase(work: str, check) -> None:
    """Poison contract on stdlib children (~1 s): exit 44 from one rank
    stops the gang without restart; the survivor flushes 43."""
    child = os.path.join(work, "poison_child.py")
    with open(child, "w") as f:
        f.write(textwrap.dedent("""\
            import os, signal, sys, time
            from tpuic.runtime.supervisor import (EXIT_POISON,
                                                  EXIT_PREEMPTED,
                                                  HeartbeatWriter)
            hb = HeartbeatWriter(os.environ["TPUIC_HEARTBEAT_FILE"],
                                 min_interval_s=0.0)
            signal.signal(signal.SIGTERM,
                          lambda s, f: sys.exit(EXIT_PREEMPTED))
            if os.environ["TPUIC_FLEET_RANK"] == "1":
                hb.last_step = 1; hb.beat()
                # Wait for rank 0's first beat (its TERM handler is
                # registered before it beats) so the teardown's flush
                # window finds an armed survivor, not a mid-import one.
                peer = os.environ["TPUIC_HEARTBEAT_FILE"].replace(
                    ".rank1", "")
                t0 = time.monotonic()
                while (not os.path.exists(peer)
                       and time.monotonic() - t0 < 30):
                    time.sleep(0.02)
                sys.exit(EXIT_POISON)
            while True:
                hb.last_step = 1; hb.beat()
                time.sleep(0.02)
        """))
    sup = GangSupervisor(
        [sys.executable, child], os.path.join(work, "poison_state"),
        ranks=RANKS, watchdog_s=30.0, startup_grace_s=30.0, poll_s=0.05,
        grace_s=10.0, max_restarts=4, backoff_s=0.05, backoff_max_s=0.1,
        env={"PYTHONPATH": _REPO})
    rc = sup.run()
    check(rc == EXIT_POISON,
          f"poison from one rank stopped the gang with exit "
          f"{EXIT_POISON} (got {rc})")
    check(sup.restarts == 0 and len(sup.attempts) == 1,
          f"no restart after poison ({sup.restarts} restarts, "
          f"{len(sup.attempts)} attempts)")
    codes = sup.attempts[0].codes if sup.attempts else []
    check(codes and codes[1] == EXIT_POISON
          and codes[0] == EXIT_PREEMPTED,
          f"survivor got its flush window during the poison teardown "
          f"(codes {codes})")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--watchdog-s", type=float, default=30.0)
    p.add_argument("--workdir", default="",
                   help="run here instead of a temp dir (CI passes a "
                        "fixed path so per-rank stackdump/flightdump "
                        "artifacts can be uploaded on failure)")
    p.add_argument("--keep", action="store_true")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args()

    t_start = time.monotonic()
    work = args.workdir or tempfile.mkdtemp(prefix="tpuic_gang_")
    os.makedirs(work, exist_ok=True)
    failures: list = []
    passed = False       # set only on the fully-green path: an unhandled
    baseline = None      # exception must also keep the artifacts


    def check(ok: bool, msg: str) -> None:
        print(("  ok  " if ok else "  FAIL") + f" {msg}")
        if not ok:
            failures.append(msg)

    try:
        print("[soak] poison contract: exit 44 from one rank stops the "
              "gang without restart")
        _poison_phase(work, check)
        if failures:
            return 1

        # -- dataset + parallel baseline --------------------------------
        from tpuic.data.synthetic import make_synthetic_imagefolder
        data = os.path.join(work, "data")
        make_synthetic_imagefolder(data, classes=("a", "b"),
                                   per_class=PER_CLASS, size=24)
        # Identical env on every side (the chaos_soak discipline): the
        # shared persistent compile cache pays each XLA compile once,
        # and cpu + cache + skip-guard disables donation on ALL of
        # baseline and both ranks, so the bitwise comparison holds.
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TF_CPP_MIN_LOG_LEVEL="3", XLA_FLAGS="")
        # A cache directory given from outside wins.
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(work, "jax_cache"))
        sink = None if args.verbose else subprocess.DEVNULL
        base_jsonl = os.path.join(work, "baseline.jsonl")
        base_ckpt = os.path.join(work, "ckpt_base")
        print("[soak] baseline (undisturbed, single process) started in "
              "parallel")
        baseline = subprocess.Popen(
            _train_cmd(data, base_ckpt, os.path.join(work, "cache_base"),
                       base_jsonl),
            cwd=_REPO, env=env, stdout=sink, stderr=sink)

        # -- the supervised 2-rank gang ---------------------------------
        streams = os.path.join(work, "streams")
        os.makedirs(streams, exist_ok=True)
        state_dir = os.path.join(work, "supervise")
        gang_cmd = _train_cmd(data, os.path.join(work, "cp{rank}"),
                              os.path.join(work, "cache{rank}"),
                              os.path.join(streams, "events.jsonl"))
        print(f"[soak] gang of {RANKS} ranks under chaos "
              f"({'; '.join(s or 'fault-free' for s in CHAOS)})")
        sup = GangSupervisor(
            gang_cmd, state_dir, ranks=RANKS,
            watchdog_s=args.watchdog_s, startup_grace_s=600.0,
            quit_wait_s=2.0, grace_s=15.0, poll_s=0.25, max_restarts=4,
            backoff_s=0.25, backoff_max_s=2.0, crash_loop_k=3,
            heartbeat_interval_s=0.2, chaos=CHAOS,
            ckpt_dirs=os.path.join(work, "cp{rank}", "resnet18-cifar"),
            env=dict(env, PYTHONPATH=_REPO))
        rc = sup.run()
        base_rc = baseline.wait(timeout=900)

        # -- the verdict -------------------------------------------------
        print(f"[soak] gang finished (exit {rc}, {len(sup.attempts)} "
              f"attempts, {sup.restarts} restarts, best fleet step "
              f"{sup.best_fleet_step}); baseline exit {base_rc}")
        check(rc == 0, "gang completed cleanly (exit 0)")
        check(base_rc == 0, "baseline completed cleanly (exit 0)")
        check(sup.restarts == 1 and sup.crash_restarts == 1,
              f"exactly ONE coordinated gang restart "
              f"({sup.restarts} restarts, {sup.crash_restarts} crash)")
        check(sup.violations == 0,
              "zero per-rank step-accounting violations")
        first = sup.attempts[0] if sup.attempts else None
        check(first is not None and first.codes[CRASH_RANK] < 0,
              f"rank {CRASH_RANK} died by signal in attempt 0 "
              f"(codes {first and first.codes})")
        check(first is not None
              and first.codes[1 - CRASH_RANK] == EXIT_PREEMPTED,
              f"the SURVIVING rank got its flush window — exit "
              f"{EXIT_PREEMPTED} observed (codes {first and first.codes})")

        ledger = [json.loads(ln) for ln in open(sup.ledger_file)]
        resume = [r for r in ledger if r["event"] == "gang_resume"]
        check(len(resume) == 1
              and resume[0]["step"] == STEPS_PER_EPOCH,
              f"coordinated restart resumed on the fleet-agreed step "
              f"{STEPS_PER_EPOCH} — epoch 0's commit, not the "
              f"survivor's newer teardown flush "
              f"(ledger: {[r.get('step') for r in resume]})")

        from tpuic.telemetry.fleet import rank_stream_path
        b_recs = _events(base_jsonl)
        b_eval = _evals(b_recs)
        b_meta = _final_meta_step(os.path.join(base_ckpt,
                                               "resnet18-cifar"))
        check(b_meta == FINAL_STEP,
              f"baseline committed final step {FINAL_STEP} (got {b_meta})")
        for rank in range(RANKS):
            recs = _events(rank_stream_path(
                os.path.join(streams, "events.jsonl"), rank))
            restarts = [r for r in recs if r["event"] == "restart"]
            check(len(restarts) == 1
                  and restarts[0]["epoch"] == 1
                  and restarts[0]["step_in_epoch"] == 0,
                  f"rank {rank} resumed at epoch 1 step 0 — the fleet "
                  f"step, never ahead of it ({restarts})")
            meta = _final_meta_step(os.path.join(work, f"cp{rank}",
                                                 "resnet18-cifar"))
            check(meta == b_meta,
                  f"rank {rank} final checkpointed step matches baseline "
                  f"({meta} == {b_meta})")
            ev = _evals(recs)
            check(ev == b_eval and set(ev) == set(range(EPOCHS)),
                  f"rank {rank} per-epoch eval accuracy bitwise-equal to "
                  f"baseline ({ev} == {b_eval})")
            per_epoch: dict = {}
            for r in recs:
                if r["event"] == "eval":
                    per_epoch.setdefault(int(r["epoch"]),
                                         set()).add(r["accuracy"])
            check(all(len(v) == 1 for v in per_epoch.values()),
                  f"rank {rank} replayed evals bitwise identical "
                  f"({per_epoch})")

        # The aggregator over the per-rank streams: full coverage
        # required, and the replay must surface as duplicate_steps.
        report_path = os.path.join(work, "fleet_report.json")
        cli = subprocess.run(
            [sys.executable, "-m", "tpuic.telemetry.fleet", streams,
             "--require-ranks", str(RANKS), "--json", report_path],
            cwd=_REPO, env=env, text=True, capture_output=True,
            timeout=120)
        print(cli.stdout, end="")
        check(cli.returncode == 0,
              f"aggregator passed with --require-ranks {RANKS} "
              f"(exit {cli.returncode}; stderr "
              f"{cli.stderr.strip()[-200:]})")
        rep = (json.load(open(report_path))
               if os.path.exists(report_path) else {})
        dup = rep.get("duplicate_steps") or {}
        check(bool(dup),
              f"duplicate_steps surfaces the coordinated replay ({dup})")
        gate = subprocess.run(
            [sys.executable, "-m", "tpuic.telemetry.fleet", streams,
             "--require-ranks", str(RANKS + 1)],
            cwd=_REPO, env=env, text=True, capture_output=True,
            timeout=120)
        check(gate.returncode == 1,
              f"--require-ranks {RANKS + 1} fails on the missing rank "
              f"(exit {gate.returncode}) — the coverage gate is "
              "bidirectional")

        took = time.monotonic() - t_start
        if failures:
            print(f"\nFAIL: {len(failures)} assertion(s) in {took:.1f}s")
            for f in failures:
                print(f"  - {f}")
            return 1
        print(f"\nOK: gang soak green in {took:.1f}s — one coordinated "
              f"restart, survivor flushed 43, fleet-agreed resume at "
              f"step {STEPS_PER_EPOCH}, final metrics bitwise-equal to "
              "baseline, poison stops the gang")
        passed = True
        return 0
    finally:
        if baseline is not None and baseline.poll() is None:
            # An exception above (a timeout, a torn ledger) must not
            # leak a still-training baseline into the CI job.
            baseline.kill()
            baseline.wait()
        if args.keep or not passed:
            # Check failures AND unhandled exceptions both keep the
            # artifacts — the tier1.yml failure-upload step needs the
            # gang ledger and per-rank dumps to diagnose anything.
            print(f"workdir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
