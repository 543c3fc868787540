#!/usr/bin/env python
"""Elastic soak (ISSUE 15 acceptance; runs in tier-1 CI).

The end-to-end proof of elastic data parallelism
(`tpuic.runtime.gang.GangSupervisor` in elastic mode driving TWO real
`train.py` ranks, CPU, synthetic data — independent ranks via the
`TPUIC_FLEET_RANK(S)` launcher override, the fleet_smoke caveat: this
container's CPU jax implements no multiprocess collectives, and
independent deterministic ranks are exactly what the bitwise verdict
wants anyway), raced against an UNDISTURBED single-process baseline:

- ``rank_crash@8#1`` SIGKILLs rank 1 mid epoch 1 (``slow_step#0.3``
  drags both ranks so the survivor is provably mid-flight);
- the fleet DEGRADES instead of restarting: the membership file walks
  init -> degrade -> rejoin, the survivor re-forms IN PLACE from the
  fleet-agreed step (one spawn record for rank 0 in the whole ledger —
  zero survivor process restarts; its stream carries a 'reform' event
  with acted=true and NO 'restart' event), and training continues;
- the FIRST replacement is armed with ``rank_rejoin_flap#1`` and dies
  inside its catch-up restore — the flap burns only rank 1's respawn
  budget (ledger 'flap', no extra membership transition); the SECOND
  replacement restores under the fleet cap, rejoins at its first
  post-restore step, and finishes;
- convergence-parity gate: both ranks' final committed optimizer step
  and per-epoch eval accuracies are BITWISE identical to the
  undisturbed baseline;
- the fleet aggregator passes the elastic coverage gate
  (``--membership ledger.jsonl``) over the per-rank streams, while the
  strict ``--require-ranks 3`` still fails (missing rank) — the
  timeline gate is additive, not a loosening;

plus the typed floor on cheap stdlib children: with 3 ranks and
``min_ranks=2``, the first kill produces a DEGRADE event and the second
kill stops the gang with the typed ``EXIT_BELOW_MIN`` verdict (the last
survivor still gets its flush window, exit 43).

Exit 0 on success.   python scripts/elastic_soak.py [--keep] [-v]
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
from tpuic.runtime.membership import read_membership  # noqa: E402
from tpuic.runtime.supervisor import (EXIT_BELOW_MIN,  # noqa: E402
                                      EXIT_PREEMPTED)

RANKS = 2
CRASH_RANK = 1
# Same workload math as the gang soak: 2 classes x 12 / global batch 4 =
# 6 steps/epoch, 2 epochs -> final optimizer step 12; epoch 0's commit is
# step 6 — the fleet-agreed degrade step (rank 1 dies at step 8, past the
# commit, so the survivor restores BACK to 6 and replays 7..12).
PER_CLASS = 12
BATCH = 4
EPOCHS = 2
STEPS_PER_EPOCH = (2 * PER_CLASS) // BATCH
FINAL_STEP = EPOCHS * STEPS_PER_EPOCH
# Per-RESPAWN chaos (elastic indexing): the original spawns get the kill,
# the first replacement flaps inside its catch-up restore, the second
# replacement runs clean and rejoins.
CHAOS = [f"rank_crash@8#{CRASH_RANK},slow_step#0.3",
         f"rank_rejoin_flap#{CRASH_RANK}", ""]


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


def _floor_phase(work: str, check) -> None:
    """Typed floor on stdlib children (~2 s): first kill degrades,
    second kill below min_ranks stops with EXIT_BELOW_MIN."""
    child = os.path.join(work, "floor_child.py")
    with open(child, "w") as f:
        f.write(textwrap.dedent("""\
            import os, signal, sys, time
            from tpuic.runtime.supervisor import (EXIT_PREEMPTED,
                                                  HeartbeatWriter)
            hb = HeartbeatWriter(os.environ["TPUIC_HEARTBEAT_FILE"],
                                 min_interval_s=0.0)
            rank = int(os.environ["TPUIC_FLEET_RANK"])
            signal.signal(signal.SIGTERM,
                          lambda s, f: sys.exit(EXIT_PREEMPTED))
            hb.last_step = 1; hb.beat()
            if rank == 1:
                time.sleep(0.4); os.kill(os.getpid(), signal.SIGKILL)
            if rank == 2:
                time.sleep(1.4); os.kill(os.getpid(), signal.SIGKILL)
            while True:
                hb.beat(); time.sleep(0.05)
        """))
    sup = GangSupervisor(
        [sys.executable, child], os.path.join(work, "floor_state"),
        ranks=3, elastic=True, min_ranks=2, max_respawns=0,
        watchdog_s=30.0, startup_grace_s=30.0, poll_s=0.05, grace_s=10.0,
        backoff_s=0.05, backoff_max_s=0.1, env={"PYTHONPATH": _REPO})
    rc = sup.run()
    check(rc == EXIT_BELOW_MIN,
          f"second kill below min_ranks stopped the gang with the typed "
          f"verdict {EXIT_BELOW_MIN} (got {rc})")
    check(sup.degrades == 1,
          f"the FIRST kill produced exactly one degrade event "
          f"({sup.degrades})")
    evs = [json.loads(ln) for ln in open(sup.ledger_file)]
    give = [e for e in evs if e["event"] == "giveup"]
    check(bool(give) and "below min replicas" in give[0]["reason"],
          f"giveup names the typed cause ({give and give[0]['reason']})")
    exits0 = [e for e in evs if e["event"] == "exit" and e["rank"] == 0]
    check(bool(exits0) and exits0[-1]["returncode"] == EXIT_PREEMPTED,
          f"last survivor got its flush window — exit {EXIT_PREEMPTED} "
          f"(exits {[e['returncode'] for e in exits0]})")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--watchdog-s", type=float, default=30.0)
    p.add_argument("--workdir", default="",
                   help="run here instead of a temp dir (CI passes a "
                        "fixed path so the gang ledger / membership "
                        "file / per-rank dumps can be uploaded on "
                        "failure)")
    p.add_argument("--keep", action="store_true")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args()

    t_start = time.monotonic()
    work = args.workdir or tempfile.mkdtemp(prefix="tpuic_elastic_")
    os.makedirs(work, exist_ok=True)
    failures: list = []
    passed = False
    baseline = None

    def check(ok: bool, msg: str) -> None:
        print(("  ok  " if ok else "  FAIL") + f" {msg}")
        if not ok:
            failures.append(msg)

    try:
        print("[soak] typed floor: degrade on the first kill, "
              f"EXIT_BELOW_MIN {EXIT_BELOW_MIN} on the second")
        _floor_phase(work, check)
        if failures:
            return 1

        # -- dataset + parallel baseline --------------------------------
        from tpuic.data.synthetic import make_synthetic_imagefolder
        data = os.path.join(work, "data")
        make_synthetic_imagefolder(data, classes=("a", "b"),
                                   per_class=PER_CLASS, size=24)
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

        # -- the elastic 2-rank fleet -----------------------------------
        streams = os.path.join(work, "streams")
        os.makedirs(streams, exist_ok=True)
        state_dir = os.path.join(work, "supervise")
        gang_cmd = _train_cmd(data, os.path.join(work, "cp{rank}"),
                              os.path.join(work, "cache{rank}"),
                              os.path.join(streams, "events.jsonl"))
        print(f"[soak] elastic fleet of {RANKS} ranks under chaos "
              f"({'; '.join(s or 'fault-free' for s in CHAOS)})")
        sup = GangSupervisor(
            gang_cmd, state_dir, ranks=RANKS, elastic=True, min_ranks=1,
            watchdog_s=args.watchdog_s, startup_grace_s=600.0,
            quit_wait_s=2.0, grace_s=15.0, poll_s=0.25, max_restarts=4,
            max_respawns=4, backoff_s=0.25, backoff_max_s=2.0,
            heartbeat_interval_s=0.2, chaos=CHAOS,
            ckpt_dirs=os.path.join(work, "cp{rank}", "resnet18-cifar"),
            env=dict(env, PYTHONPATH=_REPO))
        rc = sup.run()
        base_rc = baseline.wait(timeout=900)

        # -- the verdict -------------------------------------------------
        print(f"[soak] fleet finished (exit {rc}, {sup.degrades} "
              f"degrade(s), {sup.rejoins} rejoin(s), respawns "
              f"{sup.respawns}); baseline exit {base_rc}")
        check(rc == 0, "elastic fleet completed cleanly (exit 0)")
        check(base_rc == 0, "baseline completed cleanly (exit 0)")
        check(sup.degrades == 1 and sup.rejoins == 1,
              f"exactly one degrade and one rejoin "
              f"({sup.degrades}/{sup.rejoins})")
        check(sup.respawns == {0: 0, CRASH_RANK: 2},
              f"the survivor was NEVER respawned and the flapping "
              f"replacement cost rank {CRASH_RANK} a second respawn "
              f"({sup.respawns})")
        check(sup.violations == 0,
              "zero per-rank step-accounting violations")

        ledger = [json.loads(ln) for ln in open(sup.ledger_file)]
        spawns0 = [e for e in ledger
                   if e["event"] == "spawn" and e["rank"] == 0]
        check(len(spawns0) == 1,
              f"ZERO survivor process restarts — one spawn record for "
              f"rank 0 in the whole ledger ({len(spawns0)})")
        degrade = [e for e in ledger if e["event"] == "degrade"]
        check(len(degrade) == 1
              and degrade[0]["resume_step"] == STEPS_PER_EPOCH,
              f"degrade re-formed from the fleet-agreed step "
              f"{STEPS_PER_EPOCH} — epoch 0's commit, not anything the "
              f"survivor ran ahead to "
              f"({[e.get('resume_step') for e in degrade]})")
        check(any(e["event"] == "flap" and e["rank"] == CRASH_RANK
                  for e in ledger),
              "the first replacement's death INSIDE its catch-up "
              "restore was booked as a flap")
        mem = [e["reason"] for e in ledger if e["event"] == "membership"]
        check(mem == ["init", "degrade", "rejoin"],
              f"membership timeline is exactly init->degrade->rejoin "
              f"(the flap added no transition): {mem}")
        final_view = read_membership(sup.membership_file)
        check(final_view is not None
              and final_view.active == list(range(RANKS)),
              f"final membership back to full strength "
              f"({final_view and final_view.active})")

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
            reforms = [r for r in recs
                       if r["event"] == "reform" and r.get("acted")]
            restarts = [r for r in recs if r["event"] == "restart"]
            if rank == 0:
                check(len(reforms) == 1
                      and reforms[0]["resume_step"] == STEPS_PER_EPOCH,
                      f"survivor re-formed IN PLACE from step "
                      f"{STEPS_PER_EPOCH} ({reforms})")
                check(not restarts,
                      f"survivor stream carries NO restart event — its "
                      f"process never died ({restarts})")
            else:
                check(bool(restarts),
                      f"replacement announced its respawned life "
                      f"({restarts})")
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

        # The aggregator over the per-rank streams: the elastic
        # membership-timeline gate passes; the strict gate still fires
        # on genuinely missing coverage.
        report_path = os.path.join(work, "fleet_report.json")
        cli = subprocess.run(
            [sys.executable, "-m", "tpuic.telemetry.fleet", streams,
             "--membership", sup.ledger_file, "--json", report_path],
            cwd=_REPO, env=env, text=True, capture_output=True,
            timeout=120)
        print(cli.stdout, end="")
        check(cli.returncode == 0,
              f"aggregator passed the elastic --membership gate "
              f"(exit {cli.returncode}; stderr "
              f"{cli.stderr.strip()[-200:]})")
        rep = (json.load(open(report_path))
               if os.path.exists(report_path) else {})
        tl = rep.get("membership", {})
        check(tl.get("ever_ranks") == list(range(RANKS))
              and [t["reason"] for t in tl.get("transitions", [])]
              == ["init", "degrade", "rejoin"],
              f"report carries the membership timeline ({tl.get('ever_ranks')}, "
              f"{[t.get('reason') for t in tl.get('transitions', [])]})")
        gate = subprocess.run(
            [sys.executable, "-m", "tpuic.telemetry.fleet", streams,
             "--require-ranks", str(RANKS + 1)],
            cwd=_REPO, env=env, text=True, capture_output=True,
            timeout=120)
        check(gate.returncode == 1,
              f"strict --require-ranks {RANKS + 1} still fails on the "
              f"missing rank (exit {gate.returncode})")

        took = time.monotonic() - t_start
        if failures:
            print(f"\nFAIL: {len(failures)} assertion(s) in {took:.1f}s")
            for f in failures:
                print(f"  - {f}")
            return 1
        print(f"\nOK: elastic soak green in {took:.1f}s — rank killed "
              f"mid-epoch degraded the fleet (zero survivor restarts), "
              f"the flapping replacement burned only its own budget, "
              f"the second replacement rejoined, and the final metrics "
              f"are bitwise-equal to the undisturbed baseline")
        passed = True
        return 0
    finally:
        if baseline is not None and baseline.poll() is None:
            baseline.kill()
            baseline.wait()
        if args.keep or not passed:
            print(f"workdir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
