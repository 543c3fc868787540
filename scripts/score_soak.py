#!/usr/bin/env python
"""Bulk-score soak (ISSUE 17 acceptance; runs in tier-1 CI).

The end-to-end proof of elastic bulk scoring (``python -m tpuic.score``
— TWO real worker processes on CPU sharing a results directory via the
file lease queue), raced against an UNDISTURBED single-worker baseline
over the same corpus and the same trained checkpoint:

- rank 1 is armed with ``scorer_crash@1#1``: it is SIGKILLed at its
  FIRST shard commit, in the nastiest window — result file linked into
  place, CRC manifest and ledger record not yet written;
- this soak is the launcher: it books the death into the PR-15
  membership file (init -> degrade -> rejoin) and launches a
  replacement rank 1, which picks up fresh leases mid-corpus;
- the survivors adopt the dead rank's published-but-unmanifested shard
  and RECOVER its missing ledger record (``recovered: true``) — a
  committed shard is never rescored, an uncommitted one never dropped;
- the fleet audit (``python -m tpuic.telemetry.fleet --score-ledger``)
  exits 0 on both jobs: scored + quarantined == corpus per shard and in
  total, ZERO duplicate commit records, zero drops;
- every per-shard result file is BITWISE equal between the disturbed
  elastic run and the undisturbed baseline (canonical result bytes);
- every worker's ``score_done`` reports ZERO steady-state compiles
  (the int8 ladder is warmed before the counter is zeroed);

plus both bidirectional arms: a seeded ``shard_corrupt@2#1`` lands
exactly one row in the ledger's quarantined column with the accounting
still exact (audit exit 0), and a tampered ledger copy — one commit
record duplicated, then one dropped — fails the audit loudly (exit 1).

Exit 0 on success.   python scripts/score_soak.py [--keep] [-v]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from tpuic.runtime.membership import (ENV_MEMBERSHIP_FILE,  # noqa: E402
                                      Membership, write_membership)
from tpuic.telemetry.fleet import (ENV_FLEET_RANK,  # noqa: E402
                                   ENV_FLEET_RANKS)

RANKS = 2
CRASH_RANK = 1
PER_CLASS = 16          # 2 classes x 16 -> 32-row val corpus
SHARD_SIZE = 4          # -> 8 shards: both ranks provably mid-corpus
BATCH = 4
DTYPE = "int8"          # the quant ladder rung the scorer defaults to
MODEL = "resnet18-cifar"
RESIZE = 24


def _score_cmd(data: str, out: str, ckpt: str) -> list:
    return [sys.executable, "-m", "tpuic.score",
            "--datadir", data, "--out", out, "--ckpt-dir", ckpt,
            "--model", "auto", "--dtype", DTYPE,
            "--shard-size", str(SHARD_SIZE), "--batchsize", str(BATCH),
            "--ttl", "10", "--poll", "0.1"]


def _events(paths: list) -> list:
    from tpuic.telemetry.events import read_jsonl
    recs: list = []
    for p in paths:
        recs.extend(read_jsonl(p, on_torn=lambda ln: print(
            f"  [soak] skipping torn jsonl line: {ln[:80]!r}")))
    return recs


def _audit(out: str, env: dict, report_path: str, prom: str = "") -> int:
    cmd = [sys.executable, "-m", "tpuic.telemetry.fleet", out,
           "--score-ledger", "--json", report_path]
    if prom:
        cmd += ["--prom-dump", prom]
    cli = subprocess.run(cmd, cwd=_REPO, env=env, text=True,
                         capture_output=True, timeout=120)
    print(cli.stdout, end="")
    if cli.returncode != 0:
        print(cli.stderr, end="", file=sys.stderr)
    return cli.returncode


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workdir", default="",
                   help="run here instead of a temp dir (CI passes a "
                        "fixed path so the ledgers / membership file / "
                        "per-rank streams can be uploaded on failure)")
    p.add_argument("--keep", action="store_true")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args()

    t_start = time.monotonic()
    work = args.workdir or tempfile.mkdtemp(prefix="tpuic_score_")
    os.makedirs(work, exist_ok=True)
    failures: list = []
    passed = False

    def check(ok: bool, msg: str) -> None:
        print(("  ok  " if ok else "  FAIL") + f" {msg}")
        if not ok:
            failures.append(msg)

    try:
        # -- corpus + a real trained checkpoint --------------------------
        from tpuic.data.synthetic import make_synthetic_imagefolder
        data = os.path.join(work, "data")
        make_synthetic_imagefolder(data, classes=("a", "b"),
                                   per_class=PER_CLASS, size=RESIZE)
        n_corpus = 2 * PER_CLASS
        n_shards = n_corpus // SHARD_SIZE
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TF_CPP_MIN_LOG_LEVEL="3", XLA_FLAGS="",
                   PYTHONPATH=_REPO)
        # A cache directory given from outside wins.
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(work, "jax_cache"))
        env.pop("TPUIC_FAULTS", None)
        sink = None if args.verbose else subprocess.DEVNULL
        ckpt = os.path.join(work, "ckpt")
        print(f"[soak] training the tiny {MODEL} checkpoint the corpus "
              "is scored against")
        train = subprocess.run(
            [sys.executable, os.path.join(_REPO, "train.py"),
             "--datadir", data, "--model", MODEL, "--resize", str(RESIZE),
             "--batchsize", "8", "--epochs", "1", "--optimizer", "sgd",
             "--lr", "0.01", "--no-class-weights", "--workers", "2",
             "--save-period", "1", "--ckpt-dir", ckpt, "--cache-dir",
             os.path.join(work, "cache")],
            cwd=_REPO, env=env, stdout=sink, stderr=sink, timeout=600)
        check(train.returncode == 0,
              f"trainer produced the checkpoint (exit {train.returncode})")
        if failures:
            return 1

        # -- undisturbed single-worker baseline --------------------------
        out_base = os.path.join(work, "score_base")
        print("[soak] baseline: one undisturbed worker over the corpus")
        base = subprocess.run(_score_cmd(data, out_base, ckpt), cwd=_REPO,
                              env=env, stdout=sink, stderr=sink,
                              timeout=600)
        check(base.returncode == 0,
              f"baseline scorer exit 0 (got {base.returncode})")
        check(_audit(out_base, env,
                     os.path.join(work, "audit_base.json")) == 0,
              "baseline ledger audit exact (exit 0)")

        # -- the elastic 2-worker run under scorer_crash -----------------
        out_el = os.path.join(work, "score_elastic")
        member = os.path.join(work, "membership.json")
        write_membership(member, Membership(
            version=1, world=RANKS, active=list(range(RANKS)),
            resume_step=None, reason="init", t=time.time()))
        renv = [dict(env, **{ENV_FLEET_RANK: str(r),
                             ENV_FLEET_RANKS: str(RANKS),
                             ENV_MEMBERSHIP_FILE: member})
                for r in range(RANKS)]
        # Rank 1 dies at its FIRST commit, after the link, before the
        # manifest — the crash window the adopt/recover path exists for.
        renv[CRASH_RANK]["TPUIC_FAULTS"] = f"scorer_crash@1#{CRASH_RANK}"
        print(f"[soak] elastic fleet of {RANKS} workers; rank "
              f"{CRASH_RANK} armed scorer_crash@1#{CRASH_RANK}")
        # The armed rank launches first: it dies at its FIRST commit, so
        # a head start guarantees the kill fires even if the peer turns
        # out much faster — the peer is then provably mid-corpus when
        # the replacement picks up the pieces.
        w1 = subprocess.Popen(_score_cmd(data, out_el, ckpt), cwd=_REPO,
                              env=renv[CRASH_RANK], stdout=sink,
                              stderr=sink)
        w0 = subprocess.Popen(_score_cmd(data, out_el, ckpt), cwd=_REPO,
                              env=renv[0], stdout=sink, stderr=sink)
        rc1 = w1.wait(timeout=600)
        check(rc1 == -9, f"rank {CRASH_RANK} was SIGKILLed mid-corpus "
                         f"by scorer_crash (exit {rc1})")
        write_membership(member, Membership(
            version=2, world=RANKS, active=[0], resume_step=None,
            reason="degrade", rank=CRASH_RANK, t=time.time()))
        print(f"[soak] degrade booked; launching replacement rank "
              f"{CRASH_RANK}")
        renv[CRASH_RANK].pop("TPUIC_FAULTS")
        w1b = subprocess.Popen(_score_cmd(data, out_el, ckpt), cwd=_REPO,
                               env=renv[CRASH_RANK], stdout=sink,
                               stderr=sink)
        write_membership(member, Membership(
            version=3, world=RANKS, active=list(range(RANKS)),
            resume_step=None, reason="rejoin", rank=CRASH_RANK,
            t=time.time()))
        rc0 = w0.wait(timeout=600)
        rc1b = w1b.wait(timeout=600)
        check(rc0 == 0, f"survivor rank 0 finished the job (exit {rc0})")
        check(rc1b == 0, f"replacement rank {CRASH_RANK} finished "
                         f"cleanly (exit {rc1b})")

        # -- the verdict -------------------------------------------------
        report_path = os.path.join(work, "audit_elastic.json")
        prom_path = os.path.join(work, "score_elastic.prom")
        check(_audit(out_el, env, report_path, prom=prom_path) == 0,
              "elastic ledger audit exact (exit 0) despite the SIGKILL")
        rep = (json.load(open(report_path))
               if os.path.exists(report_path) else {})
        check(rep.get("n") == n_corpus
              and rep.get("shards_committed") == n_shards,
              f"all {n_shards} shards of the {n_corpus}-row corpus "
              f"committed ({rep.get('shards_committed')}/{rep.get('n')})")
        check(rep.get("rows_scored", -1) + rep.get("rows_quarantined", -1)
              == n_corpus and rep.get("rows_quarantined") == 0,
              f"scored + quarantined == corpus with nothing quarantined "
              f"({rep.get('rows_scored')} + {rep.get('rows_quarantined')})")
        check(rep.get("shards_duplicated") == 0,
              "ZERO duplicate commit records fleet-wide")
        check(rep.get("recovered_records", 0) >= 1,
              f"the dead rank's missing ledger record was RECOVERED by "
              f"a survivor ({rep.get('recovered_records')})")
        prom = open(prom_path).read() if os.path.exists(prom_path) else ""
        check("tpuic_score_ledger_exact 1" in prom,
              "prom exposition carries the exactness gauge")

        base_shards = sorted(glob.glob(os.path.join(out_base, "results",
                                                    "shard-*.jsonl")))
        el_shards = sorted(glob.glob(os.path.join(out_el, "results",
                                                  "shard-*.jsonl")))
        check(len(base_shards) == len(el_shards) == n_shards,
              f"both runs published all {n_shards} shard files")
        diff = [os.path.basename(b) for b, e in zip(base_shards, el_shards)
                if open(b, "rb").read() != open(e, "rb").read()]
        check(not diff,
              "every per-shard result file BITWISE equal to the "
              f"undisturbed baseline (diffs: {diff})")

        dones = [r for r in _events(sorted(
            glob.glob(os.path.join(out_el, "*.jsonl"))
            + glob.glob(os.path.join(out_base, "*.jsonl"))))
            if r.get("event") == "score_done"]
        check(len(dones) == 3,  # baseline + survivor + replacement
              f"every completed worker published score_done "
              f"({len(dones)}; the SIGKILLed life publishes none)")
        compiles = {(r.get("rank"), r.get("steady_compiles"))
                    for r in dones}
        check(all(c == 0 for _, c in compiles),
              f"ZERO steady-state compiles on every worker ({compiles})")

        # -- bidirectional arm: seeded shard_corrupt quarantines ---------
        out_q = os.path.join(work, "score_corrupt")
        print("[soak] bidirectional: shard_corrupt@2#1 must quarantine "
              "exactly one row, accounting still exact")
        q = subprocess.run(_score_cmd(data, out_q, ckpt), cwd=_REPO,
                           env=dict(env, TPUIC_FAULTS="shard_corrupt@2#1"),
                           stdout=sink, stderr=sink, timeout=600)
        check(q.returncode == 0,
              f"seeded-corruption scorer exit 0 (got {q.returncode})")
        qrep_path = os.path.join(work, "audit_corrupt.json")
        check(_audit(out_q, env, qrep_path) == 0,
              "quarantine kept the audit exact (exit 0)")
        qrep = (json.load(open(qrep_path))
                if os.path.exists(qrep_path) else {})
        check(qrep.get("rows_quarantined") == 1
              and qrep.get("rows_scored") == n_corpus - 1,
              f"exactly one row in the quarantined column "
              f"({qrep.get('rows_scored')} + {qrep.get('rows_quarantined')})")
        qcommits = [r for r in _events(sorted(glob.glob(
            os.path.join(out_q, "*.jsonl"))))
            if r.get("event") == "score_commit" and r.get("shard") == 2]
        check(len(qcommits) == 1 and qcommits[0]["quarantined"] == 1,
              f"shard 2's commit record carries the quarantined count "
              f"({[c.get('quarantined') for c in qcommits]})")

        # -- bidirectional arm: a tampered ledger fails loudly -----------
        print("[soak] bidirectional: tampered ledger copies must FAIL "
              "the audit")
        streams = sorted(glob.glob(os.path.join(out_el, "*.jsonl")))
        lines = [ln for s in streams
                 for ln in open(s).read().splitlines(keepends=True)]
        commit_ln = next(ln for ln in lines if '"score_commit"' in ln)
        tam_dup = os.path.join(work, "tampered_dup")
        os.makedirs(tam_dup, exist_ok=True)
        with open(os.path.join(tam_dup, "ledger.jsonl"), "w") as f:
            f.writelines(lines + [commit_ln])
        check(_audit(tam_dup, env,
                     os.path.join(work, "audit_dup.json")) == 1,
              "a DUPLICATED commit record fails the audit (exit 1)")
        tam_drop = os.path.join(work, "tampered_drop")
        os.makedirs(tam_drop, exist_ok=True)
        with open(os.path.join(tam_drop, "ledger.jsonl"), "w") as f:
            f.writelines(ln for ln in lines if ln != commit_ln)
        check(_audit(tam_drop, env,
                     os.path.join(work, "audit_drop.json")) == 1,
              "a DROPPED commit record fails the audit (exit 1)")

        took = time.monotonic() - t_start
        if failures:
            print(f"\nFAIL: {len(failures)} assertion(s) in {took:.1f}s")
            for f in failures:
                print(f"  - {f}")
            return 1
        print(f"\nOK: bulk-score soak green in {took:.1f}s — a worker "
              f"SIGKILLed inside the commit window lost nothing: the "
              f"fleet adopted its shard, recovered its ledger record, "
              f"the audit is exact, and every result byte matches the "
              f"undisturbed baseline")
        passed = True
        return 0
    finally:
        for proc in ("w0", "w1", "w1b"):
            h = locals().get(proc)
            if h is not None and h.poll() is None:
                h.kill()
                h.wait()
        if args.keep or not passed:
            print(f"workdir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
