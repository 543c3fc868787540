#!/usr/bin/env python
"""Prewarm smoke (ISSUE 18 acceptance; runs in tier-1 CI).

End-to-end proof of the compiled-program registry's restart path
(tpuic/compiled/, docs/performance.md "Compiled-program registry"): a
REAL supervised training run (`tpuic.runtime.supervisor.Supervisor`
driving the real `train.py` CLI as a child, CPU, synthetic data) is
SIGTERMed mid-epoch-1 (clean preemption flush, exit 43) and restarted.
The first life exported ``TPUIC_COMPILE_MANIFEST``, so its
``Trainer._build_steps`` left a prewarm manifest behind; the restarted
life finds it pre-existing and prewarms every listed program BEFORE its
first step, against the shared persistent XLA cache.

The verdict asserts, from the metrics JSONL both lives appended to:

- >= 1 automatic restart; the sigterm attempt exited with code 43,
- the manifest exists on disk and passes its CRC (tpuic.compiled
  refuses torn manifests — a load here is the integrity check),
- the restarted life emitted ``compile_cache action=prewarm_done``
  BETWEEN its 'restart' event and its first 'step' event,
- ZERO 'compile' events after prewarm_done, the first post-restart
  step included — every backend compile the resumed run will ever need
  (both the restored-state and the steady-state call signatures of each
  program) was paid up front by the prewarm,
- bitwise-equal gang resume: final checkpointed optimizer step and
  per-epoch eval accuracies identical to an UNDISTURBED parallel
  baseline (same config, no chaos, no manifest).

Exit 0 on success.   python scripts/prewarm_smoke.py [--keep] [-v]
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

from tpuic.runtime.supervisor import EXIT_PREEMPTED, Supervisor  # noqa: E402

# 24 train images / global batch 4 = 6 host-tracked steps per epoch;
# fault keys are global step numbers (0-based), so key 8 is mid-epoch-1
# — the restart resumes INSIDE an epoch, the harder geometry.
PER_CLASS = 12
BATCH = 4
EPOCHS = 2
CHAOS = ["sigterm@8",  # clean flush, exit 43, restart prewarms
         ""]           # fault-free final attempt completes


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
        f"  [smoke] skipping torn jsonl line in {path}: {ln[:80]!r}"))


def _evals(recs: list) -> dict:
    out = {}
    for r in recs:
        if r["event"] == "eval":
            out[int(r["epoch"])] = r["accuracy"]
    return out


def _final_meta_step(ckpt: str):
    try:
        man = json.load(open(os.path.join(ckpt, "resnet18-cifar",
                                          "latest.manifest.json")))
        return int(man["step"])
    except (OSError, ValueError, KeyError, TypeError):
        return None


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    # 60s (vs chaos_soak's 20): the longest legitimately silent span is
    # one cold backend-compile phase, which can exceed 20s on a loaded
    # CI box — hang detection is chaos_soak's contract, not this one's.
    p.add_argument("--watchdog-s", type=float, default=60.0)
    p.add_argument("--keep", action="store_true",
                   help="keep the temp workdir for inspection")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="stream child stdout/stderr instead of hiding it")
    args = p.parse_args()

    t_start = time.monotonic()
    work = tempfile.mkdtemp(prefix="tpuic_prewarm_")
    failures: list = []

    def check(ok: bool, msg: str) -> None:
        print(("  ok  " if ok else "  FAIL") + f" {msg}")
        if not ok:
            failures.append(msg)

    try:
        from tpuic.data.synthetic import make_synthetic_imagefolder
        data = os.path.join(work, "data")
        make_synthetic_imagefolder(data, classes=("a", "b"),
                                   per_class=PER_CLASS, size=24)
        # Shared persistent XLA cache across both lives AND the baseline
        # (identical env => identical trajectories; the restart's prewarm
        # compiles become disk reads). XLA_FLAGS overridden, not popped —
        # see chaos_soak.py for why. The cache directory is this run's own
        # even when JAX_COMPILATION_CACHE_DIR is given from outside: a cold
        # cache in the first life is what the smoke proves.
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TF_CPP_MIN_LOG_LEVEL="3", XLA_FLAGS="",
                   JAX_COMPILATION_CACHE_DIR=os.path.join(work,
                                                          "jax_cache"))

        base_jsonl = os.path.join(work, "baseline.jsonl")
        base_ckpt = os.path.join(work, "ckpt_base")
        sink = None if args.verbose else subprocess.DEVNULL
        print("[smoke] baseline (undisturbed, no manifest) started "
              "in parallel")
        baseline = subprocess.Popen(
            _train_cmd(data, base_ckpt, os.path.join(work, "cache_base"),
                       base_jsonl),
            cwd=_REPO, env=env, stdout=sink, stderr=sink)

        manifest = os.path.join(work, "programs.manifest.json")
        print(f"[smoke] supervised run: {len(CHAOS)} attempts "
              f"({', '.join(s or 'fault-free' for s in CHAOS)}), "
              f"prewarm manifest {manifest}")
        sup_jsonl = os.path.join(work, "supervised.jsonl")
        sup_ckpt = os.path.join(work, "ckpt_sup")
        sup = Supervisor(
            _train_cmd(data, sup_ckpt, os.path.join(work, "cache_sup"),
                       sup_jsonl),
            os.path.join(work, "supervise"), watchdog_s=args.watchdog_s,
            startup_grace_s=600.0, quit_wait_s=2.0, grace_s=5.0,
            poll_s=0.25, max_restarts=4, backoff_s=0.25, backoff_max_s=2.0,
            crash_loop_k=3, heartbeat_interval_s=0.2, chaos=CHAOS,
            env=dict(env, PYTHONPATH=_REPO,
                     TPUIC_COMPILE_MANIFEST=manifest))
        rc = sup.run()
        base_rc = baseline.wait(timeout=900)

        print(f"[smoke] supervised run finished (exit {rc}, "
              f"{len(sup.attempts)} attempts, {sup.restarts} restarts); "
              f"baseline exit {base_rc}")
        check(rc == 0, "supervised run completed cleanly (exit 0)")
        check(base_rc == 0, "baseline completed cleanly (exit 0)")
        check(sup.restarts >= 1,
              f"{sup.restarts} automatic restart(s) observed (>= 1)")
        codes = [a.returncode for a in sup.attempts]
        check(EXIT_PREEMPTED in codes,
              f"sigterm attempt exited {EXIT_PREEMPTED} per the contract "
              f"(attempt codes: {codes})")

        # -- manifest integrity (the reader IS the CRC check) -----------
        try:
            from tpuic.compiled import ProgramKey, load_manifest
            entries = load_manifest(manifest)
            models = sorted(ProgramKey.from_dict(e["key"]).model
                            for e in entries)
            check(len(entries) >= 2 and
                  any(m.endswith(":step") for m in models) and
                  any(m.endswith(":eval") for m in models),
                  f"manifest lists the train+eval step programs "
                  f"({models})")
        except Exception as e:
            check(False, f"prewarm manifest unreadable: {e}")

        # -- the steady-state contract, from the event stream -----------
        recs = _events(sup_jsonl)
        kinds = [r.get("event") for r in recs]
        check("restart" in kinds, "restarted life announced itself "
              "with a 'restart' event")
        last_restart = (len(kinds) - 1 - kinds[::-1].index("restart")
                        if "restart" in kinds else len(kinds))
        after = recs[last_restart:]
        after_kinds = [r.get("event") for r in after]
        first_step = (after_kinds.index("step")
                      if "step" in after_kinds else len(after))
        prewarms = [r for r in after[:first_step]
                    if r.get("event") == "compile_cache"
                    and r.get("action") == "prewarm_done"]
        pw_summary = [{k: r.get(k) for k in ("programs", "manifest_listed",
                                             "duration_s")}
                      for r in prewarms]
        check(len(prewarms) == 1,
              f"restarted life prewarmed before its first step "
              f"({pw_summary})")
        check(bool(prewarms) and prewarms[0].get("manifest_listed")
              == prewarms[0].get("programs") == 2,
              "prewarm covered both step programs, all manifest-listed")
        # Stronger than "after the first step": the prewarm executes
        # BOTH call signatures of each program (restored-state and
        # steady-state — see Trainer.prewarm), so even the first
        # post-restart step must dispatch without a single compile.
        pw_idx = (after.index(prewarms[0]) + 1 if prewarms else len(after))
        late_compiles = [r for r in after[pw_idx:]
                         if r.get("event") == "compile"]
        check(first_step < len(after) and not late_compiles,
              f"ZERO compiles after prewarm_done, first post-restart "
              f"step included ({len(late_compiles)} observed)")

        # -- bitwise-equal gang resume vs the undisturbed baseline ------
        b_recs = _events(base_jsonl)
        b_meta = _final_meta_step(base_ckpt)
        s_meta = _final_meta_step(sup_ckpt)
        check(b_meta is not None and s_meta == b_meta,
              f"final checkpointed optimizer step matches baseline "
              f"({s_meta} == {b_meta})")
        b_eval, s_eval = _evals(b_recs), _evals(recs)
        check(set(b_eval) == set(s_eval) == set(range(EPOCHS)),
              f"both runs evaluated every epoch (baseline {sorted(b_eval)}, "
              f"supervised {sorted(s_eval)})")
        check(b_eval == s_eval,
              f"per-epoch eval accuracy identical to baseline "
              f"({s_eval} == {b_eval})")

        took = time.monotonic() - t_start
        if failures:
            print(f"\nFAIL: {len(failures)} assertion(s) in {took:.1f}s")
            for f in failures:
                print(f"  - {f}")
            return 1
        print(f"\nOK: prewarm smoke green in {took:.1f}s — restart "
              f"prewarmed {prewarms[0].get('programs')} programs in "
              f"{prewarms[0].get('duration_s')}s, fit compile-flat, "
              f"resume bitwise-equal to baseline")
        return 0
    finally:
        if args.keep:
            print(f"workdir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
