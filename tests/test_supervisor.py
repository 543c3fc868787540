"""Training supervisor (ISSUE 5): heartbeat protocol, watchdog hang
escalation, the exit-code contract, the crash-loop policy — plus the
satellite regressions (PreemptionGuard latch reuse, multi-process
``agree()`` coverage, no allgather when preemption handling is off).

Supervisor tests run REAL child processes, but the children import only
``tpuic.runtime.supervisor`` (stdlib-only by design), so each attempt
costs a bare interpreter start, not a jax session — the whole module is
tier-1. The full-fat end-to-end (real train.py under real faults) is
``scripts/chaos_soak.py``, CI-gated next to this suite."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import types

import pytest

from tpuic.runtime.supervisor import (EXIT_CRASH_LOOP, EXIT_OK, EXIT_POISON,
                                      EXIT_PREEMPTED, DONE, POISON, PREEMPTED,
                                      RETRYABLE, HeartbeatWriter,
                                      NonRetryableError, Supervisor,
                                      classify_exit, read_heartbeat,
                                      restart_info)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Children talk the real protocol through the real HeartbeatWriter; the
# import is stdlib-only, so a child attempt is ~a bare python startup.
_CHILD_PRELUDE = textwrap.dedent("""\
    import os, signal, sys, time
    from tpuic.runtime.supervisor import (EXIT_PREEMPTED, EXIT_POISON,
                                          HeartbeatWriter,
                                          install_stack_dump_handler)
    hb = HeartbeatWriter(os.environ["TPUIC_HEARTBEAT_FILE"],
                         min_interval_s=0.0)
    attempt = int(os.environ.get("TPUIC_RESTART", "0"))
    def beat(step):
        hb.last_step = step
        hb.beat()
""")


def _child(tmp_path, body: str) -> list:
    path = os.path.join(str(tmp_path), "child.py")
    with open(path, "w") as f:
        f.write(_CHILD_PRELUDE + textwrap.dedent(body))
    return [sys.executable, path]


def _sup(tmp_path, cmd, **kw) -> Supervisor:
    kw.setdefault("watchdog_s", 30.0)
    kw.setdefault("startup_grace_s", 60.0)
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("backoff_s", 0.01)
    kw.setdefault("backoff_max_s", 0.05)
    kw.setdefault("env", {"PYTHONPATH": REPO})
    return Supervisor(cmd, os.path.join(str(tmp_path), "state"), **kw)


# -- heartbeat protocol ------------------------------------------------------
def test_heartbeat_writer_roundtrip_throttle_and_age(tmp_path):
    path = str(tmp_path / "hb.json")
    beats = []
    hb = HeartbeatWriter(path, min_interval_s=10.0,
                         publish=lambda kind, **d: beats.append((kind, d)))
    ev = types.SimpleNamespace(kind="step", data={"step": 7})
    hb(ev)
    rec = read_heartbeat(path)
    assert rec["step"] == 7 and rec["beats"] == 1
    assert rec["pid"] == os.getpid()
    assert beats == [("heartbeat", {"step": 7, "beats": 1})]
    # Throttled: a second event inside min_interval_s writes nothing.
    hb(types.SimpleNamespace(kind="step", data={"step": 8}))
    assert read_heartbeat(path)["step"] == 7
    assert 0.0 <= hb.age_s() < 10.0
    # Non-step events beat (liveness) without claiming step progress.
    hb2 = HeartbeatWriter(path, min_interval_s=0.0)
    hb2(types.SimpleNamespace(kind="eval", data={"epoch": 1}))
    assert read_heartbeat(path)["step"] is None


def test_heartbeat_writer_ignores_its_own_echo(tmp_path):
    hb = HeartbeatWriter(str(tmp_path / "hb.json"), min_interval_s=0.0)
    hb(types.SimpleNamespace(kind="heartbeat", data={"step": 1}))
    assert hb.beats == 0 and read_heartbeat(str(tmp_path / "hb.json")) is None


def test_heartbeat_writer_tolerates_unwritable_target(tmp_path):
    # Target path is an existing non-empty DIRECTORY: the tmp write
    # succeeds but os.replace fails — the run the heartbeat protects
    # must survive (the supervisor sees staleness, the honest signal).
    target = tmp_path / "adir"
    target.mkdir()
    (target / "x").write_text("")
    hb = HeartbeatWriter(str(target), min_interval_s=0.0)
    assert hb.beat() is False
    assert hb.age_s() is None


def test_read_heartbeat_absent_and_garbage(tmp_path):
    assert read_heartbeat(str(tmp_path / "nope.json")) is None
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    assert read_heartbeat(str(p)) is None
    p.write_text("[1, 2]")  # parseable, wrong shape
    assert read_heartbeat(str(p)) is None


def test_restart_info_env_protocol(monkeypatch):
    monkeypatch.delenv("TPUIC_RESTART", raising=False)
    assert restart_info() is None
    monkeypatch.setenv("TPUIC_RESTART", "0")
    assert restart_info() is None  # first attempt is not a restart
    monkeypatch.setenv("TPUIC_RESTART", "2")
    monkeypatch.setenv("TPUIC_DOWN_SINCE", repr(time.time() - 5.0))
    count, down = restart_info()
    assert count == 2 and 4.0 < down < 60.0
    monkeypatch.setenv("TPUIC_RESTART", "junk")
    assert restart_info() is None


# -- exit-code contract ------------------------------------------------------
def test_classify_exit_contract_table():
    assert classify_exit(EXIT_OK) == DONE
    assert classify_exit(EXIT_PREEMPTED) == PREEMPTED
    assert classify_exit(EXIT_POISON) == POISON
    for rc in (1, 2, 77, -9, -11):  # crashes and signal deaths retry
        assert classify_exit(rc) == RETRYABLE
    # Supervisor itself evicted: the flush propagates, nothing restarts.
    assert classify_exit(EXIT_PREEMPTED, shutting_down=True) == DONE
    assert classify_exit(EXIT_OK, shutting_down=True) == DONE
    assert classify_exit(1, shutting_down=True) == POISON


def test_nonretryable_is_a_runtime_error():
    # PR-2 handlers/tests matching RuntimeError keep working.
    with pytest.raises(RuntimeError):
        raise NonRetryableError("poison")


# -- the supervision loop ----------------------------------------------------
def test_clean_exit_no_restart(tmp_path):
    sup = _sup(tmp_path, _child(tmp_path, """
        beat(3)
        sys.exit(0)
    """))
    assert sup.run() == 0
    assert sup.restarts == 0 and len(sup.attempts) == 1
    assert sup.best_step == 3 and not sup.attempts[0].hung


def test_retryable_crash_restarts_and_tracks_progress(tmp_path):
    sup = _sup(tmp_path, _child(tmp_path, """
        if attempt == 0:
            beat(3)
            os._exit(1)
        beat(4)  # resumes at best + 1: progress, no accounting violation
        sys.exit(0)
    """))
    assert sup.run() == 0
    assert sup.restarts == 1 and len(sup.attempts) == 2
    assert sup.attempts[0].returncode == 1 and sup.best_step == 4
    assert sup.violations == 0
    events = [json.loads(ln)["event"]
              for ln in open(os.path.join(sup.state_dir, "ledger.jsonl"))]
    assert events.count("spawn") == 2 and events[-1] == "done"


def test_poison_exit_is_not_restarted(tmp_path):
    sup = _sup(tmp_path, _child(tmp_path, """
        beat(1)
        sys.exit(EXIT_POISON)
    """))
    assert sup.run() == EXIT_POISON
    assert sup.restarts == 0 and len(sup.attempts) == 1


def test_preemption_flush_restarts_with_resume(tmp_path):
    sup = _sup(tmp_path, _child(tmp_path, """
        if attempt == 0:
            beat(2)
            sys.exit(EXIT_PREEMPTED)
        beat(4)
        sys.exit(0)
    """))
    assert sup.run() == 0
    assert sup.restarts == 1 and sup.attempts[0].returncode == EXIT_PREEMPTED
    assert sup.best_step == 4


def test_crash_loop_gives_up_with_diagnosis(tmp_path):
    """The acceptance-criteria case: a deterministic failure must end in
    exit 45 with a crash-loop verdict, not an infinite restart loop."""
    sup = _sup(tmp_path,
               [sys.executable, "-c", "import sys; sys.exit(7)"],
               crash_loop_k=2, max_restarts=10)
    assert sup.run() == EXIT_CRASH_LOOP
    # 2 no-progress ATTEMPTS, but only 1 restart actually happened —
    # the giveup verdict must not invent a restart that never ran.
    assert sup.restarts == 1 and len(sup.attempts) == 2
    last = [json.loads(ln)
            for ln in open(os.path.join(sup.state_dir, "ledger.jsonl"))][-1]
    assert last["event"] == "giveup" and "crash loop" in last["reason"]


def test_preemption_flushes_do_not_consume_restart_budget(tmp_path):
    """A preemptible fleet evicting a healthy run N times is the fleet
    working as designed: only RETRYABLE failures count against
    --max-restarts, so three flushes survive a budget of one."""
    sup = _sup(tmp_path, _child(tmp_path, """
        if attempt < 3:
            beat(attempt + 1)
            sys.exit(EXIT_PREEMPTED)
        beat(4)
        sys.exit(0)
    """), max_restarts=1)
    assert sup.run() == 0
    assert sup.restarts == 3 and sup.crash_restarts == 0
    assert sup.best_step == 4


def test_progressing_flush_resets_crash_loop_counter(tmp_path):
    """Progress made during ANY life resets the no-progress streak: a
    crash / progressing-flush / crash / progressing-flush alternation is
    a run moving forward, not a crash loop."""
    sup = _sup(tmp_path, _child(tmp_path, """
        if attempt in (0, 2):
            os._exit(1)          # crash before any step: no progress
        if attempt in (1, 3):
            beat(attempt * 10)   # flush WITH progress: streak resets
            sys.exit(EXIT_PREEMPTED)
        beat(100)
        sys.exit(0)
    """), crash_loop_k=2)
    assert sup.run() == 0
    assert sup.crash_restarts == 2 and sup.restarts == 4


def test_no_progress_preemption_loop_trips_crash_loop(tmp_path):
    """A preemption flush that re-fires before any step lands (stale
    fault spec, instantly-evicting scheduler) is exempt from the restart
    BUDGET but not from the no-progress verdict — without it the
    supervisor would respawn forever at full speed."""
    sup = _sup(tmp_path, _child(tmp_path, """
        hb.beat()   # alive, but no step ever lands
        sys.exit(EXIT_PREEMPTED)
    """), crash_loop_k=2)
    assert sup.run() == EXIT_CRASH_LOOP
    assert sup.crash_restarts == 0 and sup.restarts == 1
    assert len(sup.attempts) == 2


def test_shutdown_signal_death_exit_code_stays_in_range(tmp_path):
    """Supervisor evicted + child ignores the forwarded SIGTERM and is
    SIGKILLed: the reported exit status must be the 128+N shell
    convention, not sys.exit(-9)'s meaningless OS status 247."""
    sup = _sup(tmp_path, _child(tmp_path, """
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        beat(1)
        time.sleep(60)
    """), grace_s=0.5)
    hb = sup.heartbeat_file
    import threading
    t = threading.Thread(target=lambda: sup._on_signal(signal.SIGTERM, None))
    code = {}

    def run():
        code["rc"] = sup.run()

    runner = threading.Thread(target=run)
    runner.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and read_heartbeat(hb) is None:
        time.sleep(0.05)
    assert read_heartbeat(hb) is not None, "child never heartbeated"
    t.start()
    t.join()
    runner.join(timeout=30)
    assert not runner.is_alive()
    assert code["rc"] == 128 + signal.SIGKILL  # 137, in contract range


def test_restart_budget_bounds_even_with_progress(tmp_path):
    # Each attempt progresses one step then dies: the crash-loop check
    # never trips, but the total budget still must.
    sup = _sup(tmp_path, _child(tmp_path, """
        beat(attempt + 1)
        os._exit(1)
    """), max_restarts=2, crash_loop_k=10)
    assert sup.run() == EXIT_CRASH_LOOP
    assert len(sup.attempts) == 3  # initial + 2 restarts


def test_hang_watchdog_escalates_and_captures_stack_dump(tmp_path):
    """No heartbeat change past the watchdog window: SIGQUIT first (the
    child's faulthandler writes an all-thread dump to the supervisor's
    per-attempt artifact), then SIGTERM, then SIGKILL — even for a child
    that ignores SIGTERM (the wedge the cooperative latch can't fix)."""
    sup = _sup(tmp_path, _child(tmp_path, """
        install_stack_dump_handler()
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        beat(1)
        while True:
            time.sleep(0.2)
    """), watchdog_s=0.6, quit_wait_s=1.5, grace_s=0.5, max_restarts=0)
    assert sup.run() == EXIT_CRASH_LOOP  # budget 0: report, don't retry
    (attempt,) = sup.attempts
    assert attempt.hung and attempt.last_step == 1
    dump = os.path.join(sup.state_dir, "stackdump-0.txt")
    body = open(dump).read()
    assert "File" in body  # a real traceback, not an empty artifact
    events = [json.loads(ln)["event"]
              for ln in open(os.path.join(sup.state_dir, "ledger.jsonl"))]
    assert "hang" in events


def test_heartbeat_records_exact_first_step_despite_throttle(tmp_path):
    """Every step EVENT updates first_step even when the write throttle
    suppresses most writes — the accounting check compares true first
    steps, not whichever step a throttled write happened to sample."""
    path = str(tmp_path / "hb.json")
    hb = HeartbeatWriter(path, min_interval_s=0.0)
    hb(types.SimpleNamespace(kind="step", data={"step": 7}))
    hb(types.SimpleNamespace(kind="step", data={"step": 8}))
    rec = read_heartbeat(path)
    assert rec["first_step"] == 7 and rec["step"] == 8


def test_heartbeat_commit_event_bypasses_write_throttle(tmp_path):
    """A checkpoint commit moves the resume point: the file must carry
    the newest observed step immediately, not when the throttle next
    expires — otherwise the supervisor's best_step lags the committed
    step and the resumed life's legitimate first step is flagged as
    skipping past it."""
    path = str(tmp_path / "hb.json")
    hb = HeartbeatWriter(path, min_interval_s=60.0)
    hb(types.SimpleNamespace(kind="step", data={"step": 7}))
    hb(types.SimpleNamespace(kind="step", data={"step": 8}))  # throttled
    assert read_heartbeat(path)["step"] == 7
    hb(types.SimpleNamespace(kind="checkpoint_commit", data={"step": 8}))
    assert read_heartbeat(path)["step"] == 8


def test_stepless_healthy_lives_do_not_accumulate_crash_loop(tmp_path):
    """A supervised tpuic.serve emits beats, never steps: healthy lives
    that each outlive startup grace + a full watchdog window (so they
    were demonstrably beating — a wedge would have been hang-killed)
    must not add up to a 'deterministic failure' crash-loop verdict,
    no matter how many crashes the streak spans."""
    sup = _sup(tmp_path, _child(tmp_path, """
        if attempt < 3:
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.9:
                hb.beat()
                time.sleep(0.05)
            os._exit(1)
        sys.exit(0)
    """), watchdog_s=0.3, startup_grace_s=0.3, crash_loop_k=2,
               max_restarts=10)
    assert sup.run() == 0
    assert sup.restarts == 3 and sup.violations == 0


def test_no_spurious_violation_when_first_write_is_late(tmp_path):
    """Fast steps + a throttled writer: the first WRITTEN heartbeat the
    supervisor samples may already be far past best-previous + 1. The
    payload's exact first_step must win over the sampled step, so no
    violation is recorded."""
    sup = _sup(tmp_path, _child(tmp_path, """
        import types
        if attempt == 0:
            beat(5)
            os._exit(1)
        # Resumed life: steps 6..20 ran, but only the LAST write landed
        # (throttle) — the supervisor samples step 20 first. first_step
        # carried in the payload says 6: legitimate resume, no skip.
        hb.first_step = 6
        beat(20)
        sys.exit(0)
    """))
    assert sup.run() == 0
    assert sup.violations == 0 and sup.best_step == 20


def test_ledger_flags_step_accounting_violation(tmp_path):
    """A resumed attempt starting PAST best-previous-step + 1 means steps
    were silently skipped — counted and ledgered, the cross-restart half
    of the Trainer._validated_start_step contract."""
    sup = _sup(tmp_path, _child(tmp_path, """
        if attempt == 0:
            beat(5)
            os._exit(1)
        beat(50)
        sys.exit(0)
    """))
    assert sup.run() == 0
    assert sup.violations == 1
    recs = [json.loads(ln)
            for ln in open(os.path.join(sup.state_dir, "ledger.jsonl"))]
    v = [r for r in recs if r["event"] == "violation"]
    assert v and v[0]["first_step"] == 50 and v[0]["best_step"] == 5


# -- python -m tpuic.supervise ----------------------------------------------
def test_supervise_cli_requires_a_child_command(capsys):
    from tpuic.supervise import main
    assert main(["--state-dir", "/tmp/unused"]) == 2


def test_supervise_cli_end_to_end_and_shared_eviction(tmp_path):
    """The CLI path, plus the shared-eviction branch: SIGTERM to the
    SUPERVISOR forwards to the child (preemption flush, exit 43) and the
    supervisor exits 43 itself instead of restarting."""
    state = str(tmp_path / "state")
    cmd = [sys.executable, "-m", "tpuic.supervise", "--state-dir", state,
           "--startup-grace-s", "60", "--grace-s", "10", "--poll-s", "0.05",
           "--"] + _child(tmp_path, """
        stop = []
        signal.signal(signal.SIGTERM, lambda s, f: stop.append(1))
        t0 = time.time()
        while not stop and time.time() - t0 < 30:
            beat(1)
            time.sleep(0.05)
        sys.exit(EXIT_PREEMPTED if stop else 1)
    """)
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.Popen(cmd, cwd=REPO, env=env)
    hb = os.path.join(state, "heartbeat.json")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and read_heartbeat(hb) is None:
        time.sleep(0.05)
    assert read_heartbeat(hb) is not None, "child never heartbeated"
    proc.send_signal(signal.SIGTERM)
    assert proc.wait(timeout=30) == EXIT_PREEMPTED


# -- heartbeat wiring through the telemetry bus ------------------------------
def test_train_telemetry_heartbeat_zero_syncs_zero_compiles(tmp_path,
                                                            monkeypatch):
    """The tentpole's measurement contract: the heartbeat piggybacks on
    events the loop already publishes — adding it performs no device
    transfers and no compiles (tpuic.analysis.runtime checkers)."""
    from tpuic import telemetry
    from tpuic.analysis import runtime as contracts
    from tpuic.config import RunConfig
    from tpuic.telemetry.events import bus, publish

    path = str(tmp_path / "hb.json")
    monkeypatch.setenv("TPUIC_HEARTBEAT_FILE", path)
    monkeypatch.setenv("TPUIC_HEARTBEAT_INTERVAL_S", "0.0")
    tm = telemetry.TrainTelemetry(RunConfig())
    try:
        assert tm.heartbeat is not None
        with contracts.watch_compiles() as cw, \
                contracts.count_device_gets() as gets:
            for s in range(1, 6):
                publish("step", step=s, total_ms=1.0)
            publish("checkpoint_commit", track="latest", phase="commit")
        assert gets.count == 0 and cw.compiles == 0
        rec = read_heartbeat(path)
        assert rec["step"] == 5 and rec["beats"] >= 2
        # The writer's own 'heartbeat' echo is published for JSONL sinks
        # but never re-consumed (no feedback loop).
        assert bus.sink_errors == 0
    finally:
        tm.close()


def test_train_telemetry_without_heartbeat_env(monkeypatch):
    from tpuic import telemetry
    from tpuic.config import RunConfig
    monkeypatch.delenv("TPUIC_HEARTBEAT_FILE", raising=False)
    tm = telemetry.TrainTelemetry(RunConfig())
    try:
        assert tm.heartbeat is None
    finally:
        tm.close()


# -- satellite: PreemptionGuard latch reuse ----------------------------------
def test_preemption_guard_fresh_span_clears_stale_latch():
    """Regression (ISSUE 5 satellite): uninstall() deliberately leaves
    the latch readable, so a guard REUSED across fit() calls must clear
    it when a new span begins — otherwise fit() #2 sees 'triggered' at
    step 0 and spuriously flushes."""
    from tpuic.runtime.preemption import PreemptionGuard
    g = PreemptionGuard(signals=())
    g.install()
    g.trigger()
    assert g.triggered
    g.uninstall()
    assert g.triggered          # still readable post-span (callers branch)
    g.install()
    assert not g.triggered      # ...but a fresh span starts clean
    g.uninstall()


def test_preemption_guard_reentrant_install_keeps_trigger():
    """The other half of the contract: install() on an ALREADY-installed
    guard is a no-op — a cooperative trigger() armed between the outer
    install() and fit()'s own install() must survive."""
    from tpuic.runtime.preemption import PreemptionGuard
    g = PreemptionGuard(signals=())
    g.install()
    g.trigger()
    g.install()                 # fit()'s re-entrant call
    assert g.triggered
    g.uninstall()


def test_preemption_guard_reentrant_install_off_main_thread():
    """Off the main thread no signal handler can be registered, but the
    span must still be marked begun: a re-entrant install() there must
    not re-clear a cooperative trigger() (regression — the fresh-span
    clear ran before the thread early-return)."""
    import threading

    from tpuic.runtime.preemption import PreemptionGuard
    g = PreemptionGuard()  # real signals: forces the thread early-return
    out = {}

    def worker():
        g.install()
        g.trigger()
        g.install()          # fit()'s re-entrant call, same thread
        out["triggered"] = g.triggered

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert out["triggered"] is True
    g.uninstall()


def test_preemption_guard_main_thread_install_after_worker_span():
    """A span begun off the main thread can't register handlers — but a
    later install() ON the main thread (a guard constructed in a worker
    and handed to fit()) must still register them, without re-clearing a
    latch set in between: handler registration is tracked separately
    from the span flag."""
    import threading

    from tpuic.runtime.preemption import PreemptionGuard
    g = PreemptionGuard()
    t = threading.Thread(target=g.install)
    t.start()
    t.join()
    g.trigger()                  # cooperative shutdown armed in between
    g.install()                  # fit()'s own call, now on the main thread
    try:
        assert g.triggered       # the latch survived
        assert signal.getsignal(signal.SIGTERM) == g._handler
    finally:
        g.uninstall()
    assert signal.getsignal(signal.SIGTERM) != g._handler


# -- satellite: agree() beyond the single-process early-return ---------------
def test_agree_multiprocess_or_reduce(monkeypatch):
    import numpy as np

    import jax
    from jax.experimental import multihost_utils
    from tpuic.runtime import preemption

    calls = []
    other_host = {"flag": False}

    def fake_allgather(arr):
        calls.append(np.asarray(arr).tolist())
        return np.asarray([[bool(np.asarray(arr)[0])],
                           [other_host["flag"]]])

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", fake_allgather)
    assert preemption.agree(False) is False       # nobody latched
    other_host["flag"] = True
    assert preemption.agree(False) is True        # OR-reduce: the OTHER
    assert preemption.agree(True) is True         # host's latch counts
    other_host["flag"] = False
    assert preemption.agree(True) is True         # ...and so does ours
    assert calls == [[False], [False], [True], [True]]


def _loop_stub(*, handle_preemption: bool, steps: int):
    """A duck-typed Trainer just rich enough to run the REAL
    Trainer.train_epoch body — no model, no compile; the point is the
    loop's preemption-polling control flow, not the math."""
    import numpy as np

    from tpuic.config import RunConfig

    batch = {"image": np.zeros((2, 4, 4, 3), np.float32),
             "label": np.zeros((2,), np.int64),
             "mask": np.ones((2,), np.float32),
             "indices": np.arange(2)}

    class _Steptime:
        last_step = 0
        # the epoch's first loader wait and dispatch (epoch.* spans)
        first_batch = first_dispatch = (0.0, 0.0)

        def epoch_start(self):
            pass

        def wrap_epoch(self, it):
            return it

        def dispatch_start(self):
            pass

        def dispatch_end(self):
            pass

        def step_end(self, step):
            return {}

    class _Loader:
        global_batch = 2
        quarantine_count = 0
        last_epoch_ahead = 0

        def __len__(self):
            return steps

        def epoch(self, epoch, start_step=0):
            return iter([batch] * (steps - start_step))

    from tpuic.runtime.preemption import PreemptionGuard
    stub = types.SimpleNamespace(
        cfg=types.SimpleNamespace(run=RunConfig(
            log_every_steps=10 ** 6,  # no drains: loop control flow only
            handle_preemption=handle_preemption)),
        telemetry=types.SimpleNamespace(steptime=_Steptime()),
        train_loader=_Loader(),
        state=types.SimpleNamespace(step=0),
        train_step=lambda state, b: (state, {"loss": 0.1, "accuracy": 1.0}),
        preemption=PreemptionGuard(signals=()),
        logger=types.SimpleNamespace(write=lambda *a, **k: None),
        membership=None,   # no elastic watcher (runtime/membership.py)
        _rollback_pending=False, _last_skip_streak=0, _quarantine_seen=0)
    # train_epoch opens its span and hands over to the loop proper
    from tpuic.train.loop import Trainer
    stub._train_epoch = types.MethodType(Trainer._train_epoch, stub)
    return stub


def test_no_allgather_when_preemption_handling_off(monkeypatch):
    """ISSUE 5 satellite: with run.handle_preemption=False the loop must
    not only skip acting on the latch — it must never even CALL agree()
    (no allgather collective on the hot path)."""
    import jax
    from tpuic.runtime import preemption
    from tpuic.train.loop import Trainer

    calls = []
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(preemption, "agree",
                        lambda flag: calls.append(1) or bool(flag))
    stub = _loop_stub(handle_preemption=False, steps=33)
    Trainer.train_epoch(stub, 0)
    assert calls == []
    assert stub.last_epoch_steps == 33


def test_agree_called_only_at_boundaries_when_on(monkeypatch):
    import jax
    from tpuic.runtime import preemption
    from tpuic.train.loop import Trainer

    calls = []
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(preemption, "agree",
                        lambda flag: calls.append(1) or bool(flag))
    stub = _loop_stub(handle_preemption=True, steps=33)
    Trainer.train_epoch(stub, 0)
    assert len(calls) == 3  # steps 0, 16, 32 — every 16th boundary only
    assert stub.last_epoch_steps == 33
