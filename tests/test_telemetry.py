"""Telemetry subsystem (ISSUE 3 acceptance): event-bus ordering, step-time
breakdown, MFU math vs. bench.py's golden values, the zero-sync/zero-compile
contract with telemetry enabled, trace trigger on an injected slow step, and
the Prometheus exposition."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.runtime import faults
from tpuic.telemetry import events as tme
from tpuic.telemetry.events import EventBus, JsonlSink, MemorySink
from tpuic.telemetry.goodput import (FWD_FLOPS_PER_IMAGE, GoodputTracker,
                                     PEAK_FLOPS, analytic_flops_per_step,
                                     peak_flops)
from tpuic.telemetry.steptime import StepTimer
from tpuic.telemetry.tracing import TraceTrigger


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# -- event bus ---------------------------------------------------------------
def test_event_bus_ordering_filter_unsubscribe():
    bus = EventBus()
    everything, steps_only = MemorySink(), MemorySink()
    unsub_all = bus.subscribe(everything)
    bus.subscribe(steps_only, kinds=("step",))
    for i in range(3):
        bus.publish("step", step=i)
        bus.publish("compile", key="backend_compile_duration",
                    duration_s=0.01)
    # Synchronous delivery preserves emission order exactly.
    assert everything.kinds() == ["step", "compile"] * 3
    assert [e.data["step"] for e in everything.of("step")] == [0, 1, 2]
    # Kind filter: the filtered sink saw no compile events.
    assert steps_only.kinds() == ["step"] * 3
    # Unsubscribe is effective and idempotent.
    unsub_all()
    unsub_all()
    bus.publish("step", step=99)
    assert len(everything.of("step")) == 3
    assert steps_only.events[-1].data["step"] == 99


def test_event_bus_idle_is_free_and_sink_errors_contained():
    bus = EventBus()
    assert bus.publish("step", step=0) is None  # no subscribers: no Event
    good = MemorySink()

    def broken(ev):
        raise RuntimeError("boom")
    bus.subscribe(broken)
    bus.subscribe(good)
    bus.publish("step", step=1)  # must not raise
    assert bus.sink_errors == 1
    assert [e.data["step"] for e in good.events] == [1]


def test_event_bus_reports_a_subscribers_exception(capsys):
    """A failing subscriber is named: once on stderr, and the newest
    failure in ``last_sink_error``; delivery to the others is unchanged."""
    bus = EventBus()
    assert bus.last_sink_error is None
    good = MemorySink()

    class Broken:
        def __call__(self, ev):
            raise KeyError("no such field")

    def other_broken(ev):
        raise RuntimeError("boom")
    bus.subscribe(Broken(), kinds=("step",))
    bus.subscribe(good)
    bus.subscribe(other_broken, kinds=("epoch",))
    for i in range(3):
        bus.publish("step", step=i)     # must not raise
    assert bus.sink_errors == 3
    assert [e.data["step"] for e in good.events] == [0, 1, 2]
    err = bus.last_sink_error
    assert err["kind"] == "step" and err["error"] == \
        "KeyError: 'no such field'"
    assert err["subscriber"].endswith("Broken")
    assert __name__ in err["subscriber"]        # the qualified name
    bus.publish("epoch", epoch=0)
    assert bus.sink_errors == 4
    assert bus.last_sink_error["subscriber"].endswith("other_broken")
    assert bus.last_sink_error["error"] == "RuntimeError: boom"
    # one stderr line per failing subscriber, not one per failure
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("[telemetry] subscriber")]
    assert len(lines) == 2
    assert "Broken" in lines[0] and "KeyError" in lines[0]
    assert "other_broken" in lines[1] and "'epoch'" in lines[1]
    bus.reset()
    assert bus.last_sink_error is None and bus.sink_errors == 0


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    bus = EventBus()
    sink = JsonlSink(path)
    bus.subscribe(sink)
    bus.publish("step", step=1, total_ms=12.5, data_ms=2.0,
                dispatch_ms=0.4, device_ms=10.1)
    bus.publish("quarantine", path="img.png", count=1)
    sink.close()
    recs = [json.loads(ln) for ln in open(path)]
    assert [r["event"] for r in recs] == ["step", "quarantine"]
    assert recs[0]["total_ms"] == 12.5 and "t" in recs[0]
    # write-after-close is a no-op, not a crash (fit() can outlive sinks)
    bus.publish("step", step=2)


# -- step-time breakdown -----------------------------------------------------
def test_steptime_breakdown_synthetic():
    """Known sleeps in each phase come back in the right buckets and the
    buckets sum to the step total."""
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)
    timer = StepTimer(bus)
    timer.epoch_start()

    def loader():
        for i in range(3):
            time.sleep(0.02)   # data wait
            yield i

    for i, _ in enumerate(timer.wrap_epoch(loader())):
        timer.dispatch_start()
        time.sleep(0.005)      # dispatch
        timer.dispatch_end()
        time.sleep(0.01)       # "device" residual (drain etc.)
        timer.step_end(i + 1)

    evs = ms.of("step")
    assert [e.data["step"] for e in evs] == [1, 2, 3]
    for e in evs:
        d = e.data
        assert d["data_ms"] >= 15 and d["dispatch_ms"] >= 3
        assert d["device_ms"] >= 7
        assert (d["data_ms"] + d["dispatch_ms"] + d["device_ms"]
                == pytest.approx(d["total_ms"], abs=0.01))
    s = timer.summary()
    assert s["steps"] == 3 and 0.3 < s["data_frac"] < 0.8
    assert "p50" in s["total_ms"]


# -- goodput / MFU -----------------------------------------------------------
def test_goodput_buckets_and_accounting():
    bus = EventBus()
    gt = GoodputTracker(flops_per_step=1e9, peak_flops=1e12, global_batch=4)
    bus.subscribe(gt.on_event)
    gt.start()
    t0 = time.monotonic()
    # 4 steps of 50 ms (10 ms input each); one compile of 30 ms stalled
    # step 1; a 20 ms checkpoint commit; a skip streak of 2 at the drain.
    bus.publish("compile", key="backend_compile_duration", duration_s=0.03)
    for i in range(4):
        bus.publish("step", step=i + 1, total_ms=50.0, data_ms=10.0,
                    dispatch_ms=1.0, device_ms=39.0)
    bus.publish("checkpoint_commit", track="latest", epoch=0, step=4,
                phase="commit", duration_s=0.02)
    bus.publish("skip", step=4, streak=2, delta=2)
    bus.publish("eval", epoch=0, duration_s=0.04)
    r = gt.report()
    assert r["steps"] == 4
    assert r["input_s"] == pytest.approx(0.04, abs=1e-6)
    assert r["compile_s"] == pytest.approx(0.03, abs=1e-6)
    assert r["checkpoint_s"] == pytest.approx(0.02, abs=1e-6)
    assert r["eval_s"] == pytest.approx(0.04, abs=1e-6)
    # skip estimate: 2 steps at the 50 ms rolling mean, moved OUT of
    # productive (which was 4*40ms - 30ms compile = 130ms).
    assert r["skip_s"] == pytest.approx(0.1, abs=1e-6)
    assert r["productive_s"] == pytest.approx(0.03, abs=1e-6)
    assert r["skipped_steps_est"] == 2
    assert r["compiles"] == 1
    # Fractions are consistent with the buckets and wall time (wall is
    # real elapsed time here, so just check internal consistency).
    wall = r["wall_s"]
    assert wall >= 0 and abs(wall - (time.monotonic() - t0)) < 1.0
    named = sum(r[f"{k}_s"] for k in ("productive", "input", "compile",
                                      "checkpoint", "skip", "rollback",
                                      "eval"))
    # 0.2 s of steps (input+compile+productive+skip) + 0.02 ckpt + 0.04 eval
    assert named == pytest.approx(0.26, abs=1e-5)
    if wall > 0:
        assert r["accounted_frac"] == pytest.approx(
            min(named / wall, 1.0), abs=0.01)
    # MFU counts only non-skipped steps: (4-2) * 1e9 / (1e12 * wall);
    # pin the wall explicitly (the test runs in well under a millisecond,
    # so the report's rounded wall_s is not a stable divisor).
    assert gt.mfu(wall_s=1.0) == pytest.approx(2e9 / 1e12)


def test_mfu_math_matches_bench_golden():
    """The analytic-FLOPs scaling semantics (3x train, quadratic
    resolution) stay pinned, and bench.py must be importing THIS table
    (one source of truth).  The resnet50 basis is 8.2e9 = 2 * 4.1
    GMACs: bench.py's historical inline 3*2*4.1e9*B/2 had pasted the
    literature MAC count as FLOPs — 2x low, caught by the
    tests/test_flops_zoo.py compiler cross-check (PR 16)."""
    B = 8
    assert analytic_flops_per_step("resnet50", 224, B) == \
        pytest.approx(3 * 8.2e9 * B)
    # resolution scaling is quadratic in side length
    assert analytic_flops_per_step("resnet50", 112, B) == \
        pytest.approx(3 * 8.2e9 * B * 0.25)
    # eval = forward only
    assert analytic_flops_per_step("resnet50", 224, B, train=False) == \
        pytest.approx(8.2e9 * B)
    # longest-prefix: the cifar variant gets its own entry, not resnet18's
    assert analytic_flops_per_step("resnet18-cifar", 32, 4) == \
        pytest.approx(3 * FWD_FLOPS_PER_IMAGE["resnet18-cifar"][0] * 4)
    assert analytic_flops_per_step("no-such-model", 224, B) is None
    assert analytic_flops_per_step("resnet50", 224, 0) is None
    # peak table: cpu nominal keeps CI finite
    assert peak_flops(jax.devices()[0]) == PEAK_FLOPS["cpu"] == 1e12
    assert peak_flops(None) == 1e12
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import bench
    finally:
        sys.path.pop(0)
    assert bench._PEAK_FLOPS is PEAK_FLOPS
    assert bench.analytic_flops_per_step is analytic_flops_per_step


# -- the PR-2 discipline: no new syncs, no new compiles ----------------------
def _mini_loop(n_steps, telemetry, jsonl_path=None):
    """A miniature of train_epoch's drain pattern around a jitted step:
    returns (jitted step, device_get call count).  Transfer counting
    rides the shared tpuic.analysis.runtime checker instead of a local
    jax.device_get monkeypatch (docs/analysis.md)."""
    from tpuic.analysis import runtime as contracts

    bus = EventBus()
    closers = []
    if telemetry:
        gt = GoodputTracker(flops_per_step=1e9, peak_flops=1e12)
        bus.subscribe(gt.on_event)
        if jsonl_path:
            sink = JsonlSink(jsonl_path)
            bus.subscribe(sink)
            closers.append(sink.close)
    timer = StepTimer(bus) if telemetry else None

    @jax.jit
    def step(s, x):
        s = s + x.sum()
        return s, {"loss": s}

    try:
        with contracts.count_device_gets() as gets:
            state = jnp.zeros(())
            if timer:
                timer.epoch_start()

            def loader():
                for i in range(n_steps):
                    yield jnp.ones((4,)) * i
            it = timer.wrap_epoch(loader()) if timer else loader()
            for i, batch in enumerate(it):
                if timer:
                    timer.dispatch_start()
                state, m = step(state, batch)
                if timer:
                    timer.dispatch_end()
                # the loop's ONE deferred readback per log interval
                jax.device_get({"loss": m["loss"]})
                if timer:
                    timer.step_end(i + 1)
    finally:
        for c in closers:
            c()
    return step, gets.count


def test_compile_counter_and_host_syncs_flat_with_telemetry(tmp_path):
    """The acceptance contract: per-step host-sync count and the compile
    counter are IDENTICAL with telemetry on vs. off — telemetry is
    perf_counter arithmetic plus host-side event plumbing, nothing else."""
    from tpuic.analysis import runtime as contracts

    step_off, gets_off = _mini_loop(6, telemetry=False)
    step_on, gets_on = _mini_loop(6, telemetry=True,
                                  jsonl_path=str(tmp_path / "ev.jsonl"))
    assert gets_on == gets_off == 6
    # zero extra compiles: one executable each, no telemetry-induced
    # retrace (same assertion style as the PR-2 skip-guard contract)
    assert contracts.jit_cache_size(step_off) == 1
    assert contracts.jit_cache_size(step_on) == 1
    # and the JSONL sink recorded a breakdown for every step
    recs = [json.loads(ln) for ln in open(str(tmp_path / "ev.jsonl"))]
    steps = [r for r in recs if r["event"] == "step"]
    assert [r["step"] for r in steps] == [1, 2, 3, 4, 5, 6]
    for r in steps:
        assert {"total_ms", "data_ms", "dispatch_ms", "device_ms"} <= set(r)


def test_jax_compile_listener_publishes_compile_events():
    assert tme.install_jax_compile_listener()  # idempotent re-install ok
    ms = MemorySink()
    unsub = tme.bus.subscribe(ms, kinds=("compile",))
    try:
        @jax.jit
        def f(x):
            return x * 3 + 1
        f(jnp.ones((8,))).block_until_ready()
    finally:
        unsub()
    keys = {e.data["key"] for e in ms.of("compile")}
    assert any(k.startswith("jaxpr_trace") for k in keys)
    # every event carries a finite duration
    assert all(e.data["duration_s"] >= 0 for e in ms.of("compile"))


# -- trace trigger -----------------------------------------------------------
def test_trace_trigger_fires_on_injected_slow_step(tmp_path):
    """A slow_step fault (runtime/faults.py) regresses one step past the
    threshold x rolling-median trigger; the trigger opens a bounded
    jax.profiler window and publishes trace events."""
    faults.arm("slow_step", steps=(7,), param=0.2)
    trace_dir = str(tmp_path / "traces")
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)
    trig = TraceTrigger(trace_dir, threshold=3.0, warmup=4, trace_steps=2,
                        keep=2, cooldown=4, bus=bus)
    for i in range(12):
        dur = 0.01
        if faults.fire("slow_step", step=i):
            dur += float(faults.param("slow_step"))
        # the bus-subscription path ('step' events) is how the loop wires it
        trig.on_event(tme.Event("step", time.time(),
                                {"step": i, "total_ms": dur * 1000.0}))
    trig.finish()
    actions = [e.data["action"] for e in ms.of("trace")]
    assert "started" in actions and "stopped" in actions
    assert "error" not in actions
    started = next(e for e in ms.of("trace") if e.data["action"] == "started")
    assert started.data["reason"] == "slow_step"
    assert started.data["ratio"] >= 3.0
    assert trig.fired == 1  # cooldown: one regression != a trace per step
    dirs = [d for d in os.listdir(trace_dir) if d.startswith("trace-")]
    assert len(dirs) == 1


def test_trace_trigger_bounded_dir_and_force(tmp_path):
    """TPUIC_TRACE-style force_first fires immediately; repeated windows
    never keep more than ``keep`` traces on disk."""
    trace_dir = str(tmp_path / "traces")
    bus = EventBus()
    trig = TraceTrigger(trace_dir, threshold=0.0, trace_steps=1, keep=2,
                        cooldown=0, bus=bus, force_first=True)
    trig.observe(0.01)   # force_first: starts
    trig.observe(0.01)   # window of 1 step: stops
    assert trig.fired == 1
    # fabricate more windows via force (threshold 0 disables auto-arm)
    for _ in range(3):
        trig._force = True
        trig.observe(0.01)
        trig.observe(0.01)
    dirs = [d for d in os.listdir(trace_dir) if d.startswith("trace-")]
    assert len(dirs) <= 2  # bounded: oldest pruned


# -- prometheus exposition ---------------------------------------------------
def test_prom_serve_exposition_from_shared_meter():
    from tpuic.serve.metrics import LatencyMeter, ServeStats
    from tpuic.telemetry.prom import serve_exposition
    # the re-export shim: serve's meter IS the shared meter
    from tpuic.metrics.meters import LatencyMeter as SharedMeter
    assert LatencyMeter is SharedMeter
    s = ServeStats()
    s.record_dispatch(8, 5, [0.001, 0.002])
    s.record_dispatch(32, 30, [0.004])
    s.record_done(3, 35, [0.010, 0.020, 0.030])
    s.record_compile(8, 1.5)
    text = serve_exposition(s.snapshot())
    assert 'tpuic_serve_queue_wait_ms{quantile="p50"}' in text
    assert 'tpuic_serve_latency_ms{quantile="p99"}' in text
    assert "tpuic_serve_pad_efficiency " in text
    assert 'tpuic_serve_batches_total{bucket="8"} 1' in text
    assert "tpuic_serve_compiles_total 1" in text
    # exposition format: every non-comment line is "name[{labels}] value"
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            name, val = line.rsplit(" ", 1)
            float(val)
            assert name.startswith("tpuic_serve_")


def test_prom_train_exposition_and_http_server():
    from tpuic.telemetry.prom import PromServer, train_exposition
    gt = GoodputTracker(flops_per_step=1e9, peak_flops=1e12)
    gt.start()
    gt.on_event(tme.Event("step", time.time(),
                          {"step": 1, "total_ms": 10.0, "data_ms": 2.0}))
    text = train_exposition(gt.report())
    assert "tpuic_train_steps_total 1" in text
    assert 'tpuic_train_goodput_fraction{bucket="productive"}' in text
    srv = PromServer(0, lambda: text)  # port 0: any free port
    try:
        import urllib.request
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read().decode()
        assert "tpuic_train_steps_total 1" in body
    finally:
        srv.close()


def test_serve_main_prom_dump(tmp_path, monkeypatch):
    """``python -m tpuic.serve --prom-dump`` end to end (checkpoint load
    stubbed): the exposition file carries queue-wait, pad-efficiency,
    and latency-percentile counters sourced from the shared meter."""
    from PIL import Image

    import tpuic.serve.__main__ as serve_main
    from tpuic.serve import InferenceEngine

    size = 8
    rng = np.random.default_rng(3)
    watch = tmp_path / "incoming"
    watch.mkdir()
    for i in range(4):
        Image.fromarray(rng.integers(0, 256, (size, size, 3),
                                     np.uint8)).save(watch / f"im_{i}.png")

    def fake_build_engine(args):
        def fwd(variables, images):
            s = jnp.sum(images.astype(jnp.float32), axis=(1, 2, 3))
            probs = jax.nn.softmax(
                jnp.stack([s, -s], axis=-1), axis=-1)
            return probs, jnp.argsort(-probs, axis=-1)
        eng = InferenceEngine(forward_fn=fwd, variables={},
                              image_size=size, input_dtype=np.uint8,
                              buckets=(1, 2, 4), max_wait_ms=5.0)
        eng.warmup()
        return eng, size, 2, "stub"

    monkeypatch.setattr(serve_main, "build_engine", fake_build_engine)
    dump = tmp_path / "metrics.prom"
    rc = serve_main.main(["--watch", str(watch), "--once",
                          "--out", str(tmp_path / "resp.jsonl"),
                          "--num-classes", "2",
                          "--prom-dump", str(dump)])
    assert rc == 0
    text = dump.read_text()
    assert 'tpuic_serve_queue_wait_ms{quantile="p50"}' in text
    assert 'tpuic_serve_latency_ms{quantile="p95"}' in text
    assert "tpuic_serve_pad_efficiency " in text
    assert "tpuic_serve_images_total 4" in text


def test_latency_meter_std():
    from tpuic.metrics.meters import LatencyMeter
    m = LatencyMeter()
    assert m.std_ms == 0.0
    for v in (0.010, 0.010, 0.010):
        m.update(v)
    assert m.std_ms == pytest.approx(0.0, abs=1e-6)
    m.update(0.050)
    assert m.std_ms > 10.0  # ms-scale spread is visible


# -- end-to-end (full fit: slow, the CI telemetry smoke covers it too) -------
@pytest.mark.slow
def test_trainer_emits_step_events_and_goodput(imagefolder, tmp_path,
                                               devices8):
    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.train.loop import Trainer
    jsonl = str(tmp_path / "events.jsonl")
    cfg = Config(
        # batch 1/chip x 8 devices = 2 steps/epoch over the 18-image
        # fixture; epochs=2 gives 4 potential steps, so --steps 3 stops
        # MID-epoch (exercising the budget break + skipped val).
        data=DataConfig(data_dir=imagefolder, resize_size=32, batch_size=1,
                        num_workers=2, shuffle_seed=0),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="adam", learning_rate=1e-3,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=2, ckpt_dir=str(tmp_path / "cp"),
                      save_period=1, resume=False, log_every_steps=1,
                      max_steps=3, metrics_jsonl=jsonl),
        mesh=MeshConfig(),
    )
    trainer = Trainer(cfg)
    trainer.fit()
    recs = [json.loads(ln) for ln in open(jsonl)]
    steps = [r for r in recs if r["event"] == "step"]
    # --steps 3: exactly three step events, each with the full breakdown
    assert [r["step"] for r in steps] == [1, 2, 3]
    for r in steps:
        assert {"total_ms", "data_ms", "dispatch_ms", "device_ms"} <= set(r)
    final = [r for r in recs if r["event"] == "goodput" and r.get("final")]
    assert len(final) == 1
    named = sum(final[0][f"{k}_s"] for k in
                ("productive", "input", "compile", "checkpoint", "skip",
                 "rollback", "eval"))
    # the named buckets explain the fit() wall clock (ISSUE 3 acceptance:
    # within 2%; compile dominates a cold run and is attributed)
    assert named == pytest.approx(final[0]["wall_s"],
                                  rel=0.02, abs=0.05)
    assert final[0]["accounted_frac"] >= 0.9
    trainer.telemetry.close()
