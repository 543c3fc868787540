"""Device-time attribution (ISSUE 11 acceptance): op classification, the
chrome-trace parser on the committed synthetic fixture, the HLO cost model
on the REAL CPU-lowered train step (per-layer scope names included), the
roofline classification boundaries and golden HBM constants, measured-bucket
attribution, the capture analyzer's taint/finalize/error containment, the
zero-sync/zero-compile on-vs-off contract, the roofline gate firing through
regress.compare, and the committed baseline's self-consistency."""

import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.telemetry import events as tme
from tpuic.telemetry.events import EVENT_KINDS, EventBus, MemorySink
from tpuic.telemetry.goodput import (HBM_GBPS, check_flops_drift,
                                     hbm_bandwidth, ridge_intensity,
                                     roofline_intensity, roofline_verdict)
from tpuic.telemetry.profile import (OP_CLASSES, PROFILE_SPECS,
                                     CaptureAnalyzer, attribute_device_time,
                                     classify_fusion, classify_op,
                                     hlo_waterfall, layer_of,
                                     metrics_from_event, parse_trace,
                                     scope_segments, train_step_waterfall)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(_REPO, "tests", "data", "profile_trace")
VERDICTS = {"compute-bound", "hbm-bound", "overhead"}


# -- op classification --------------------------------------------------------
def test_classify_op_table():
    assert classify_op("dot.3") == "matmul"
    assert classify_op("%convolution.5") == "matmul"
    assert classify_op("custom-call.2") == "matmul"  # Pallas entry points
    assert classify_op("reduce.9") == "reduce"
    assert classify_op("reduce-window.1") == "reduce"
    assert classify_op("copy.2") == "copy"
    assert classify_op("transpose.8") == "copy"
    assert classify_op("all-reduce.1") == "collective"
    assert classify_op("get-tuple-element.4") == "overhead"
    assert classify_op("add.77") == "elementwise"
    assert classify_op("rsqrt.3") == "elementwise"
    # Profiler category hints win over the bare name (TPU trace events
    # name fusions without their called computation).
    assert classify_op("fusion.12", "convolution fusion") == "matmul"
    assert classify_op("fusion.7", "loop fusion") == "elementwise"
    assert classify_op("fusion.1", "reduction") == "reduce"


def test_classify_fusion_by_contents():
    assert classify_fusion(["add.1", "dot.2", "multiply.3"]) == "matmul"
    assert classify_fusion(["add.1", "reduce.2"]) == "reduce"
    assert classify_fusion(["copy.1", "transpose.2", "parameter.0"]) == "copy"
    assert classify_fusion(["add.1", "multiply.2"]) == "elementwise"


def test_scope_segments_unwrap_and_layer_of():
    name = ("jit(train_step)/jit(main)/transpose(jvp(Classifier))/"
            "backbone/layer1_0/conv1/conv_general_dilated")
    # jit wrappers drop whole (their payload is a function, not a
    # layer); autodiff wrappers unwrap, so fwd and bwd ops of the same
    # layer share a bucket.
    assert scope_segments(name) == ["Classifier", "backbone", "layer1_0",
                                    "conv1", "conv_general_dilated"]
    assert layer_of(name) == "Classifier/backbone/layer1_0"
    assert layer_of(name, depth=2) == "Classifier/backbone"
    # a scope that is nothing but wrappers has no layer to charge
    assert layer_of("jit(f)/jit(main)") == "(unattributed)"
    # a bare primitive with no module scope rolls up as itself
    assert layer_of("jit(f)/jit(main)/add") == "add"


# -- trace parser on the committed fixture ------------------------------------
def test_parse_trace_fixture():
    wf = parse_trace(FIXTURE)
    assert wf is not None and wf["source"] == "trace"
    c = wf["classes"]
    # conv 4.0 + dot 2.0 + convolution-fusion 1.5 (category hint)
    assert c["matmul"] == pytest.approx(7.5)
    assert c["elementwise"] == pytest.approx(1.0)   # loop fusion
    assert c["copy"] == pytest.approx(0.5)
    assert c["reduce"] == pytest.approx(0.3)
    assert c["collective"] == pytest.approx(0.2)
    # host-side (/host:CPU) timelines and zero-duration ops contribute
    # nothing — 50 ms of python/runtime events are NOT device time.
    assert wf["device_ms_total"] == pytest.approx(9.5)
    assert wf["ops"] == 7
    # per-layer rollup from the scope paths (fwd + bwd merge)
    ly = wf["layers"]
    assert ly["Classifier/backbone/layer1_0"] == pytest.approx(5.0)
    assert ly["Classifier/head/fc0"] == pytest.approx(2.0)
    assert ly["Classifier/backbone/layer2_0"] == pytest.approx(1.5)
    assert ly["Classifier/backbone/gap"] == pytest.approx(0.3)


def test_parse_trace_cpu_capture_is_none(tmp_path):
    """A capture with no device timelines (every CPU capture) must say
    so — None — instead of fabricating a waterfall from host events."""
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.trace.json").write_text(json.dumps({"traceEvents": [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "TfrtCpuExecutable::Execute"}]}))
    assert parse_trace(str(tmp_path)) is None
    assert parse_trace(str(tmp_path / "nothing-here")) is None


# -- roofline math (golden constants + boundaries) ----------------------------
def test_hbm_table_golden_values():
    """Pinned like the PEAK_FLOPS table: these are public spec-sheet
    numbers every roofline verdict is judged against."""
    assert HBM_GBPS["TPU v5e"] == 819
    assert HBM_GBPS["TPU v5"] == 2765
    assert HBM_GBPS["TPU v4"] == 1228
    assert HBM_GBPS["cpu"] == 50
    assert hbm_bandwidth(None) == 50e9
    assert hbm_bandwidth(jax.devices()[0]) == 50e9  # CPU CI


def test_unknown_device_kind_has_no_bandwidth():
    """A device the table does not know is an error, not 50 GB/s."""
    class _Dev:
        device_kind = "QPU v1"

    with pytest.raises(ValueError, match="unknown device kind 'QPU v1'"):
        hbm_bandwidth(_Dev())


def test_roofline_classification_boundaries():
    peak, bw = 100e12, 1e12   # ridge = 100 FLOPs/byte
    assert ridge_intensity(peak, bw) == 100.0
    assert roofline_intensity(200.0, 2.0) == 100.0
    assert roofline_intensity(1.0, 0.0) is None
    # exactly AT the ridge counts as compute-bound (>=)
    assert roofline_verdict(100.0, 1.0, peak, bw) == "compute-bound"
    assert roofline_verdict(99.0, 1.0, peak, bw) == "hbm-bound"
    assert roofline_verdict(101.0, 1.0, peak, bw) == "compute-bound"
    # neither axis exercised -> overhead; flops with no bytes -> compute
    assert roofline_verdict(0.0, 0.0, peak, bw) == "overhead"
    assert roofline_verdict(5.0, 0.0, peak, bw) == "compute-bound"
    assert roofline_verdict(0.0, 5.0, peak, bw) == "hbm-bound"


def test_check_flops_drift_warns_past_tolerance():
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # within 10%: silent
        d = check_flops_drift("resnet50", 224, 8,
                              1.05 * 3 * 8.2e9 * 8)
        assert d == pytest.approx(0.05, abs=0.01)
    seen = []
    d = check_flops_drift("resnet50", 224, 8, 2 * 3 * 8.2e9 * 8,
                          warn=seen.append)
    assert d == pytest.approx(0.5)
    assert len(seen) == 1 and "drifts" in seen[0]
    assert check_flops_drift("no-such-model", 224, 8, 1e9) is None
    assert check_flops_drift("resnet50", 224, 8, 0.0) is None


# -- HLO cost model on the real train step ------------------------------------
def test_hlo_waterfall_real_train_step_and_scope_names():
    """Cost-analysis extraction on the real CPU-lowered train step: the
    classes exist with verdicts, matmul carries the FLOPs, and the
    per-layer scope names (flax module paths + the jax.named_scope tags
    threaded through the model zoo and step functions) appear in the
    lowered HLO and the layer rollup."""
    wf = train_step_waterfall("resnet18-cifar", 32, 2)
    assert wf["source"] == "hlo_cost_model"
    c = wf["classes"]
    assert c["matmul"]["flops"] > 1e9          # fwd+bwd conv/dot flops
    assert c["matmul"]["ms"] > 0
    for name, cls in c.items():
        assert cls["verdict"] in VERDICTS, (name, cls)
        assert name in OP_CLASSES
    # cost_analysis total flows through (and the drift cross-check ran)
    assert wf["total_flops"] > 1e9
    assert "analytic_flops_drift" in wf
    ly = wf["layers"]
    assert any("layer1_0" in k for k in ly), ly
    assert any("stem" in k for k in ly), ly       # jax.named_scope tag
    # time concentrates where the channels are (layer4 >> layer1)
    l4 = sum(v for k, v in ly.items() if "layer4" in k)
    l1 = sum(v for k, v in ly.items() if "layer1" in k and "bn" not in k)
    assert l4 > l1
    # the modeled class times sum to the modeled total
    assert sum(cl["ms"] for cl in c.values()) == pytest.approx(
        wf["modeled_ms_total"], rel=0.01)


def test_named_scopes_in_compiled_hlo_vit():
    """The ViT structural scopes (tokenize/cls_pool/attention_core) land
    in compiled-HLO op metadata — the paths the waterfall rolls up by."""
    from tpuic.models import create_model
    m = create_model("vit-tiny", 10, dtype="float32")
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    v = m.init(jax.random.key(0), x, train=False)
    text = jax.jit(lambda v, x: m.apply(v, x, train=False)).lower(
        v, x).compile().as_text()
    for scope in ("tokenize", "cls_pool", "attention_core"):
        assert scope in text, scope


# -- measured-bucket attribution ----------------------------------------------
def _tiny_model_wf():
    return {"source": "hlo_cost_model", "modeled_ms_total": 8.0,
            "peak_flops": 1e12, "hbm_bytes_per_s": 50e9,
            "ridge_intensity": 20.0, "total_flops": 6e9,
            "classes": {
                "matmul": {"ms": 6.0, "frac": 0.75, "flops": 6e9,
                           "bytes": 1e8, "ops": 3, "intensity": 60.0,
                           "verdict": "compute-bound"},
                "copy": {"ms": 2.0, "frac": 0.25, "flops": 0.0,
                         "bytes": 1e8, "ops": 2, "intensity": 0.0,
                         "verdict": "hbm-bound"}},
            "layers": {"a/b": 6.0, "a/c": 2.0}}


def test_attribute_device_time_sums_to_measured_mean():
    out = attribute_device_time(_tiny_model_wf(), [10.0, 10.0, 40.0])
    assert out["device_ms_best"] == 10.0
    assert out["device_ms_per_step"] == 20.0
    assert out["stall_ms"] == 10.0
    # modeled 8 ms scales onto the best step (10 ms): matmul 7.5, copy
    # 2.5; the mean-over-best excess books to overhead.
    assert out["classes"]["matmul"]["ms"] == pytest.approx(7.5)
    assert out["classes"]["copy"]["ms"] == pytest.approx(2.5)
    assert out["classes"]["overhead"]["ms"] == pytest.approx(10.0)
    assert out["classes"]["overhead"]["verdict"] == "overhead"
    # THE acceptance invariant: per-class times sum to the measured mean
    assert sum(c["ms"] for c in out["classes"].values()) == pytest.approx(
        out["device_ms_per_step"], rel=0.001)
    # fractions renormalized over the measured total
    assert sum(c["frac"] for c in out["classes"].values()) == pytest.approx(
        1.0, abs=0.01)
    # layers scale with the program-time anchor
    assert out["layers"]["a/b"] == pytest.approx(7.5)
    # no measured steps: the model passes through untouched
    assert attribute_device_time(_tiny_model_wf(), [])["classes"][
        "matmul"]["ms"] == 6.0


# -- capture analyzer ---------------------------------------------------------
def _provider_tiny():
    """A minimal real compiled program as the HLO source."""
    f = jax.jit(lambda x: (x @ x).sum())
    compiled = f.lower(jnp.ones((32, 32), jnp.float32)).compile()
    from tpuic.telemetry.goodput import cost_analysis_dict
    return compiled.as_text(), cost_analysis_dict(compiled)


def test_capture_analyzer_taint_finalize_and_event():
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)
    an = CaptureAnalyzer(hlo_provider=_provider_tiny, peak=1e12,
                         hbm_bytes_per_s=50e9, bus=bus, warmup_steps=0)
    bus.subscribe(an.on_event, kinds=("step", "trace"))

    def step(n, device_ms):
        bus.publish("step", step=n, total_ms=device_ms + 1.0, data_ms=0.5,
                    dispatch_ms=0.5, device_ms=device_ms)
    step(1, 10.0)
    bus.publish("trace", action="started", path="t")
    step(2, 500.0)   # inside the window: tainted
    step(3, 500.0)
    bus.publish("trace", action="stopped", path="t")
    step(4, 300.0)   # absorbed the stop/serialize: tainted
    step(5, 12.0)
    step(6, 14.0)
    an.finalize()
    assert an.tainted_steps == 3
    evs = ms.of("profile")
    assert len(evs) == 1 and evs[0].data["final"]
    d = evs[0].data
    assert d["tainted_steps_excluded"] == 3
    assert d["steps"] == 3              # steps 1, 5, 6 only
    assert d["device_ms_per_step"] == pytest.approx(12.0, abs=0.01)
    assert sum(c["ms"] for c in d["classes"].values()) == pytest.approx(
        d["device_ms_per_step"], rel=0.01)
    for c in d["classes"].values():
        assert c["verdict"] in VERDICTS
    # "profile" is a typed event kind
    assert "profile" in EVENT_KINDS


def test_capture_analyzer_error_contained():
    """A broken HLO provider publishes an error field — it must never
    raise into the capture/finalize path (tracing.py discipline)."""
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)

    def broken():
        raise RuntimeError("no HLO for you")
    an = CaptureAnalyzer(hlo_provider=broken, bus=bus)
    bus.subscribe(an.on_event, kinds=("step",))
    bus.publish("step", step=1, device_ms=5.0)
    an.finalize()          # must not raise
    an.on_capture("/nonexistent/trace/dir")  # must not raise
    evs = ms.of("profile")
    assert len(evs) == 2
    assert all("no HLO for you" in e.data["error"] for e in evs)
    assert an.last is None


def test_trace_trigger_on_capture_hook_and_analyze_error(tmp_path):
    """The tracing.py satellite: a closed window invokes on_capture with
    the capture path; a hook failure publishes analyze_error and does
    NOT disable the trigger (capture failure semantics unchanged)."""
    from tpuic.telemetry.tracing import TraceTrigger
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)
    seen = []

    def hook(path):
        seen.append(path)
        raise RuntimeError("analyzer exploded")
    trig = TraceTrigger(str(tmp_path / "tr"), threshold=0.0, trace_steps=1,
                        cooldown=0, bus=bus, force_first=True,
                        on_capture=hook)
    trig.observe(0.01)   # force_first: window opens
    trig.observe(0.01)   # window of 1 step closes -> hook fires
    assert len(seen) == 1 and seen[0].startswith(str(tmp_path / "tr"))
    actions = [e.data["action"] for e in ms.of("trace")]
    assert actions.count("analyze_error") == 1
    assert "stopped" in actions
    assert not trig._disabled    # analysis failure never stands down
    trig._force = True
    trig.observe(0.01)
    trig.observe(0.01)
    assert len(seen) == 2        # still capturing AND still analyzing


# -- the PR-2 discipline: no new syncs, no new compiles -----------------------
def test_analyzer_zero_syncs_zero_compiles_on_vs_off():
    """The on-vs-off equality check every telemetry module carries: the
    analyzer's step intake adds no device_gets and no compiles."""
    from tpuic.analysis import runtime as contracts

    def loop(with_analyzer):
        bus = EventBus()
        an = None
        if with_analyzer:
            an = CaptureAnalyzer(bus=bus)
            bus.subscribe(an.on_event, kinds=("step", "trace"))

        @jax.jit
        def step(s, x):
            s = s + x.sum()
            return s, {"loss": s}
        with contracts.count_device_gets() as gets:
            state = jnp.zeros(())
            for i in range(6):
                state, m = step(state, jnp.ones((4,)) * i)
                jax.device_get({"loss": m["loss"]})
                bus.publish("step", step=i + 1, total_ms=5.0, data_ms=1.0,
                            dispatch_ms=0.1, device_ms=3.9)
        return step, gets.count

    step_off, gets_off = loop(False)
    step_on, gets_on = loop(True)
    assert gets_on == gets_off == 6
    assert contracts.jit_cache_size(step_off) == 1
    assert contracts.jit_cache_size(step_on) == 1


# -- the roofline gate --------------------------------------------------------
def test_roofline_gate_fires_on_class_shift():
    """PROFILE_SPECS through regress.compare (the shared tolerance
    machinery): a clean fresh passes, a stall-shifted distribution
    regresses naming frac_overhead."""
    from tpuic.telemetry.regress import compare
    baseline = {"schema": 1, "calibration_s": 0.01, "metrics": {
        "profile.frac_matmul": {"value": 0.55, "noise": 0.05},
        "profile.frac_copy": {"value": 0.26, "noise": 0.05},
        "profile.frac_overhead": {"value": 0.13, "noise": 0.1},
        "profile.device_ms_per_step": {"value": 9.0, "noise": 0.1}}}
    clean = {"profile.frac_matmul": 0.53, "profile.frac_copy": 0.27,
             "profile.frac_overhead": 0.16,
             "profile.device_ms_per_step": 9.8}
    rep = compare(baseline, clean, 0.01, specs=PROFILE_SPECS)
    assert not rep["regressed"], rep
    shifted = {"profile.frac_matmul": 0.03, "profile.frac_copy": 0.01,
               "profile.frac_overhead": 0.95,
               "profile.device_ms_per_step": 200.0}
    rep = compare(baseline, shifted, 0.01, specs=PROFILE_SPECS)
    assert rep["regressed"]
    assert "profile.frac_overhead" in rep["regressed_metrics"]
    assert "profile.device_ms_per_step" in rep["regressed_metrics"]


def test_metrics_from_event():
    ev = {"classes": {"matmul": {"frac": 0.5}, "copy": {"frac": 0.2},
                      "overhead": {"frac": 0.3}},
          "device_ms_per_step": 12.5}
    m = metrics_from_event(ev)
    assert m == {"profile.frac_matmul": 0.5, "profile.frac_copy": 0.2,
                 "profile.frac_overhead": 0.3,
                 "profile.device_ms_per_step": 12.5}
    # absent classes read as 0 (a run with no stall must still gate)
    m = metrics_from_event({"classes": {"matmul": {"frac": 1.0}}})
    assert m["profile.frac_overhead"] == 0.0


def test_committed_roofline_baseline_selfconsistent():
    """The committed artifact IS the acceptance claim: per-op-class
    times sum to within 5% of the recorded device bucket and every
    class carries a roofline verdict."""
    path = os.path.join(_REPO, "perf", "roofline_baseline.json")
    with open(path) as f:
        b = json.load(f)
    for name in PROFILE_SPECS:
        assert name in b["metrics"], name
    wf = b["waterfall"]
    assert wf["final"]
    total = sum(c["ms"] for c in wf["classes"].values())
    assert total == pytest.approx(wf["device_ms_per_step"], rel=0.05)
    for name, c in wf["classes"].items():
        assert c["verdict"] in VERDICTS, (name, c)
    assert wf["classes"]["matmul"]["verdict"] == "compute-bound"


# -- prom exposition ----------------------------------------------------------
def test_prom_profile_rows_on_both_expositions():
    from tpuic.telemetry.goodput import GoodputTracker
    from tpuic.telemetry.prom import (profile_rows, render,
                                      serve_exposition, train_exposition)
    wf = attribute_device_time(_tiny_model_wf(), [10.0, 12.0])
    text = render(profile_rows(wf))
    assert 'device_time_ms{op_class="matmul"}' in text
    assert 'device_time_frac{op_class="overhead"}' in text
    assert 'roofline_verdict{op_class="matmul"} 1' in text
    assert 'roofline_verdict{op_class="copy"} 0' in text
    assert "device_ms_per_step" in text
    gt = GoodputTracker(flops_per_step=1e9, peak_flops=1e12)
    gt.start()
    t = train_exposition(gt.report(), profile=wf)
    assert 'tpuic_train_device_time_ms{op_class="matmul"}' in t
    assert train_exposition(gt.report())  # None profile renders nothing
    assert "device_time_ms" not in train_exposition(gt.report())
    from tpuic.serve.metrics import ServeStats
    s = ServeStats()
    s.record_cost(8, 1e9, 1e7)
    text = serve_exposition(s.snapshot(), profile=wf)
    assert 'tpuic_serve_device_time_ms{op_class="matmul"}' in text
    assert 'tpuic_serve_executable_flops{bucket="8"} 1e+09' in text
    assert 'tpuic_serve_executable_intensity{bucket="8"} 100' in text


# -- serve engine cost capture ------------------------------------------------
def test_serve_engine_cost_analysis_and_waterfall():
    """The AOT bucket executables expose cost_analysis where the runtime
    provides it: recorded per bucket at compile, rendered as roofline
    context, and the engine can produce a device-time waterfall scaled
    to the span ledger's measured device phase."""
    from tpuic.serve import InferenceEngine
    size = 8

    def fwd(variables, images):
        x = images.astype(jnp.float32).reshape(images.shape[0], -1)
        w = jnp.ones((x.shape[1], 4), jnp.float32)
        return jax.nn.softmax(x @ w, axis=-1)

    eng = InferenceEngine(forward_fn=fwd, variables={}, image_size=size,
                          input_dtype=np.uint8, buckets=(1, 4),
                          max_wait_ms=1.0)
    try:
        eng.warmup()
        cost = eng.stats.snapshot()["executable_cost"]
        assert set(cost) == {"1", "4"}
        assert cost["4"]["flops"] > 0 and cost["4"]["bytes"] > 0
        assert cost["4"]["intensity"] is not None
        # before any traffic: the model-only waterfall
        wf = eng.profile_waterfall()
        assert wf is not None and wf["bucket"] == 4
        assert set(wf["classes"]) <= set(OP_CLASSES)
        # after traffic the span ledger's device phase anchors it
        rng = np.random.default_rng(0)
        for _ in range(3):
            eng.predict(rng.integers(0, 256, (2, size, size, 3), np.uint8))
        wf = eng.profile_waterfall()
        assert wf["source"].endswith("+measured")
        assert sum(c["ms"] for c in wf["classes"].values()) == \
            pytest.approx(wf["device_ms_per_step"], rel=0.01)
    finally:
        eng.close()


# -- end-to-end through the Trainer (slow; CI profile smoke also covers) ------
@pytest.mark.slow
def test_trainer_trace_analyze_end_to_end(imagefolder, tmp_path,
                                          monkeypatch):
    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.train.loop import Trainer
    monkeypatch.setenv("TPUIC_TRACE", str(tmp_path / "traces"))
    jsonl = str(tmp_path / "events.jsonl")
    cfg = Config(
        data=DataConfig(data_dir=imagefolder, resize_size=32, batch_size=2,
                        num_workers=2, shuffle_seed=0),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="adam", learning_rate=1e-3,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=3, ckpt_dir=str(tmp_path / "cp"),
                      save_period=1, resume=False, log_every_steps=1,
                      max_steps=10, metrics_jsonl=jsonl,
                      trace_analyze=True),
        mesh=MeshConfig(),
    )
    trainer = Trainer(cfg)
    trainer.fit()
    trainer.telemetry.flush()
    recs = [json.loads(ln) for ln in open(jsonl)]
    finals = [r for r in recs if r["event"] == "profile" and r.get("final")
              and not r.get("error")]
    assert finals, [r for r in recs if r["event"] == "profile"]
    d = finals[-1]
    assert sum(c["ms"] for c in d["classes"].values()) == pytest.approx(
        d["device_ms_per_step"], rel=0.05)
    for c in d["classes"].values():
        assert c["verdict"] in VERDICTS
    assert any("layer" in k for k in d["layers"])
    trainer.telemetry.close()
    tme.bus.reset()
