"""The assembly of the result line, the memory arithmetic and the compile
log, on stand-ins (no device, no program run)."""

import json
import time
import types

import pytest

from benchmark import harness

SPEC = harness.load_spec()
CELL = SPEC["workloads"][0]["name"]


class FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_peak_adds_the_reservation_and_takes_the_fullest_chip():
    a = FakeDevice({"peak_bytes_in_use": 2_000, "peak_bytes_reserved": 4_000})
    b = FakeDevice({"peak_bytes_in_use": 5_000})
    assert harness.memory_peak_bytes([a, b]) == 6_000
    assert harness.memory_peak_bytes([b]) == 5_000
    assert harness.memory_peak_bytes([FakeDevice(None)]) is None


def _ctx(trace, tiny=False):
    ctx = types.SimpleNamespace(
        devices=[FakeDevice({})], trace=trace, tiny=tiny, t_start=10.0,
        t_open=52.5, said=[])
    ctx.say = ctx.said.append
    return ctx


def _result(trace=None, **spans):
    obs = harness.Observations(
        step_events=[{"total_ms": 50.0, "data_ms": 1.0, "dispatch_ms": 4.0}],
        engine_stats={}, trace=trace,
        spans={"memory_peak_bytes": 7 << 30, "compile_s": 3.5,
               "compiles_in_window": 0, "epoch_gap_ms": [60.0, 70.0],
               "flops_per_step_per_chip": 3.14e12,
               "device_kind": "TPU v5 lite", **spans})
    return harness.ModeResult(
        end_to_end={"train_images_per_s_per_chip": 2400.5}, attempted=400,
        failed=0, problems=[], obs=obs)


def test_untraced_line_has_the_end_to_end_metrics_and_setup(monkeypatch):
    monkeypatch.setattr("jax.devices", lambda: [None])
    r = harness.resolve_cell(SPEC, CELL)
    line = harness.result_line(_ctx(trace=False), r, _result())
    json.dumps(line)
    assert line["correct"] is True and line["attempted"] == 400
    assert line["metrics"] == {
        "train_images_per_s_per_chip": {"value": 2400.5,
                                        "unit": "images/s/chip"},
        "setup_s": {"value": 42.5, "unit": "s"}}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 7 << 30}
    assert "breakdown" not in line


def test_traced_line_has_the_per_layer_metrics_busy_and_breakdown(
        monkeypatch):
    monkeypatch.setattr("jax.devices", lambda: [None])
    dev = {"module": "jit_train_step(1)", "steps": 17, "window_s": 0.92,
           "busy_s": 0.90, "collective_s": 0.0, "collective_exposed_s": 0.0,
           "ops": [["fusion.1", 0.3]], "idle_gaps": [["bench.step", 0.02]]}
    trace = {"devices": {0: dev}, "busy_s": 0.90, "window_s": 0.92}
    r = harness.resolve_cell(SPEC, CELL)
    line = harness.result_line(_ctx(trace=True), r, _result(trace))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {x["name"] for x in r["per_layer"]}
    assert m["device_step_ms"] == pytest.approx(900.0 / 17)
    assert m["device_idle_share"] == pytest.approx(100 * 0.02 / 0.92)
    assert m["device_mfu"] == pytest.approx(
        100 * 3.14e12 / (0.9 / 17 * 197e12))
    assert m["data_wait_share"] == 2.0 and m["epoch_gap_ms"] == 65.0
    assert m["peak_hbm_gib"] == 7.0 and m["compile_s"] == 3.5
    assert line["device"]["busy_s"] == 0.90
    assert line["breakdown"] == {"device_ops": [["fusion.1", 0.3]],
                                 "idle_gaps": [["bench.step", 0.02]]}


def test_a_missing_or_broken_number_makes_the_run_incorrect(monkeypatch):
    monkeypatch.setattr("jax.devices", lambda: [None])
    r = harness.resolve_cell(SPEC, CELL)
    res = _result()
    res.end_to_end = {}
    assert harness.result_line(_ctx(False), r, res)["correct"] is False
    res = _result()
    res.end_to_end["train_images_per_s_per_chip"] = float("nan")
    assert harness.result_line(_ctx(False), r, res)["correct"] is False
    res = _result()
    res.problems.append("a compile inside the window")
    assert harness.result_line(_ctx(False), r, res)["correct"] is False
    # a traced run on a TPU whose trace shows no device op is refused
    assert harness.result_line(_ctx(True), r, _result())["correct"] is False


def test_compile_log_counts_backend_compiles_by_when_they_ended():
    from tpuic.telemetry.events import publish
    log = harness.CompileLog()
    try:
        publish("compile", key="jaxpr_trace_duration", duration_s=9.0)
        publish("compile", key=harness.BACKEND_COMPILE, duration_s=1.5)
        mid = time.perf_counter()
        publish("compile", key=harness.BACKEND_COMPILE, duration_s=0.25)
        end = time.perf_counter()
    finally:
        log.close()
    publish("compile", key=harness.BACKEND_COMPILE, duration_s=7.0)
    assert log.seconds_before(mid) == 1.5
    assert log.seconds_before(end) == 1.75
    assert log.count_between(mid, end) == 1 and log.count_between(0, mid) == 1
