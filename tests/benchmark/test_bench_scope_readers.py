"""The six readers of device time by scope (``layer_metrics/_scopes.py``):
what each reads of the program's attribution, that a CPU rehearsal's trace
(no device plane) and a program without the registry of programs read as
nothing, and that the trace is read once a run."""

import pytest

from benchmark import harness
from benchmark.layer_metrics import _scopes

READERS = ["rotary_ms_per_step", "attention_core_ms_per_step",
           "routed_experts_ms_per_step", "optimizer_update_ms_per_step",
           "input_prep_ms_per_step", "device_idle_unlabelled_share"]

ATTRIBUTION = {
    "programs": {"step": 374.0, "input_prep": 4.4},
    "scopes": {"Classifier": 300.0, "rotary": 20.6, "attention_core": 108.2,
               "routed_experts": 101.7, "optimizer_update": 11.0},
    "idle": {"tpuic.step.drain": 0.3, "(unlabelled)": 0.1},
}
WANT = {"rotary_ms_per_step": 20.6, "attention_core_ms_per_step": 108.2,
        "routed_experts_ms_per_step": 101.7,
        "optimizer_update_ms_per_step": 11.0, "input_prep_ms_per_step": 4.4,
        "device_idle_unlabelled_share": 25.0}


def _obs(devices=True):
    dev = {"module": "jit_train_step(1)", "steps": 9, "window_s": 1.0,
           "busy_s": 0.99, "ops": [], "idle_gaps": []}
    return harness.Observations(
        step_events=[], engine_stats={},
        trace={"devices": {0: dev} if devices else {}, "busy_s": 0.99,
               "window_s": 1.0}, spans={})


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_the_attribution(metric, monkeypatch):
    monkeypatch.setattr(_scopes, "attribution", lambda obs: ATTRIBUTION)
    assert harness.load_reader(metric)(_obs()) == pytest.approx(WANT[metric])


def test_a_scope_or_program_that_did_not_run_and_a_device_never_idle(
        monkeypatch):
    bare = {"programs": {"step": 50.0}, "scopes": {"optimizer_update": 1.0},
            "idle": {}}
    monkeypatch.setattr(_scopes, "attribution", lambda obs: bare)
    read = {m: harness.load_reader(m)(_obs()) for m in READERS}
    assert read == {"rotary_ms_per_step": None,
                    "attention_core_ms_per_step": None,
                    "routed_experts_ms_per_step": None,
                    "optimizer_update_ms_per_step": 1.0,
                    "input_prep_ms_per_step": None,
                    "device_idle_unlabelled_share": 0.0}


@pytest.fixture
def cpu_rehearsal_trace(tmp_path, monkeypatch):
    """A CPU capture where the harness keeps a cell's trace, and a program
    registered as a run registers its step."""
    import jax
    import jax.numpy as jnp
    from tpuic.telemetry import profile
    cell = harness.load_spec()["workloads"][0]["name"]
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(_scopes, "_read", {})
    monkeypatch.setattr(profile, "programs", profile.Programs())
    step = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    profile.note("step", step, (x,))
    jax.profiler.start_trace(str(tmp_path / "work" / cell / "trace"))
    try:
        step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return profile


def test_readers_find_nothing_in_a_cpu_rehearsal_trace(cpu_rehearsal_trace):
    for metric in READERS:
        assert harness.load_reader(metric)(_obs()) is None, metric
    # read once: the run's trace was parsed and found to hold no device
    assert list(_scopes._read.values()) == [None]
    # and no trace: the reducer found no device either
    assert all(harness.load_reader(m)(_obs(devices=False)) is None
               for m in READERS)


def test_readers_on_a_program_without_the_registry(cpu_rehearsal_trace,
                                                   monkeypatch):
    """An older program, one without the registry, is read by these
    readers too: they find nothing there and do not raise."""
    monkeypatch.delattr(cpu_rehearsal_trace, "programs")
    for metric in READERS:
        assert harness.load_reader(metric)(_obs()) is None, metric
