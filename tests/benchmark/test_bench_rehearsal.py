"""CPU rehearsals of every cell at the tiny sizes of its files, through
the command the driver runs: the last line of standard output is the one
JSON object, and every metric the cell declares is on it (those of the
device only on a TPU: their readers find nothing on a CPU and return
nothing). One file, so the runs share a worker and never race for
the generated data."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness

SPEC = harness.load_spec()
SPECS = {"BENCHMARK.json": SPEC, "benchmark/candidates.json":
         harness.load_spec("benchmark/candidates.json")}
CELLS = [(path, w["name"]) for path, spec in SPECS.items()
         for w in spec["workloads"]]
IDS = [c for _, c in CELLS]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cwd, *args, chips=1, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    cmd = [sys.executable, *SPEC["command"][1:], *args]
    if shutil.which("taskset"):
        # Two cores are enough for a rehearsal; XLA's thread pools would
        # otherwise take every core from the tests of the other workers.
        cores = sorted(os.sched_getaffinity(0))[-2:]
        cmd = ["taskset", "-c", ",".join(map(str, cores)), *cmd]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _rehearse(spec, cell, trace):
    chips = next(w["chips"] for w in SPECS[spec]["workloads"]
                 if w["name"] == cell)
    p = _run(harness.REPO, "--spec", spec, "--workload", cell, "--seed", "3",
             "--seconds", "2", "--trace", str(trace), "--tiny", chips=chips)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line), line
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == chips
    assert "rehearsal" in dev         # unmistakably not a measurement
    return harness.resolve_cell(SPECS[spec], cell), line


@pytest.mark.parametrize("spec,cell", CELLS, ids=IDS)
def test_traced_rehearsal_reports_every_per_layer_metric(spec, cell):
    r, line = _rehearse(spec, cell, trace=1)
    want = {m["name"]: m for m in r["per_layer"]}
    # the CPU backend has no device plane in its trace and no memory stats
    on_cpu = {n for n, m in want.items()
              if m["source"] != "device_trace" and m["layer"] != "Device"}
    assert on_cpu <= set(line["metrics"]) <= set(want)
    for name, m in line["metrics"].items():
        assert m["unit"] == want[name]["unit"]
        assert isinstance(m["value"], (int, float))
    assert line["metrics"][next(n for n in want if "compiles_in_window"
                                in n)]["value"] == 0


@pytest.mark.parametrize("spec,cell", [CELLS[0], CELLS[-1]],
                         ids=[IDS[0], IDS[-1]])
def test_untraced_rehearsal_reports_the_end_to_end_metrics(spec, cell):
    r, line = _rehearse(spec, cell, trace=0)
    want = {m["name"]: m["unit"] for m in r["end_to_end"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "breakdown" not in line


def test_without_a_tpu_nothing_is_measured():
    p = _run(harness.REPO, "--workload", IDS[0], "--seed", "0",
             "--seconds", "1", "--trace", "0", timeout=300)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_fewer_devices_than_the_cell_needs_is_refused():
    four = next(w["name"] for w in SPEC["workloads"] if w["chips"] == 4)
    p = _run(harness.REPO, "--workload", four, "--seed", "0", "--seconds",
             "1", "--trace", "0", "--tiny", chips=2, timeout=300)
    assert p.returncode != 0 and "needs 4 chip(s)" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_a_checkout_without_the_program_is_refused(tmp_path):
    shutil.copy(os.path.join(harness.REPO, "BENCHMARK.json"), tmp_path)
    for path in SPEC["paths"]:
        shutil.copytree(os.path.join(harness.REPO, path), tmp_path / path)
    p = _run(str(tmp_path), "--workload", IDS[0], "--seed", "0",
             "--seconds", "1", "--trace", "0", "--tiny", timeout=300)
    assert p.returncode != 0 and "not in this checkout" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
