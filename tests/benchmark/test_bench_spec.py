"""BENCHMARK.json against the contract's limits, and the harness against
its own rule: every cell resolves through lookups by name, and no harness
code names a cell, a traffic mix or a configuration."""

import ast
import glob
import json
import os
import re

import pytest

from benchmark import harness

REPO = harness.REPO
SPEC = harness.load_spec()
CANDIDATES = harness.load_spec("benchmark/candidates.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
ALL_CELLS = [(spec, w["name"]) for spec in (SPEC, CANDIDATES)
             for w in spec["workloads"]]


def test_candidates_are_not_in_the_benchmark_and_say_why():
    admitted = set(CELLS)
    for w in CANDIDATES["workloads"]:
        assert w["name"] not in admitted
        assert CANDIDATES["why_not_admitted"][w["name"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    runs = 2 + 14 * 24      # a full check with the most cells allowed
    assert (runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200) <= 43200
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])


@pytest.mark.parametrize("spec", [SPEC, CANDIDATES],
                         ids=["benchmark", "candidates"])
def test_names_units_and_metric_entries(spec):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in spec[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in spec["workloads"] + spec["configs"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
        assert NAME.match(e.get("traffic", "x")) and NAME.match(
            e.get("config", "x"))


def test_config_files_state_source_and_cuts():
    for c in SPEC["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] == []
        assert body["assumed"] and body["reference"]
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


@pytest.mark.parametrize("spec,cell", ALL_CELLS,
                         ids=[c for _, c in ALL_CELLS])
def test_cell_resolves_through_lookups_only(spec, cell):
    r = harness.resolve_cell(spec, cell)
    mode = harness.load_mode(r["traffic"]["mode"])
    assert callable(mode.run)
    assert callable(harness.load_reference(r["config"]["reference"]).forward)
    e2e = [m["name"] for m in r["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert r["per_layer"]
    for m in r["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in e2e, (cell, m["name"])
    tiny = harness.resolve_cell(spec, cell, tiny=True)
    assert tiny["config"]["image_size"] < r["config"]["image_size"]


def _harness_sources():
    files = glob.glob(os.path.join(harness.HERE, "**", "*.py"),
                      recursive=True)
    assert len(files) > 20
    return files


def test_no_cell_traffic_or_config_name_in_harness_code():
    """Adding a cell, a mix or a configuration must need no edit of code:
    so no code may know one by name. Cell and traffic names may not appear
    anywhere in a source file; a configuration's name (which is also a
    word of ordinary prose) not as a string the code could compare with."""
    forbidden = {c for _, c in ALL_CELLS} | {
        w["traffic"] for spec in (SPEC, CANDIDATES)
        for w in spec["workloads"]}
    configs = {c["name"] for c in SPEC["configs"]}
    for path in _harness_sources():
        with open(path) as f:
            text = f.read()
        for name in forbidden:
            assert name not in text, (path, name)
        for node in ast.walk(ast.parse(text)):
            if isinstance(node, ast.Constant) and isinstance(node.value,
                                                             str):
                assert node.value not in configs, (path, node.value)


def test_every_reader_file_is_a_declared_metric_or_shared():
    declared = {m["name"] for spec in (SPEC, CANDIDATES)
                for m in spec["per_layer"]}
    here = os.path.join(harness.HERE, "layer_metrics")
    files = {os.path.splitext(f)[0] for f in os.listdir(here)
             if f.endswith(".py") and not f.startswith("_")}
    assert files == declared


def test_a_reader_that_finds_nothing_returns_nothing():
    empty = harness.Observations(step_events=[], engine_stats={},
                                 trace=None, spans={})
    for m in SPEC["per_layer"] + CANDIDATES["per_layer"]:
        assert harness.load_reader(m["name"])(empty) is None, m["name"]


def test_peaks_table_has_the_v5e_and_refuses_unknown_kinds():
    from benchmark.peaks import peaks_for
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
