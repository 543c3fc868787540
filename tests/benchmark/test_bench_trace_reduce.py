"""The reduction from a profiler trace to busy, idle and collective time:
the union arithmetic on hand-built events, and the shape of a real TPU
trace on an excerpt cut from the chip trace recorded under perf/vit_trace
(TPU v5 lite, 2026-07-31; every 12th op of the first three steps)."""

import os

import pytest

from benchmark import harness
from benchmark.trace_reduce import (is_collective, op_name, read_xplane,
                                    reduce_device, reduce_trace)

EXCERPT = os.path.join(harness.HERE, "fixtures",
                       "vit_b16_b64_v5e_2026-07-31.excerpt.xplane.pb")


def test_busy_is_the_union_of_three_events_not_their_sum():
    ops = [("%a = f32[] add()", 0.0, 4.0), ("%b = f32[] add()", 3.0, 3.0),
           ("%c = f32[] add()", 8.0, 1.0)]
    r = reduce_device([], ops, skip_first=0)
    assert r["window_s"] == 9.0             # extent of the ops
    assert r["busy_s"] == 7.0               # [0,6] + [8,9], not 4 + 3 + 1
    assert r["longest_gap_s"] == 2.0
    assert dict(r["ops"]) == {"a": 4.0, "b": 3.0, "c": 1.0}


def test_window_spans_whole_periods_of_the_dominant_program():
    mods = [("jit_step(1)", 10.0 * i, 6.0) for i in range(5)]
    mods += [("jit_prep(2)", 10.0 * i + 7.0, 1.0) for i in range(5)]
    ops = [("%conv = f32[] convolution()", 10.0 * i, 6.0) for i in range(5)]
    ops += [("%gather = f32[] gather()", 10.0 * i + 7.0, 1.0)
            for i in range(5)]
    r = reduce_device(mods, ops, skip_first=2)
    assert r["module"] == "jit_step(1)" and r["steps"] == 2
    assert r["window_s"] == 20.0 and r["busy_s"] == 14.0
    notes = [("bench.step", 26.0, 1.0), ("bench.train_epoch", 0.0, 50.0)]
    r = reduce_device(mods, ops, skip_first=2, annotations=notes)
    # gaps [26,27] [28,30] [36,37] [38,40]: one midpoint under the step span
    assert dict(r["idle_gaps"]) == {"bench.step": 1.0,
                                    "bench.train_epoch": 5.0}


def test_collective_time_and_its_exposed_part():
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 10.0),
           ("%all-reduce-start.1 = f32[] all-reduce-start()", 10.0, 0.1),
           ("%fusion.2 = f32[] fusion()", 10.1, 3.9),
           ("%all-reduce-done.1 = f32[] all-reduce-done()", 14.0, 2.0),
           ("%all-reduce.7 = f32[] all-reduce()", 16.0, 1.0),
           ("%fusion.3 = f32[] fusion()", 17.0, 3.0)]
    async_ops = [("%all-reduce-start.1 = f32[] all-reduce-start()", 10.0,
                  6.0)]
    r = reduce_device([], ops, async_ops, skip_first=0)
    assert r["collective_s"] == pytest.approx(7.0)          # [10, 17]
    # hidden behind fusion.2 for 3.9; start, done and the sync one are not
    assert r["collective_exposed_s"] == pytest.approx(3.1)
    assert r["busy_s"] == pytest.approx(20.0)


def test_names():
    assert op_name("%fusion.12 = bf16[64,197]{1,0} fusion(bf16[] %p)") \
        == "fusion.12"
    assert is_collective("all-reduce-start.3")
    assert is_collective("reduce-scatter.1") and not is_collective("reduce.4")
    assert reduce_device([], []) is None


def test_recorded_chip_trace_planes_lines_and_names():
    raw = read_xplane(EXCERPT)
    assert list(raw["devices"]) == [0]          # one '/device:TPU:0' plane
    dev = raw["devices"][0]
    assert len(dev["modules"]) == 3
    assert all(n.startswith("jit_train_step(") for n, _, _ in dev["modules"])
    assert len(dev["ops"]) > 1000 and len(dev["async_ops"]) > 100
    assert all(n.startswith("%") and " = " in n for n, _, _ in dev["ops"])
    # ops on the TensorCore line never overlap one another
    ev = sorted((s, s + d) for _, s, d in dev["ops"])
    assert all(a[1] <= b[0] + 1e-9 for a, b in zip(ev, ev[1:]))
    r = reduce_trace(EXCERPT, skip_first=0)
    d0 = r["devices"][0]
    assert d0["steps"] == 2 and d0["window_s"] == pytest.approx(0.1432, rel=0.01)
    assert 0 < d0["busy_s"] < d0["window_s"]
    assert d0["collective_s"] == 0              # one chip: no collectives
    assert d0["ops"][0][0].startswith("fusion.")
    assert r["busy_s"] == d0["busy_s"] and r["window_s"] == d0["window_s"]
