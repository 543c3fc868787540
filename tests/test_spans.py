"""The span ledger (tpuic/telemetry/spans.py): nesting, self time, bounds,
the jax-free import, the shared clock with the profiler, the spans a
Trainer leaves, and the replay into a --metrics-jsonl stream. No test
asserts the size of a duration."""

import glob
import json
import subprocess
import sys
import threading
import time

import pytest

from tpuic.telemetry import spans
from tpuic.telemetry.events import EventBus, MemorySink

EPOCH_CHILDREN = ["epoch.head", "epoch.first_batch", "epoch.first_dispatch",
                  "epoch.tail"]
TRAINER_CHILDREN = ["trainer.data", "trainer.state_init",
                    "trainer.build_steps", "trainer.checkpoint"]


@pytest.fixture
def ledger(monkeypatch):
    """A ledger of the test's own: the process-wide one holds whatever
    the worker's earlier tests left."""
    fresh = spans.Ledger()
    monkeypatch.setattr(spans, "ledger", fresh)
    return fresh


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r["name"], []).append(r)
    return out


# -- the primitive ------------------------------------------------------------
def test_nesting_gives_the_parent_per_thread(ledger):
    def other():
        with spans.span("t.outer"):
            with spans.span("t.inner"):
                pass
    with spans.span("outer") as outer:
        th = threading.Thread(target=other)
        th.start()
        th.join()
        with spans.span("inner", k=1) as inner:
            inner.attrs["late"] = 2     # attrs may grow until the exit
    by = _by_name(ledger.snapshot())
    assert by["outer"][0]["parent"] is None
    assert by["inner"][0]["parent"] == outer.id
    assert by["inner"][0]["attrs"] == {"k": 1, "late": 2}
    # the other thread's stack is its own: no parent from this thread
    assert by["t.outer"][0]["parent"] is None
    assert by["t.inner"][0]["parent"] == by["t.outer"][0]["id"]
    assert by["t.outer"][0]["thread"] != by["outer"][0]["thread"]
    # children close, and are appended, before their parents
    assert [r["name"] for r in ledger.snapshot()] == [
        "t.inner", "t.outer", "inner", "outer"]


def test_an_exception_closes_the_span_and_pops_the_stack(ledger):
    with pytest.raises(ValueError):
        with spans.span("fails"):
            raise ValueError("boom")
    with spans.span("after"):
        pass
    by = _by_name(ledger.snapshot())
    assert by["fails"][0]["t1"] >= by["fails"][0]["t0"]
    assert by["after"][0]["parent"] is None


def test_record_of_a_past_interval(ledger):
    t0 = time.perf_counter()
    orphan = spans.record("past", t0 - 2.0, t0 - 1.0, module="m")
    assert orphan["parent"] is None and orphan["attrs"] == {"module": "m"}
    assert orphan["t1"] - orphan["t0"] == pytest.approx(1.0)
    with spans.span("open") as sp:
        child = spans.record("past", t0, t0 + 0.5)
    assert child["parent"] == sp.id
    assert ledger.origin == t0 - 2.0
    assert [r["name"] for r in ledger.snapshot()] == ["past", "past", "open"]


def test_self_time_subtracts_the_union_of_children():
    def rec(i, parent, t0, t1):
        return {"id": i, "parent": parent, "name": f"s{i}", "t0": t0,
                "t1": t1, "thread": 0, "attrs": {}}
    records = [rec(1, None, 0.0, 10.0),
               rec(2, 1, 1.0, 4.0), rec(3, 1, 3.0, 6.0),    # overlap 3..4
               rec(4, 1, 8.0, 12.0),                        # clipped at 10
               rec(5, 2, 1.0, 2.0),
               rec(6, None, 20.0, 21.0)]
    self_s = spans.self_time(records)
    assert self_s[1] == pytest.approx(10.0 - (5.0 + 2.0))   # not 10 - 10
    assert self_s[2] == pytest.approx(2.0)
    assert self_s[3] == pytest.approx(3.0)
    assert self_s[6] == pytest.approx(1.0)


def test_the_ledger_is_bounded_and_keeps_the_set_up_records(ledger):
    for name in ("import", "trainer.init"):
        with spans.span(name):
            pass
    for i in range(10_000):
        spans.record("train_epoch", float(i), float(i) + 0.5, epoch=i)
    snap = ledger.snapshot()
    assert len(snap) == spans.KEEP_FIRST + spans.KEEP_RECENT
    assert [r["name"] for r in snap[:2]] == ["import", "trainer.init"]
    assert snap[-1]["attrs"] == {"epoch": 9_999}
    snap[0]["name"] = "mine"            # a snapshot is the caller's copy
    assert ledger.snapshot()[0]["name"] == "import"
    ledger.clear()
    assert ledger.snapshot() == [] and ledger.origin is None


def test_a_span_event_is_published_only_to_a_listening_bus(ledger,
                                                           monkeypatch):
    bus = EventBus()
    monkeypatch.setattr(spans, "bus", bus)
    with spans.span("unheard"):
        pass
    assert bus.published == 0
    sink = MemorySink()
    bus.subscribe(sink, kinds=("span",))
    with spans.span("outer", model="m") as outer:
        spans.record("inner", outer.t0, outer.t0 + 0.25, epoch=7)
    inner, got = [e.data for e in sink.of("span")]
    assert got["name"] == "outer" and got["model"] == "m"
    assert got["parent"] is None and got["id"] == outer.id
    assert inner["parent"] == outer.id and inner["epoch"] == 7
    assert inner["dur_ms"] == pytest.approx(250.0)
    # start_s counts from the ledger's first record ("unheard")
    assert 0.0 <= inner["start_s"] == got["start_s"]
    # replay hands a late subscriber everything, the unheard one included
    late = MemorySink()
    assert spans.replay(late) == 3
    assert [e.data["name"] for e in late.events] == ["unheard", "inner",
                                                     "outer"]
    assert {e.kind for e in late.events} == {"span"}


@pytest.mark.parametrize("module", ["tpuic.telemetry.spans",
                                    "tpuic.telemetry"])
def test_importing_the_ledger_does_not_import_jax(module):
    code = (f"import {module}, sys; "
            "from tpuic.telemetry.spans import span, ledger\n"
            "with span('x'): pass\n"
            "assert ledger.snapshot()[0]['name'] == 'x'\n"
            "bad = [m for m in ('jax', 'numpy', 'flax') "
            "if m in sys.modules]; assert not bad, bad")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_a_span_sits_on_the_profilers_host_plane_with_the_same_duration(
        ledger, tmp_path):
    """The two clocks: the TraceAnnotation a live span enters puts the
    interval on the profiler's host plane; its duration there is the
    ledger's to within a millisecond."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span("x"):
            time.sleep(0.02)
            with spans.span("x.child"):
                time.sleep(0.005)
            spans.record("x.past", 0.0, 1.0)    # ledger only: no annotation
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tpuic."):
                    found[ev.name] = (plane.name, ev.start_ns,
                                      ev.duration_ns)
    assert set(found) == {"tpuic.x", "tpuic.x.child"}
    by = _by_name(ledger.snapshot())
    for name, (plane, start_ns, dur_ns) in found.items():
        rec = by[name[len("tpuic."):]][0]
        assert plane.startswith("/host:")
        assert dur_ns * 1e-9 == pytest.approx(rec["t1"] - rec["t0"],
                                              abs=1e-3)
    # and the same nesting: the child starts and ends inside the parent
    (_, x0, xd), (_, c0, cd) = found["tpuic.x"], found["tpuic.x.child"]
    assert x0 <= c0 and c0 + cd <= x0 + xd


def test_spans_add_no_device_get_and_no_compile(ledger):
    """The discipline of tests/test_telemetry.py's on-versus-off test: a
    loop with its epoch spans makes the same device_gets and jit-cache
    entries as one without."""
    import jax
    import jax.numpy as jnp
    from tpuic.analysis import runtime as contracts

    def loop(with_spans):
        @jax.jit
        def step(s, x):
            return s + x.sum()
        with contracts.count_device_gets() as gets:
            state = jnp.zeros(())
            for epoch in range(2):
                if with_spans:
                    with spans.span("train_epoch", epoch=epoch):
                        with spans.span("epoch.head", epoch=epoch):
                            pass
                        for i in range(4):
                            t0 = time.perf_counter()
                            state = step(state, jnp.ones((4,)))
                            if i == 0:
                                spans.record("epoch.first_dispatch", t0,
                                             time.perf_counter())
                        jax.device_get(state)
                else:
                    for i in range(4):
                        state = step(state, jnp.ones((4,)))
                    jax.device_get(state)
        return gets.count, step._cache_size()
    assert loop(True) == loop(False)
    assert len(ledger.snapshot()) == 2 * 3


# -- the spans a Trainer leaves -----------------------------------------------
@pytest.fixture(scope="module")
def trained(imagefolder, tmp_path_factory):
    """One tiny Trainer on one device, two train_epoch calls (numbered 3
    and 4: nothing may depend on epoch 0), one val_epoch; its ledger and
    its --metrics-jsonl stream."""
    import jax
    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.runtime.mesh import make_mesh
    from tpuic.train.loop import Trainer
    tmp = tmp_path_factory.mktemp("spans")
    jsonl = str(tmp / "events.jsonl")
    cfg = Config(
        data=DataConfig(data_dir=imagefolder, resize_size=32, batch_size=4,
                        num_workers=2, shuffle_seed=0),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="adam", learning_rate=1e-3,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=2, ckpt_dir=str(tmp / "cp"), save_period=1,
                      resume=False, log_every_steps=2, metrics_jsonl=jsonl),
        mesh=MeshConfig())
    spans.ledger.clear()
    spans.record("import", 0.0, 1.0, module="stand-in")  # before any Trainer
    trainer = Trainer(cfg, mesh=make_mesh(MeshConfig(data=1),
                                          devices=jax.devices()[:1]))
    steps = len(trainer.train_loader)
    trainer.train_epoch(3)
    trainer.train_epoch(4)
    trainer.val_epoch(4)
    trainer.telemetry.close()
    records = spans.ledger.snapshot()
    with open(jsonl) as f:
        stream = [json.loads(ln) for ln in f if ln.strip()]
    return {"records": records, "stream": stream, "steps": steps}


def test_a_trainer_leaves_exactly_the_documented_spans(trained):
    by = _by_name(trained["records"])
    assert set(by) == {"import", "trainer.init", *TRAINER_CHILDREN,
                       "train_epoch", *EPOCH_CHILDREN, "val_epoch"}
    assert {n: len(v) for n, v in by.items() if n.startswith("trainer.")} \
        == {n: 1 for n in ("trainer.init", *TRAINER_CHILDREN)}
    init = by["trainer.init"][0]
    assert init["parent"] is None
    assert init["attrs"] == {"model": "resnet18-cifar", "chips": 1}
    assert by["trainer.data"][0]["attrs"] == {"images": 18}
    assert by["val_epoch"][0]["attrs"] == {"epoch": 4}
    for name in TRAINER_CHILDREN:
        assert by[name][0]["parent"] == init["id"], name


def test_every_child_lies_inside_its_parent_and_siblings_follow_in_order(
        trained):
    ids = {r["id"]: r for r in trained["records"]}
    for r in trained["records"]:
        assert r["t1"] >= r["t0"]
        if r["parent"] is not None:
            parent = ids[r["parent"]]
            assert parent["t0"] <= r["t0"] and r["t1"] <= parent["t1"], (
                r["name"], parent["name"])
    by = _by_name(trained["records"])
    stages = [by[n][0] for n in TRAINER_CHILDREN]
    assert all(a["t1"] <= b["t0"] for a, b in zip(stages, stages[1:]))
    self_s = spans.self_time(trained["records"])
    assert all(s >= -1e-9 for s in self_s.values())


def test_two_epochs_leave_two_of_each_epoch_span_and_nothing_per_step(
        trained):
    by = _by_name(trained["records"])
    epochs = by["train_epoch"]
    assert [e["attrs"] for e in epochs] == [
        {"epoch": 3, "steps": trained["steps"]},
        {"epoch": 4, "steps": trained["steps"]}]
    assert trained["steps"] > 1         # steps 2..n exist and left nothing
    for e in epochs:
        kids = sorted((r for r in trained["records"]
                       if r["parent"] == e["id"]), key=lambda r: r["t0"])
        assert [k["name"] for k in kids] == EPOCH_CHILDREN
        for k in kids:
            ahead = ({"ahead": k["attrs"].get("ahead")}
                     if k["name"] == "epoch.first_batch" else {})
            assert k["attrs"] == {"epoch": e["attrs"]["epoch"], **ahead}
        assert all(a["t1"] <= b["t0"] for a, b in zip(kids, kids[1:]))


def test_the_metrics_jsonl_stream_begins_with_the_replayed_set_up_spans(
        trained):
    stream = trained["stream"]
    names = [r.get("name") for r in stream if r["event"] == "span"]
    # replayed: what closed before the sink existed, in ledger order ...
    assert names[:5] == ["import", *TRAINER_CHILDREN]
    assert [r["event"] for r in stream[:5]] == ["span"] * 5
    # ... then trainer.init, which closes after the sink is attached, and
    # the epochs' spans as they close, before the first epoch's event
    assert names[5] == "trainer.init"
    assert names[6:11] == [*EPOCH_CHILDREN, "train_epoch"]
    assert names.count("train_epoch") == 2 and names[-1] == "val_epoch"
    first_step = next(i for i, r in enumerate(stream)
                      if r["event"] == "step")
    assert all(r["event"] == "span" for r in stream[:6]) and first_step > 6
    # every span once, with the fields of the schema table
    ids = [r["id"] for r in stream if r["event"] == "span"]
    assert len(ids) == len(set(ids)) == len(trained["records"])
    for r in stream:
        if r["event"] == "span":
            assert {"name", "id", "parent", "start_s", "dur_ms"} <= set(r)
