"""The program's span ledger, made by hand, for the tests of the readers
that read it (``tpuic/telemetry/spans.py``; ``layer_metrics/setup_*.py``,
``epoch_boundary_ms.py``)."""

import pytest


@pytest.fixture
def ledger(monkeypatch):
    """A ledger of the test's own in place of the process-wide one, which
    holds whatever the worker's earlier tests left."""
    from tpuic.telemetry import spans
    fresh = spans.Ledger()
    monkeypatch.setattr(spans, "ledger", fresh)
    return fresh


@pytest.fixture
def fill_ledger(ledger):
    """``fill_ledger(first_epoch=0)`` enters a whole train run."""
    from tpuic.telemetry import spans

    def fill(first_epoch=0):
        """A run as the train mode leaves it: imports, one Trainer, a warm-up
        epoch that meets everything for the first time, three more epochs
        with boundaries of 12, 20 and (around a profiled slice) 500 ms."""
        ids = iter(range(1, 100))

        def add(name, t0, t1, parent=None, **attrs):
            rec = {"id": next(ids), "parent": parent, "name": name, "t0": t0,
                   "t1": t1, "thread": 0, "attrs": attrs}
            spans.ledger.add(rec)
            return rec["id"]
        outer = add("import", 0.0, 10.0, module="train")
        add("import", 2.0, 9.0, parent=outer, module="tpuic.train.loop")
        add("import", 11.0, 11.5, module="late")
        init = add("trainer.init", 20.0, 29.0)
        add("trainer.data", 20.0, 21.5, parent=init)
        add("trainer.state_init", 21.5, 27.5, parent=init)
        add("trainer.build_steps", 27.5, 27.75, parent=init)
        add("trainer.checkpoint", 27.75, 28.0, parent=init)
        #            head   first_batch  first_dispatch  tail
        epochs = [(0.050, 3.0, 6.0, 0.004),          # the warm-up
                  (0.002, 0.009, 0.003, 0.001),
                  (0.003, 0.008, 0.004, 0.002),      # 0.001 + 0.003 + 0.008
                  (0.004, 0.014, 0.005, 0.100),      # 0.002 + 0.004 + 0.014
                  (0.100, 0.300, 0.005, 0.001)]      # 0.100 + 0.100 + 0.300
        for i, (head, batch, dispatch, tail) in enumerate(epochs):
            n, t = first_epoch + i, 30.0 + 20.0 * i
            # children close, and are recorded, before their parent
            ep = next(ids)
            add("epoch.head", t, t + head, parent=ep, epoch=n)
            add("epoch.first_batch", t + 1, t + 1 + batch, parent=ep, epoch=n)
            add("epoch.first_dispatch", t + 5, t + 5 + dispatch, parent=ep,
                epoch=n)
            add("epoch.tail", t + 15, t + 15 + tail, parent=ep, epoch=n)
            spans.ledger.add({"id": ep, "parent": None, "name": "train_epoch",
                              "t0": t, "t1": t + 16.0, "thread": 0,
                              "attrs": {"epoch": n, "steps": 4}})
    return fill


@pytest.fixture(autouse=True)
def _the_result_line_tests_see_a_train_run(request):
    """``test_bench_harness.py`` assembles result lines from observations
    made by hand and expects every per-layer metric the cell declares: the
    ledger is one more observation, so it gets one made by hand too."""
    if request.module.__name__.rsplit(".", 1)[-1] == "test_bench_harness":
        request.getfixturevalue("fill_ledger")()
