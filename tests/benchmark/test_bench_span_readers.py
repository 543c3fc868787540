"""The five readers of the program's span ledger on a hand-made ledger:
what each adds up, that a missing span reads as nothing, that nothing is
read outside the train mode or from a program without a ledger, and that
no reader depends on the number an epoch carries."""

import sys

import pytest

from benchmark import harness

READERS = ["setup_import_s", "setup_data_s", "setup_state_init_s",
           "setup_first_step_s", "epoch_boundary_ms"]


def _obs(train=True):
    marks = {"epoch_gap_ms": [30.0], "steps_per_epoch": 4} if train else {}
    return harness.Observations(step_events=[], engine_stats={}, trace=None,
                                spans=marks)


def _drop(ledger, keep):
    """The ledger again, with only the records ``keep`` accepts."""
    records = ledger.snapshot()
    ledger.clear()
    for r in records:
        if keep(r):
            ledger.add(r)


WANT = {"setup_import_s": 10.0 + 0.5,            # the union, not 17.5
        "setup_data_s": 1.5 + 3.0,
        "setup_state_init_s": 6.0 + 0.25,
        "setup_first_step_s": 6.0,
        "epoch_boundary_ms": 20.0}               # median of 12, 20, 500


@pytest.mark.parametrize("first_epoch", [0, 3])
@pytest.mark.parametrize("metric", READERS)
def test_reader_on_a_hand_made_ledger(fill_ledger, metric, first_epoch):
    fill_ledger(first_epoch)
    assert harness.load_reader(metric)(_obs()) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_no_spans_and_returns_nothing(fill_ledger, metric):
    assert harness.load_reader(metric)(_obs()) is None
    # a serve run: spans of some Trainer in the ledger, no train mode mark
    fill_ledger()
    assert harness.load_reader(metric)(_obs(train=False)) is None


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_a_program_without_a_span_ledger(fill_ledger, metric,
                                                   monkeypatch):
    """The driver lays these readers over the parent's checkout too."""
    fill_ledger()
    monkeypatch.setitem(sys.modules, "tpuic.telemetry.spans", None)
    monkeypatch.delattr("tpuic.telemetry.spans", raising=False)
    assert harness.load_reader(metric)(_obs()) is None


def test_a_part_that_is_missing_leaves_the_sum_out(ledger, fill_ledger):
    fill_ledger()
    _drop(ledger, lambda r: r["name"] not in ("trainer.build_steps",
                                              "epoch.first_batch"))
    read = {m: harness.load_reader(m)(_obs()) for m in READERS}
    assert read["setup_state_init_s"] is None and read["setup_data_s"] is None
    assert read["epoch_boundary_ms"] is None
    assert read["setup_first_step_s"] == pytest.approx(6.0)


def test_one_boundary_after_the_warm_up_is_not_enough_to_skip_it(
        ledger, fill_ledger):
    """Two epochs: the only boundary is the warm-up's, which is left out."""
    fill_ledger()
    later = {e["id"] for e in ledger.snapshot()
             if e["name"] == "train_epoch"
             and e["attrs"]["epoch"] >= 2}
    _drop(ledger, lambda r: r["id"] not in later
          and r["parent"] not in later)
    assert harness.load_reader("epoch_boundary_ms")(_obs()) is None
    assert harness.load_reader("setup_first_step_s")(_obs()) == 6.0
