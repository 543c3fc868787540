"""The packed loader runs ahead over the epoch boundary (ISSUE 32).

A packed loader that augments keeps its producer thread after an epoch's
last batch: it makes the next epoch's first ``prefetch`` batches and parks
on the bounded queue; ``epoch(e + 1)`` takes them over. What these tests
hold: the batches are the bits a fresh loader yields, whatever was made
ahead; the plan is discarded by any other call and by an abandoned epoch;
``close()`` and an abandoned epoch leave no thread; the executors that have
nothing to hide never park. No timing is asserted: a wait has a deadline
and the assertion is on the state after it.
"""

import contextlib
import os
import random
import sys
import threading
import time

import numpy as np
import pytest
from PIL import Image

from tpuic.config import DataConfig
from tpuic.data.folder import ImageFolderDataset
from tpuic.data.pack import pack_dataset
from tpuic.data.pipeline import Loader

GLOBAL_BATCH = 4
PREFETCH = 2
STEPS = 5            # 20 train images / GLOBAL_BATCH
EXECUTORS = ["resident", "streaming"]


@pytest.fixture(scope="module")
def folds(tmp_path_factory):
    """(decode dataset, packed dataset) of one 20-image train fold."""
    root = str(tmp_path_factory.mktemp("aheaddata"))
    rng = np.random.default_rng(0)
    for cls in ("ant", "bee"):
        d = os.path.join(root, "train", cls)
        os.makedirs(d)
        for i in range(10):
            img = rng.integers(0, 256, (40, 52, 3), np.uint8)
            Image.fromarray(img).save(os.path.join(d, f"{cls}{i}.png"))
    cfg = DataConfig(data_dir=root, resize_size=32)
    ds = ImageFolderDataset(root, "train", 32, cfg)
    packed = pack_dataset(ds, str(tmp_path_factory.mktemp("aheadpack")),
                          verbose=False)
    return ds, packed


@pytest.fixture
def no_new_threads():
    """Call it to assert that every thread started since the test began has
    ended (each join has a deadline; the assertion is on ``is_alive``)."""
    before = set(threading.enumerate())

    def check():
        new = [t for t in threading.enumerate() if t not in before]
        for t in new:
            t.join(timeout=10.0)
        assert [t.name for t in new if t.is_alive()] == []
    return check


def _loader(dataset, executor="resident", **kw):
    kw.setdefault("device_cache_bytes",
                  None if executor == "resident" else 0)
    loader = Loader(dataset, GLOBAL_BATCH, seed=7, prefetch=PREFETCH,
                    num_workers=2, **kw)
    assert loader.resident == (executor == "resident" and loader.packed)
    return loader


@contextlib.contextmanager
def _spied_params(loader):
    """The packed augment parameters of every batch the loader hands to its
    device program meanwhile (the batch itself does not carry them)."""
    seen = []
    if not loader.packed:
        yield seen
        return
    name = "_resident_prep" if loader.resident else "_device_prep"
    prep = getattr(loader, name)

    def spy(*args):
        seen.append(np.asarray(args[-1]))
        return prep(*args)
    setattr(loader, name, spy)
    try:
        yield seen
    finally:
        setattr(loader, name, prep)


def _consume(loader, epoch, start_step=0):
    """One epoch to its end: (batches as host arrays, ``ahead`` it read)."""
    out = []
    with _spied_params(loader) as params:
        for batch in loader.epoch(epoch, start_step=start_step):
            out.append({"indices": np.asarray(batch.indices),
                        "ids": list(batch.image_ids),
                        "label": np.asarray(batch["label"]),
                        "mask": np.asarray(batch["mask"]),
                        "image": np.asarray(batch["image"])})
    if loader.packed:
        assert len(params) == len(out)
        for b, p in zip(out, params):
            b["params"] = p
    return out, loader.last_epoch_ahead


def _fresh(dataset, executor, epoch, start_step=0, **kw):
    """The same epoch from a loader of its own, closed again."""
    loader = _loader(dataset, executor, **kw)
    out, ahead = _consume(loader, epoch, start_step)
    loader.close()
    assert ahead == 0
    return out


def _assert_same(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.keys() == b.keys()
        assert a["ids"] == b["ids"]
        for k in a.keys() - {"ids"}:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def _wait_until(done, what):
    deadline = time.monotonic() + 20.0
    while not done():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


def _wait_parked(loader):
    """Until the parked producer has filled its queue."""
    _wait_until(lambda: loader._parked is not None
                and loader._parked.q.full(),
                "the producer did not park with a full queue")


@pytest.mark.parametrize("executor", EXECUTORS)
def test_consecutive_epochs_equal_fresh_loaders(folds, executor,
                                                no_new_threads):
    """Three epochs from one loader, the second and third taken over from
    the parked producer, are the fresh loader's bit for bit: indices,
    labels, mask, ids, augment parameters, images."""
    _, packed = folds
    loader = _loader(packed, executor)
    aheads = []
    for epoch in (3, 4, 5):         # nothing may depend on epoch 0
        got, ahead = _consume(loader, epoch)
        aheads.append(ahead)
        assert len(got) == STEPS and "params" in got[0]
        _assert_same(got, _fresh(packed, executor, epoch))
        _wait_parked(loader)
    assert aheads == [0, PREFETCH, PREFETCH]
    # An epoch is not the one before it: the comparison above has teeth.
    assert any(not np.array_equal(x["indices"], y["indices"])
               for x, y in zip(_fresh(packed, executor, 3),
                               _fresh(packed, executor, 4)))
    loader.close()
    no_new_threads()


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("call", [(7, 0), (3, 0), (4, 3), (4, STEPS)],
                         ids=["another_epoch", "same_epoch_again",
                              "start_step_3", "start_step_at_the_end"])
def test_another_call_discards_the_plan_and_starts_cold(folds, executor, call,
                                                        no_new_threads):
    """After a whole epoch 3 the plan is (4, 0). Any other call ends the
    parked producer, reads ``ahead`` 0 and serves the fresh loader's
    batches."""
    _, packed = folds
    loader = _loader(packed, executor)
    _consume(loader, 3)
    _wait_parked(loader)
    parked = loader._parked
    got, ahead = _consume(loader, *call)
    assert ahead == 0
    assert not parked._thread.is_alive()
    assert len(got) == STEPS - call[1]
    _assert_same(got, _fresh(packed, executor, *call))
    loader.close()
    no_new_threads()


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("how", ["close", "drop", "raise"])
def test_an_abandoned_epoch_leaves_no_plan_and_no_thread(folds, executor, how,
                                                         no_new_threads):
    """Two batches in, the iterator is closed, dropped, or made to raise:
    its producer ends, nothing is parked, and the next epoch starts cold
    with the right batches."""
    _, packed = folds
    loader = _loader(packed, executor)
    it = loader.epoch(3)
    next(it), next(it)
    if how == "close":
        it.close()
    elif how == "drop":
        del it
    else:
        with pytest.raises(KeyError):
            it.throw(KeyError("consumer"))
    assert loader._parked is None
    no_new_threads()
    got, ahead = _consume(loader, 4)
    assert ahead == 0
    _assert_same(got, _fresh(packed, executor, 4))
    loader.close()


@pytest.mark.parametrize("executor", EXECUTORS)
def test_close_leaves_no_thread_and_a_usable_loader(folds, executor,
                                                    no_new_threads):
    _, packed = folds
    loader = _loader(packed, executor)
    _consume(loader, 3)
    _wait_parked(loader)
    assert loader._parked._thread.is_alive()     # parked, not finished
    loader.close()
    assert loader._parked is None
    no_new_threads()
    loader.close()                               # idempotent
    got, ahead = _consume(loader, 4)             # what was (4, 0): cold now
    assert ahead == 0
    _assert_same(got, _fresh(packed, executor, 4))
    loader.close()
    no_new_threads()


@pytest.mark.parametrize("kind", ["resident_no_augment",
                                  "streaming_no_augment", "decode"])
def test_executors_with_nothing_to_hide_never_run_ahead(folds, kind,
                                                        no_new_threads):
    """A packed loader without augmentation (validation, predict) and the
    decode executor start every epoch cold: ``ahead`` stays 0, nothing is
    parked, and no thread outlives the epoch."""
    ds, packed = folds
    if kind == "decode":
        loader = _loader(ds)
        assert not loader.packed and loader.augment
    else:
        loader = _loader(packed, kind.split("_")[0], augment=False)
        assert loader.packed and not loader.augment
    for epoch in (3, 4, 5):
        got, ahead = _consume(loader, epoch)
        assert ahead == 0 and len(got) == STEPS
        assert loader._parked is None
        no_new_threads()
    _assert_same(got, _fresh(loader.dataset, kind.split("_")[0], 5,
                             augment=loader.augment))


class _Recording:
    """A packed dataset that notes, in one list shared with the test, which
    thread asked for each batch's labels (one ``label_batch`` a batch), and
    makes batch number ``gated`` (from 0, over epochs) wait for ``gate``."""

    def __init__(self, packed, log, gated=None):
        self._packed, self._log, self._gated = packed, log, gated
        self.gate = threading.Event()

    def __len__(self):
        return len(self._packed)

    def __getattr__(self, name):
        return getattr(self._packed, name)

    def label_batch(self, indices):
        if len(self._log) == self._gated:
            assert self.gate.wait(timeout=20.0)
        self._log.append(("batch", threading.get_ident()))
        return self._packed.label_batch(indices)


@pytest.mark.parametrize("executor", EXECUTORS)
def test_the_next_epochs_first_batches_are_made_before_it_is_called(
        folds, executor):
    """By the log's order: when ``epoch(4)`` is called, the one producer
    thread of epoch 3 has already made epoch 4's first ``prefetch`` batches
    (and holds one more in hand, as it does inside an epoch); the call
    starts no second thread and makes no batch twice. The producer then
    makes nothing until the caller is back for its second batch (the first
    step's dispatch is not made to wait for the interpreter lock), and goes
    on from there."""
    _, packed = folds
    log = []
    loader = _loader(_Recording(packed, log), executor)
    main = threading.get_ident()
    _consume(loader, 3)
    _wait_parked(loader)
    _wait_until(lambda: len(log) == STEPS + PREFETCH + 1,
                "the producer did not make the batch it holds in hand")
    log.append(("epoch(4) called", main))
    it = loader.epoch(4)
    next(it)
    assert loader.last_epoch_ahead == PREFETCH
    time.sleep(0.05)            # can only let a producer that is not held through
    assert log[-1] == ("epoch(4) called", main)
    assert log.index(log[-1]) == STEPS + PREFETCH + 1
    next(it)
    _wait_until(lambda: log[-1][0] == "batch",
                "the producer was not released by the second next()")
    assert len(list(it)) == STEPS - 2
    # Epoch 4's five batches, epoch 5's two and the one in hand: none twice.
    _wait_parked(loader)
    _wait_until(lambda: len(log) >= 2 * STEPS + PREFETCH + 2,
                "the producer did not go on to epoch 5")
    made = [who for what, who in log if what == "batch"]
    assert len(made) == 2 * STEPS + PREFETCH + 1
    assert len(set(made)) == 1 and main not in made
    loader.close()


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("ready", [0, 1])
def test_an_epoch_called_before_the_producer_has_parked_waits_for_it(
        folds, executor, ready, no_new_threads):
    """``epoch(4)`` arrives while the producer is still making epoch 4's
    first or second batch: it takes over what there is (``ahead`` 0 or 1),
    waits for the rest like any consumer, and serves the fresh loader's
    batches. (A producer held back here would never make the second batch
    the first yield needs.)"""
    _, packed = folds
    log = []
    ds = _Recording(packed, log, gated=STEPS + ready)
    loader = _loader(ds, executor)
    _consume(loader, 3)
    _wait_until(lambda: loader._parked.q.qsize() == ready,
                "the producer did not reach the gate")
    got = []
    consumer = threading.Thread(
        target=lambda: got.append(_consume(loader, 4)), daemon=True)
    consumer.start()
    _wait_until(lambda: loader._parked is None, "epoch(4) did not start")
    ds.gate.set()
    consumer.join(timeout=20.0)
    assert not consumer.is_alive(), "the consumer never got its batches"
    batches, ahead = got[0]
    assert ahead == ready
    _assert_same(batches, _fresh(packed, executor, 4))
    loader.close()
    no_new_threads()


@pytest.mark.parametrize("executor", EXECUTORS)
def test_stress_of_hits_misses_abandonments_and_closes(folds, executor,
                                                       no_new_threads):
    """Time-bounded stress with the interpreter switching threads every
    10 us: whatever mix of whole epochs, repeats, resumes, abandoned epochs
    and closes, every batch served is the fresh loader's (by indices and
    labels: the cheap witnesses of order and position), ``ahead`` is 0
    wherever the call is not (e + 1, 0) after a whole epoch e, and no
    thread is left."""
    _, packed = folds
    want = {}

    def expected(epoch):
        if epoch not in want:
            fresh = _loader(packed, executor, augment=False)
            want[epoch] = [(np.asarray(b.indices), np.asarray(b["label"]))
                           for b in fresh.epoch(epoch)]
        return want[epoch]

    rng = random.Random(32)
    loader = _loader(packed, executor)
    rounds = []

    def mix():
        plan = None                      # the (epoch, step) a hit needs
        deadline = time.monotonic() + 20.0
        while len(rounds) < 60 and time.monotonic() < deadline:
            epoch = plan[0] if plan and rng.random() < 0.6 \
                else rng.randrange(0, 6)
            start = rng.choice([0, 0, 0, 2])
            take = rng.choice([STEPS, STEPS, STEPS, 1, 3])
            it = loader.epoch(epoch, start_step=start)
            got = []
            for batch in it:
                got.append((np.asarray(batch.indices),
                            np.asarray(batch["label"])))
                if len(got) == take:
                    break
            if (epoch, start) != plan:
                assert loader.last_epoch_ahead == 0
            assert 0 <= loader.last_epoch_ahead <= PREFETCH
            whole = len(got) == STEPS - start and next(it, None) is None
            it.close()
            for (gi, gl), (wi, wl) in zip(got, expected(epoch)[start:]):
                np.testing.assert_array_equal(gi, wi)
                np.testing.assert_array_equal(gl, wl)
            plan = (epoch + 1, 0) if whole else None
            assert (loader._parked is not None) == whole
            if rng.random() < 0.2:
                loader.close()
                plan = None
            rounds.append((epoch, start, len(got)))
        rounds.append("done")

    # On a thread of its own, so that a consumer left waiting for a producer
    # that was stopped or held fails the test and does not hang it.
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        worker = threading.Thread(target=mix, daemon=True)
        worker.start()
        worker.join(timeout=60.0)
    finally:
        sys.setswitchinterval(old)
    assert rounds and rounds[-1] == "done", rounds[-3:]
    assert len(rounds) > 10
    loader.close()
    no_new_threads()
