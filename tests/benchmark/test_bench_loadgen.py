"""The open-loop generator: seeded streams reproduce, latency runs from the
due time, and the generator's own lateness is reported."""

import queue
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from benchmark import loadgen


def test_seeded_offsets_and_sizes_reproduce():
    a = loadgen.poisson_offsets(500.0, 4.0, np.random.default_rng(7))
    b = loadgen.poisson_offsets(500.0, 4.0, np.random.default_rng(7))
    c = loadgen.poisson_offsets(500.0, 4.0, np.random.default_rng(8))
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert np.all(np.diff(a) > 0) and a[-1] < 4.0
    assert len(a) == pytest.approx(2000, rel=0.1)       # rate x duration
    mix = {"1": 0.70, "2": 0.12, "4": 0.10, "8": 0.08}
    sizes = loadgen.request_sizes(20000, mix, np.random.default_rng(7))
    assert set(sizes) == {1, 2, 4, 8}
    assert sizes.mean() == pytest.approx(1.98, rel=0.03)
    assert np.array_equal(
        sizes, loadgen.request_sizes(20000, mix, np.random.default_rng(7)))
    with pytest.raises(ValueError):
        loadgen.request_sizes(10, {"1": 0.5}, np.random.default_rng(0))


class FakeTime:
    def __init__(self):
        self.now = 100.0

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += max(0.0, s)


def test_latency_runs_from_the_due_time_and_lateness_is_reported():
    """A submit that blocks for 100 ms (a full queue's backpressure) makes
    every request that fell due meanwhile late: timed from the actual
    submit they would all look instant."""
    ft = FakeTime()
    reqs = [np.zeros((1, 2, 2, 3), np.uint8)] * 12
    due = [0.010 * i for i in range(12)]
    seen = []

    def submit(images):
        seen.append(ft.now)
        if len(seen) == 3:
            ft.now += 0.100                 # the stall, inside submit
        fut = Future()
        fut.set_result((np.zeros((len(images), 5)),))
        return fut

    out = loadgen.drive(submit, reqs, due, clock=ft.clock, sleep=ft.sleep,
                        rows_of=lambda r: r[0].shape[0], settle_s=0.0)
    assert out.status == ["ok"] * 12
    assert out.latency[:2] == pytest.approx([0, 0], abs=1e-9)
    assert out.latency[2] == pytest.approx(0.100)
    # requests 3..11 fell due at 30..110 ms; the stall ended at 120 ms
    for i in range(3, 12):
        assert out.latency[i] == pytest.approx(0.120 - due[i]), i
        assert out.late[i] == pytest.approx(0.120 - due[i]), i
    assert out.late[:3] == pytest.approx([0, 0, 0], abs=1e-9)
    assert list(out.rows_back) == [1] * 12


class FifoEngine:
    """One worker, FIFO, a fixed service time; one request stalls it."""

    def __init__(self, service_s, stall_at, stall_s):
        self.q = queue.Queue()
        self.n = 0
        self.service_s, self.stall_at, self.stall_s = (service_s, stall_at,
                                                       stall_s)
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def submit(self, images):
        fut = Future()
        self.q.put((images, fut))
        return fut

    def _run(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            images, fut = item
            time.sleep(self.stall_s if self.n == self.stall_at
                       else self.service_s)
            self.n += 1
            fut.set_result((np.zeros((len(images), 3)),))

    def close(self):
        self.q.put(None)
        self.thread.join(timeout=10)
        assert not self.thread.is_alive()


def test_a_stalled_engine_shows_in_every_later_requests_latency():
    # Arrivals as fast as service: the queue never drains the stall away.
    eng = FifoEngine(service_s=0.005, stall_at=4, stall_s=0.100)
    try:
        reqs = [np.zeros((2, 2, 2, 3), np.uint8)] * 30
        due = [0.005 * i for i in range(30)]
        out = loadgen.drive(eng.submit, reqs, due, settle_s=10.0,
                            rows_of=lambda r: r[0].shape[0])
    finally:
        eng.close()
    assert out.status == ["ok"] * 30
    assert np.all(out.latency[5:] >= 0.090), out.latency
    assert np.all(out.latency[:4] < 0.090), out.latency
    assert np.all(out.late >= 0) and np.all(out.rows_back == 2)


def test_rejected_failed_and_unanswered_are_told_apart_and_infinitely_late():
    class Refused(Exception):
        pass

    never = Future()

    def submit(images):
        n = len(images)
        if n == 1:
            raise Refused()
        fut = Future()
        if n == 2:
            fut.set_exception(Refused())
        elif n == 3:
            fut.set_exception(RuntimeError("boom"))
        elif n == 4:
            return never
        else:
            fut.set_result((np.zeros((n, 3)),))
        return fut

    reqs = [np.zeros((n, 2, 2, 3), np.uint8) for n in (1, 2, 3, 4, 5)]
    out = loadgen.drive(submit, reqs, [0, 0, 0, 0, 0], rejected=(Refused,),
                        settle_s=0.05, rows_of=lambda r: r[0].shape[0])
    assert out.status == ["rejected", "rejected", "failed", "unanswered",
                          "ok"]
    assert np.all(np.isinf(out.latency[:4])) and np.isfinite(out.latency[4])
