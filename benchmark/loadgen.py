"""Open-loop load for the serve cells: a seeded request stream, Poisson
arrival offsets, and a paced driver that times every request from the
moment it was DUE.

The arithmetic of the stream and the offsets is copied from
``bench_serve.py`` (``_request_stream``, ``_poisson_run``: cumulative
exponential gaps), and the pacing loop from ``tpuic/serve/loadgen.py``
``run_stream``. Two things differ from the program's copy, on purpose:

- latency runs from the due time, not from the actual ``submit()``: when
  the generator (or a blocked ``submit``) falls behind, the wait it imposes
  on every later request is part of their latency;
- how late each submit ran is recorded, so a starved generator is not read
  as a fast server.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Optional, Sequence

import numpy as np


def poisson_offsets(rate_per_s: float, duration_s: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the window's opening) of a Poisson process
    at ``rate_per_s``, cut at ``duration_s``."""
    if rate_per_s <= 0 or duration_s <= 0:
        raise ValueError("rate and duration must be positive")
    n = int(rate_per_s * duration_s * 1.2) + 64
    t = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))
    while t[-1] < duration_s:        # the draw fell short: extend it
        more = t[-1] + np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))
        t = np.concatenate([t, more])
    return t[t < duration_s]


def request_sizes(n: int, size_mix: dict, rng: np.random.Generator
                  ) -> np.ndarray:
    """Images per request for ``n`` requests, drawn from ``size_mix``
    (``{"1": 0.70, "2": 0.12, ...}``: images -> probability)."""
    sizes = np.array([int(k) for k in size_mix], np.int64)
    probs = np.array([float(v) for v in size_mix.values()], np.float64)
    if sizes.min() < 1 or abs(probs.sum() - 1.0) > 1e-6:
        raise ValueError(f"size mix {size_mix}: sizes must be >= 1 and "
                         "probabilities must sum to 1")
    return rng.choice(sizes, size=n, p=probs)


def image_pool(rows: int, size: int, rng: np.random.Generator) -> np.ndarray:
    """A pool of seeded uint8 images; request ``i`` is a slice of it, so a
    stream of tens of thousands of requests costs no per-request drawing
    (the engine borrows the array it is given and never writes to it)."""
    return rng.integers(0, 256, (rows, size, size, 3), np.uint8)


@dataclasses.dataclass
class Outcome:
    """What happened to every request of one open-loop drive. Arrays are
    indexed by request; times are seconds."""

    due: np.ndarray             # due time, from the window's opening
    rows: np.ndarray            # images per request
    late: np.ndarray            # actual submit - due (>= 0)
    latency: np.ndarray         # result on host - due; inf if none came
    done: np.ndarray            # result on host, from the opening; inf if none
    status: list                # "ok" | "rejected" | "failed" | "unanswered"
    rows_back: np.ndarray       # rows of the answer (0 if none)


def drive(submit: Callable, requests: Sequence, due_s: Sequence[float], *,
          rejected: tuple = (), settle_s: float = 5.0,
          rows_of: Callable = lambda result: len(result),
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> Outcome:
    """Submit ``requests[i]`` at ``due_s[i]`` after the opening, never
    waiting for a result before the whole stream is offered.

    ``submit(request)`` returns a ``concurrent.futures.Future``; raising
    one of ``rejected`` (the system's typed refusal, e.g. a full queue)
    counts the request as rejected. After the last submit the drive waits
    at most ``settle_s`` for outstanding answers; what has not come by
    then is ``unanswered`` and infinitely late."""
    n = len(requests)
    due = np.asarray(due_s, np.float64)
    late = np.zeros(n)
    done_at = np.full(n, math.inf)
    rows_back = np.zeros(n, np.int64)
    status = ["unanswered"] * n
    futures: list = [None] * n

    def settled(fut, i: int) -> None:
        # Runs in the thread that resolved the future: the completion
        # stamp is not distorted by this driver's own pacing.
        now = clock()
        if fut.cancelled() or fut.exception() is not None:
            status[i] = ("rejected" if isinstance(fut.exception(), rejected)
                         and rejected else "failed")
            return
        done_at[i] = now
        rows_back[i] = rows_of(fut.result())
        status[i] = "ok"

    t0 = clock()
    for i in range(n):
        delay = t0 + due[i] - clock()
        if delay > 0:
            sleep(delay)
        late[i] = max(0.0, clock() - t0 - due[i])
        try:
            fut = submit(requests[i])
        except rejected:
            status[i] = "rejected"
            continue
        futures[i] = fut
        fut.add_done_callback(lambda f, i=i: settled(f, i))
    deadline = clock() + settle_s
    for fut in futures:
        if fut is not None and not fut.done():
            try:
                fut.exception(timeout=max(0.0, deadline - clock()))
            except Exception:   # timed out or cancelled: stays unanswered
                pass
    # A done callback can trail its future's resolution by a moment.
    for _ in range(200):
        if not any(s == "unanswered" and f is not None and f.done()
                   for s, f in zip(status, futures)):
            break
        sleep(0.001)
    return Outcome(due=due, rows=np.array([len(r) for r in requests]),
                   late=late, latency=done_at - t0 - due, done=done_at - t0,
                   status=list(status), rows_back=rows_back)
