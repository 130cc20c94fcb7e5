"""Open-loop request generator for the serving cells.

After ``benchmarks/bench_serve.py`` (``_request_pool`` / ``_drive``): requests
drawn from the sources in proportion to their sizes, each asking its own
source's head, sent on a seeded schedule of exponential gaps whether or not
earlier requests have finished. Unlike that script, each request is timed
from the moment it was due, not from its submit call, so a stall of the
generator or of ``submit`` charges every request behind it.

The schedule is fixed work: ``n = rate * seconds`` requests whose gaps are
drawn once from the traffic's own seed and scaled to span exactly
``seconds``; the run's seed only permutes which structure goes where and
the order of the gaps. So every seed sends the same requests at the same
rate, in another order.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from perfbench.atoms import SAMPLE_KEYS


def request_pool(sources, n: int, seed: int) -> list:
    """n (source, row) pairs, source s with probability |s| / total."""
    rng = np.random.default_rng(seed)
    sizes = np.array([s["species"].shape[0] for s in sources], float)
    picks = rng.choice(len(sources), size=n, p=sizes / sizes.sum())
    return [(int(t), int(rng.integers(sources[t]["species"].shape[0])))
            for t in picks]


def schedule(rate: float, seconds: float, traffic_seed: int,
             run_seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``rate * seconds``
    requests: exponential gaps from the traffic seed, scaled to end at
    ``seconds``, in an order drawn from the run seed."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(traffic_seed).exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(run_seed).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


class Record:
    """Per-request times on ``time.perf_counter``: due, submitted, done
    (result or exception set), and whether it failed."""

    def __init__(self, n: int):
        self.due = np.zeros(n)
        self.sent = np.zeros(n)
        self.done = np.full(n, np.nan)
        self.failed = np.zeros(n, bool)
        self.futures = [None] * n
        self._left = n
        self._lock = threading.Lock()
        self.all_done = threading.Event()

    def finish(self, i, failed: bool):
        self.done[i] = time.perf_counter()
        self.failed[i] = failed
        with self._lock:
            self._left -= 1
            if self._left == 0:
                self.all_done.set()

    def _mark(self, i):
        return lambda fut: self.finish(i, fut.exception() is not None)

    def latency_ms(self) -> np.ndarray:
        """Due-to-done latency; a failed or unfinished request is inf."""
        lat = 1e3 * (self.done - self.due)
        lat[self.failed | np.isnan(lat)] = np.inf
        return lat


def drive(submit, sources, pool, due, *, span=None) -> Record:
    """Send ``pool[i]`` at ``t0 + due[i]`` through ``submit(sample, head)``
    (which returns a future). Sleeps until a request is due and never waits
    for results. ``span(name)`` wraps the host's calls for the profiler."""
    rec = Record(len(pool))
    t0 = time.perf_counter()
    rec.due[:] = t0 + due
    for i, (t, row) in enumerate(pool):
        while True:
            dt = rec.due[i] - time.perf_counter()
            if dt <= 0:
                break
            if span is None:
                time.sleep(min(dt, 1e-3))
            else:
                with span("generator_sleep"):
                    time.sleep(min(dt, 1e-3))
        sample = {k: sources[t][k][row] for k in SAMPLE_KEYS}
        rec.sent[i] = time.perf_counter()
        try:
            if span is None:
                fut = submit(sample, t)
            else:
                with span("generator_submit"):
                    fut = submit(sample, t)
        except Exception:      # refused at admission: never answered
            rec.finish(i, True)
            continue
        rec.futures[i] = fut
        fut.add_done_callback(rec._mark(i))
    return rec
