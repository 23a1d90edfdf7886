"""Open loop: Poisson arrivals at the traffic's ``rate_per_s`` for
``seconds``. The gaps between arrivals are one fixed set (drawn from a
fixed generator, scaled to span the window) in the seed's order, so
every seed offers the same load. The loop submits whatever has
come due and flushes it, then sleeps until the next arrival if none is
due. Each request is timed from its due time. How late the loop itself
submitted a request (past its due time, or past the end of the flush
that kept the loop busy when it came due) is kept in ``rec.late``. The
window ends when the last arrival's labels are delivered."""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.harness import flush, submit


def arrivals(traffic: dict, seconds: float, rng) -> np.ndarray:
    """Arrival offsets in seconds from the window's start."""
    rate = float(traffic["rate_per_s"])
    count = max(2, int(round(rate * seconds)))
    fixed = np.random.default_rng(1)
    gaps = fixed.exponential(1.0 / rate, count - 1)
    gaps *= seconds * (count - 1) / count / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))])


def drive(served, traffic: dict, seconds: float, rec) -> None:
    sess, pool, feed = served.sess, served.pool, served.feed
    offsets = arrivals(traffic, seconds, served.arrival_rng)
    rec.t0 = t0 = time.perf_counter()
    i, count = 0, len(offsets)
    while i < count:
        now = time.perf_counter() - t0
        if offsets[i] > now:
            time.sleep(offsets[i] - now)
            continue
        j = int(np.searchsorted(offsets, now, side="right"))
        due = [t0 + float(t) for t in offsets[i:j]]
        began = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            reqs = submit(sess, pool, [feed.next() for _ in due], due)
        submitted = time.perf_counter()
        free = rec.flushes[-1]["end"] if rec.flushes else t0
        rec.late += [submitted - max(t, free) for t in due]
        flush(sess, reqs, rec, began=began, submitted=submitted)
        i = j
    rec.t1 = rec.flushes[-1]["end"]
