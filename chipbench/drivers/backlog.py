"""Closed loop over a backlog: each flush takes the next ``per_flush``
reports of the pool, submits them and flushes; the next flush starts
when the caller holds the last one's labels. A request is due when its
flush's submits begin. The window ends at the first flush that ends
``seconds`` or more after the first began, and counts all of it."""
from __future__ import annotations

import time

import jax

from chipbench.harness import flush, submit


def drive(served, traffic: dict, seconds: float, rec) -> None:
    per = int(traffic["per_flush"])
    sess, pool, feed = served.sess, served.pool, served.feed
    rec.t0 = time.perf_counter()
    while True:
        due = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            reqs = submit(sess, pool, [feed.next() for _ in range(per)],
                          [due] * per)
        flush(sess, reqs, rec, began=due, submitted=time.perf_counter())
        if rec.flushes[-1]["end"] - rec.t0 >= seconds:
            break
    rec.t1 = rec.flushes[-1]["end"]
