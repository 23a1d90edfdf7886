"""Host spans and counters of the attachment service's flush path
(DESIGN.md §12).

One :class:`FlushTelemetry` per ``AttachService``. Its :meth:`phase`
context manager is the only boundary the flush path is timed at: it
opens a ``jax.profiler.TraceAnnotation`` named ``kfed.<phase>`` (a host
span on the profiler's clock, the clock its device planes share) and
adds the phase's ``time.perf_counter`` duration to that phase's counter,
so a span and its counter always come from the same boundary. With the
profiler off the span costs about a microsecond.

The spans, by the fixed names trace readers key on (the part of an
event name before any ``#``):

* ``kfed.flush`` — one ``AttachService`` flush, top level;
* ``kfed.bucket`` — queue snapshot, autoscale decision and grouping;
* ``kfed.prep`` — one per batch: the batch written into its shape's
  host staging arrays (pad-by-repeat), after any wait for the step
  that last read them, and the request keys' ``fold_in`` derivation;
* ``kfed.step`` — one per batch: the host-to-device transfers and the
  serve step's dispatch (carries the pad ``rung`` and the real ``rows``);
* ``kfed.fold`` — fold admission and the fold scatter's dispatch;
* ``kfed.refresh`` — a sync refresh or an async refresh's staging,
  nested in ``kfed.fold`` when the cadence fires it;
* ``kfed.deliver`` — phase 2: labels gathered to the host.

Every span carries ``flush``, the sequence number of the latest flush
begun. The counters (``stats()``) are cumulative since the service was
built: ``flushes``, ``batches``, ``rows_stepped`` (batch rows
dispatched, repeat-padding included), ``points_stepped`` (rows x pad
rung), ``refreshes`` and each phase's host seconds as self time (a
nested phase's seconds are taken out of its parent's, so ``fold_s``
excludes the refreshes it fires). Of the delivered real rows (padding
excluded), ``proj_iters`` sums the iterations Algorithm 1 step 1's
projection took, ``proj_iters_max`` is the most any row took, and
``proj_capped`` counts the rows that reached the iteration cap
(``core.local_kmeans.PROJ_MAX_ITERS``) and so may stop unconverged.
``refresh_builds`` counts the refreshes during which Algorithm 2's
program (``core.server.aggregate``) had to be traced anew: one per fold
state shape, then none. ``staging_allocs`` counts the host staging
arrays made (a ring of ``fed.stream.STAGING_DEPTH`` per batch shape, at
the shape's first batch), ``staging_reuses`` the batches prepped into a
ring made before them (every batch past each shape's first), and
``staging_waits`` those reuses that blocked on the step still reading
the slot.

They are observability only: no scaling or refresh decision reads them
(wall clock does not replay, DESIGN.md §12), no checkpoint carries them,
and a restored service starts them at zero. A phase adds no host sync:
its host time is whatever the host already spends in it.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import jax
import numpy as np

from repro.core.local_kmeans import PROJ_MAX_ITERS

SPAN_PREFIX = "kfed."
PHASES = ("flush", "bucket", "prep", "step", "fold", "refresh", "deliver")


class FlushTelemetry:
    """Cumulative flush counters plus the :meth:`phase` span boundary."""

    def __init__(self):
        self.flushes = 0
        self.batches = 0
        self.rows_stepped = 0
        self.points_stepped = 0
        self.refreshes = 0
        self.refresh_builds = 0
        self.proj_iters = 0
        self.proj_iters_max = 0
        self.proj_capped = 0
        self.staging_allocs = 0
        self.staging_reuses = 0
        self.staging_waits = 0
        self.seconds: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self._inner: List[float] = []   # nested seconds per open phase

    @contextlib.contextmanager
    def phase(self, name: str, **meta):
        """Time one phase as a ``kfed.<name>`` host span; ``meta`` rides
        the span beside the flush sequence number."""
        self._inner.append(0.0)
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name,
                                              flush=self.flushes, **meta):
                yield
        finally:
            took = time.perf_counter() - t
            self.seconds[name] += took - self._inner.pop()
            if self._inner:
                self._inner[-1] += took

    def stepped(self, rows: int, rung: int) -> None:
        """Count one dispatched batch of ``rows`` rows padded to ``rung``
        points each."""
        self.batches += 1
        self.rows_stepped += rows
        self.points_stepped += rows * rung

    def projected(self, iters: np.ndarray) -> None:
        """Count the projection iterations of one batch's real rows."""
        if iters.size:
            self.proj_iters += int(iters.sum())
            self.proj_iters_max = max(self.proj_iters_max, int(iters.max()))
            self.proj_capped += int((iters >= PROJ_MAX_ITERS).sum())

    def stats(self) -> dict:
        return {"flushes": self.flushes, "batches": self.batches,
                "rows_stepped": self.rows_stepped,
                "points_stepped": self.points_stepped,
                "refreshes": self.refreshes,
                "refresh_builds": self.refresh_builds,
                "proj_iters": self.proj_iters,
                "proj_iters_max": self.proj_iters_max,
                "proj_capped": self.proj_capped,
                "staging_allocs": self.staging_allocs,
                "staging_reuses": self.staging_reuses,
                "staging_waits": self.staging_waits,
                **{f"{p}_s": s for p, s in self.seconds.items()}}
