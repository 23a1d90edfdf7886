"""Small reductions shared by the metric readers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The q-th percentile (linear interpolation), None if empty."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def latencies_ms(rec):
    """Due time to labels in hand, per request due in the window. A
    request never answered counts from its due time to the window's end,
    the least it waited."""
    return [((r["done"] if r["done"] is not None else rec.t1) - r["due"])
            * 1e3 for r in rec.requests]


def idle_share(rec):
    t = rec.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
