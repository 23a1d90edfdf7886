"""Reduce the program's own host spans (``kfed.*``, written by
``repro/fed/telemetry.py``) from a profiler trace, and put the device's
idle time down to them.

The spans are ``jax.profiler.TraceAnnotation`` events on the profiler's
host plane, which shares its clock with the device planes (the device
clock lags the host's by about a millisecond on a v5e). A reader keys on
an event's name before any ``#`` (a span's keywords may ride there).

* idle: the same busy union as ``trace.py`` (``XLA Ops`` intervals per
  device inside the ``chipbench.window`` span); each idle interval is cut
  at span boundaries and each piece goes to the innermost ``kfed.*`` span
  covering it, or to ``outside`` where no span is open (between flushes:
  the caller's own work, such as its submits). Attributing by overlap
  rather than by a gap's middle, a clock skew of about a millisecond
  moves at most about that much per gap. Averaged over the devices that
  ran anything, so the pieces sum to the window's idle time;
* durations: each span's host seconds, per name, for the spans that
  start inside the window;
* step lead: the ``kfed.step`` spans paired in order with the serve
  step's device modules (``jit_step``), and how far each module started
  after the span that dispatched it (the clock check); no pairing where
  the two counts differ.

A trace without a ``kfed.flush`` span in its window (a program that
opens no spans) reduces to None. Only ``jax.profiler.ProfileData`` is
needed to read the file. From the root of a checkout, on a trace kept by
``chipbench/run.py --trace 1 --keep-trace DIR``:

    python3 -m chipbench.spans DIR

prints the split as one JSON object (``summary``) and one readable line
(``describe``).
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench import trace
from chipbench.stat import percentile
from chipbench.trace import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                             WINDOW_SPAN, gaps, module_key)

PREFIX = "kfed."
FLUSH = "kfed.flush"
STEP = "kfed.step"
OUTSIDE = "outside"
STEP_MODULE = "jit_step"

Span = Tuple[str, float, float]


def span_name(name: str) -> str:
    """``kfed.step#flush=3,rung=64#`` -> ``kfed.step``."""
    return name.split("#", 1)[0]


def innermost(spans: List[Span]) -> List[Span]:
    """The sorted, disjoint pieces of the time some span covers, each
    named by the innermost span covering it: the one that started last
    (on a tie, the one that ends first; then the one listed last)."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(spans, key=lambda sp: sp[1])
    out, active, p = [], [], 0
    for t, nxt in zip(bounds, bounds[1:]):
        while p < len(order) and order[p][1] <= t:
            active.append(order[p])
            p += 1
        active = [sp for sp in active if sp[2] > t]
        if active:
            name = max(reversed(active), key=lambda sp: (sp[1], -sp[2]))[0]
            out.append((name, t, nxt))
    return out


def attribute(idle: List[Tuple[float, float]], pieces: List[Span]
              ) -> Dict[str, float]:
    """Split disjoint idle intervals over the ``innermost`` pieces; what
    no piece covers goes to ``OUTSIDE``. The values sum to the idle
    intervals' total length."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in sorted(idle):
        while j < len(pieces) and pieces[j][2] <= s:
            j += 1
        cur, k = s, j
        while k < len(pieces) and pieces[k][1] < e:
            name, a, b = pieces[k]
            if a > cur:
                out[OUTSIDE] += a - cur
            lo, hi = max(a, cur), min(b, e)
            out[name] += hi - lo
            cur = hi
            k += 1
        if cur < e:
            out[OUTSIDE] += e - cur
    return dict(out)


def reduce(pd) -> Optional[dict]:
    """Reduce a loaded trace: ``window_s``; ``devices`` (device planes
    that ran anything); ``idle_s`` ({span name or ``outside``: device
    idle seconds}, averaged over those devices); ``durations`` ({span
    name: [host seconds, ...]}); ``step`` (``spans``, ``modules``,
    ``min_lead_s``, ``max_lead_s``, None unless the counts match: the
    clock check and the longest wait for a dispatched step). None
    when the window holds no ``kfed.flush`` span."""
    spans: List[Span] = []
    window = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW_SPAN and window is None:
                    window = (float(ev.start_ns), float(ev.end_ns))
                elif name.startswith(PREFIX):
                    spans.append((span_name(name), float(ev.start_ns),
                                  float(ev.end_ns)))
    per_dev = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: [(ev.name, float(ev.start_ns),
                              float(ev.end_ns)) for ev in line.events]
                 for line in plane.lines
                 if line.name in (OPS_LINE, MODULES_LINE)}
        ops, mods = lines.get(OPS_LINE, []), lines.get(MODULES_LINE, [])
        if ops or mods:
            per_dev.append((ops or mods, mods))
    if window is None:
        ends = [(s, e) for ops, _ in per_dev for _, s, e in ops]
        window = (min(s for s, _ in ends), max(e for _, e in ends)) \
            if ends else (0.0, 0.0)
    lo, hi = window
    inside = [sp for sp in spans if lo <= sp[1] < hi]
    if not any(name == FLUSH for name, _, _ in inside):
        return None
    pieces = innermost(spans)
    idle: Dict[str, float] = defaultdict(float)
    for ops, _ in per_dev:
        iv = [(max(s, lo), min(e, hi)) for _, s, e in ops]
        for name, t in attribute(gaps([(s, e) for s, e in iv if e > s],
                                      lo, hi), pieces).items():
            idle[name] += t * 1e-9 / len(per_dev)
    durations: Dict[str, List[float]] = defaultdict(list)
    for name, s, e in inside:
        durations[name].append((e - s) * 1e-9)
    steps = sorted(s for name, s, _ in spans if name == STEP)
    mods = sorted(s for name, s, _ in (per_dev[0][1] if per_dev else [])
                  if module_key(name) == STEP_MODULE)
    leads = [(m - s) * 1e-9 for s, m in zip(steps, mods)] \
        if len(steps) == len(mods) else []
    return {"window_s": (hi - lo) * 1e-9, "devices": len(per_dev),
            "idle_s": dict(idle),
            "durations": dict(durations),
            "step": {"spans": len(steps), "modules": len(mods),
                     "min_lead_s": min(leads) if leads else None,
                     "max_lead_s": max(leads) if leads else None}}


def describe(sp: dict, device_idle_s: float) -> str:
    """One line: each name's share of the window's idle time in % of the
    window, their sum beside the trace's device idle share, and the
    least and the longest step lead."""
    w = sp["window_s"]
    shares = {n: round(100.0 * t / w, 3) for n, t in
              sorted(sp["idle_s"].items(), key=lambda kv: -kv[1])}
    st = sp["step"]
    lead = ("none" if st["min_lead_s"] is None else
            f"{st['min_lead_s'] * 1e3:.3f} ms to "
            f"{st['max_lead_s'] * 1e3:.3f} ms")
    return (f"{shares}; sum {100.0 * sum(sp['idle_s'].values()) / w:.3f}% "
            f"against device idle {100.0 * device_idle_s / w:.3f}%; "
            f"{st['spans']} kfed.step spans, {st['modules']} "
            f"{STEP_MODULE} modules, leads {lead}")


def summary(sp: dict, tr: dict) -> dict:
    """The split in % of the window, from ``reduce`` and ``trace.reduce``
    of one trace: ``idle_share`` ({span name or ``outside``: %}), their
    ``sum`` beside the trace's ``device_idle_share``, the median
    ``refresh_ms`` of the window's ``kfed.refresh`` spans and the
    ``step`` clock check."""
    w = sp["window_s"]
    return {"idle_share": {n: 100.0 * t / w
                           for n, t in sorted(sp["idle_s"].items())},
            "sum": 100.0 * sum(sp["idle_s"].values()) / w,
            "device_idle_share":
                100.0 * (1.0 - tr["busy_s"] / tr["window_s"]),
            "refresh_ms": percentile(
                (t * 1e3 for t in sp["durations"].get("kfed.refresh", [])),
                50),
            "step": sp["step"]}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python3 -m chipbench.spans TRACE (an .xplane.pb "
              "file or the directory that holds it)", file=sys.stderr)
        return 2
    path = args[0] if args[0].endswith(".xplane.pb") \
        else trace.find_xplane(args[0])
    pd = trace.read(path)
    sp = reduce(pd)
    if sp is None:
        print(f"no {FLUSH} span in the window of {path}", file=sys.stderr)
        return 1
    tr = trace.reduce(pd)
    print(json.dumps(summary(sp, tr)))
    print(describe(sp, tr["window_s"] - tr["busy_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
