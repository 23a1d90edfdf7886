"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

* busy: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices used;
* per-module time: the device time of each XLA module (a jitted program,
  or one operation run eagerly), keyed by its name as the trace shows
  it, without the program id suffix (``jit_step(12)`` -> ``jit_step``);
* the device operations that took most time (an operation's time
  includes the operations nested in it, as a while loop's body), and the
  longest idle gaps with what the host was doing in each.

The traced window is the host span the harness names ``chipbench.window``;
a trace without it is read whole. Only ``jax.profiler.ProfileData`` is
needed to read the file.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 160   # an operation's name is its HLO text: keep its head


def find_xplane(logdir: str) -> str:
    """The one ``.xplane.pb`` file under a trace directory."""
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def module_key(name: str) -> str:
    """``jit_step(12)`` -> ``jit_step``."""
    return name.split("(", 1)[0].strip()


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The uncovered stretches of [lo, hi)."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(s: float, e: float, lo: float, hi: float):
    return max(s, lo), min(e, hi)


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.end_ns))
            for ev in line.events]


def read(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(pd, top: int = 10, program_files=()) -> dict:
    """Reduce a loaded trace. Returns ``busy_s`` and ``window_s`` (busy
    averaged over the device planes that ran anything), ``modules``
    ({name: device seconds}, summed over devices), ``device_ops`` and
    ``idle_gaps`` (each at most ``top`` ``[name, seconds]`` pairs), and
    ``devices`` (how many device planes ran anything). An idle gap is
    named by what the host was doing in it (``host_activity``), with the
    innermost Python frame of a file in ``program_files`` (basenames)
    first where the trace has Python frames."""
    host_spans = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW_SPAN and window is None:
                        window = (s, e)
                    host_spans.append((name, s, e))
    per_dev = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        ops = lines.get(OPS_LINE, [])
        mods = lines.get(MODULES_LINE, [])
        if ops or mods:
            per_dev.append((ops or mods, mods))
    if window is None:
        spans = [(s, e) for ops, _ in per_dev for _, s, e in ops]
        window = (min(s for s, _ in spans), max(e for _, e in spans)) \
            if spans else (0.0, 0.0)
    lo, hi = window
    busy, modules, op_time = [], defaultdict(float), defaultdict(float)
    all_gaps = []
    for ops, mods in per_dev:
        iv = [_clip(s, e, lo, hi) for _, s, e in ops]
        iv = [(s, e) for s, e in iv if e > s]
        busy.append(union_length(iv))
        all_gaps += gaps(iv, lo, hi)
        for name, s, e in ops:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                op_time[name] += (e - s) * 1e-9
        for name, s, e in mods:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                modules[module_key(name)] += (e - s) * 1e-9
    window_s = (hi - lo) * 1e-9
    busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "devices": len(per_dev),
        "modules": dict(modules),
        "device_ops": sorted(([n[:NAME_CHARS], t]
                              for n, t in op_time.items()),
                             key=lambda p: -p[1])[:top],
        "idle_gaps": [[host_activity(host_spans, s, e, program_files),
                       (e - s) * 1e-9] for s, e in longest],
    }


def frame_file(name: str) -> str:
    """``$stream.py:689 _serve_batch`` -> ``stream.py`` (Python frames
    of the profiler's Python tracer start with ``$``)."""
    return name[1:].split(":", 1)[0] if name.startswith("$") else ""


def host_activity(spans, s: float, e: float, program_files=()) -> str:
    """What the host was doing in [s, e): the innermost host span that
    covers the gap's middle, after the innermost program frame covering
    it (``frame > span``); else the span that overlaps the gap most."""
    mid = 0.5 * (s + e)
    covering = [(he - hs, name) for name, hs, he in spans
                if hs <= mid < he and name != WINDOW_SPAN]
    if covering:
        inner = min(covering)[1]
        mine = [c for c in covering if frame_file(c[1]) in program_files]
        if mine and min(mine)[1] != inner:
            return f"{min(mine)[1]} > {inner}"
        return inner
    overlap = [(min(e, he) - max(s, hs), name) for name, hs, he in spans
               if min(e, he) > max(s, hs) and name != WINDOW_SPAN]
    return max(overlap)[1] if overlap else "no host span"


def module_seconds(reduced: dict, names) -> Optional[float]:
    """Device seconds of the modules named (exact keys); None where the
    trace holds none of them, so a renamed program reads as missing."""
    found = [reduced["modules"][n] for n in names if n in reduced["modules"]]
    return sum(found) if found else None


def describe(pd, per_line: int = 12) -> str:
    """A readable summary of a trace's planes, lines and the most
    frequent event names: for looking at a trace by hand."""
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = _events(line)
            count: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
            for name, s, e in evs:
                count[name][0] += 1
                count[name][1] += (e - s) * 1e-9
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for name, (c, t) in sorted(count.items(),
                                       key=lambda kv: -kv[1][1])[:per_line]:
                out.append(f"    {c:7d} x {t:10.6f} s  {name[:120]}")
    return "\n".join(out)
