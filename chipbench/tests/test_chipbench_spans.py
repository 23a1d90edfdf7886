"""The program's spans and the idle attribution (``spans.py``): synthetic
intervals against a brute-force count, a CPU trace of a real flush, the
parent's case (no spans), a trace kept by a traced ``run_cell`` and the
command line on a recorded chip trace.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import spans, trace  # noqa: E402

SMALL_TRACE = ROOT / "chipbench" / "testdata" / "v5e_small.xplane.pb"
NAMES = {"kfed.flush", "kfed.bucket", "kfed.prep", "kfed.step",
         "kfed.fold", "kfed.refresh", "kfed.deliver"}


def split(spans_, idle):
    return spans.attribute(idle, spans.innermost(spans_))


NESTED = [("kfed.flush", 0.0, 100.0), ("kfed.fold", 20.0, 60.0),
          ("kfed.refresh", 30.0, 50.0)]


def test_innermost_span_wins():
    assert split(NESTED, [(10.0, 40.0)]) == {
        "kfed.flush": 10.0, "kfed.fold": 10.0, "kfed.refresh": 10.0}
    assert split(NESTED, [(35.0, 45.0)]) == {"kfed.refresh": 10.0}


def test_nested_self_time_and_outside():
    got = split(NESTED, [(-10.0, 0.0), (0.0, 100.0), (100.0, 120.0)])
    assert got == {spans.OUTSIDE: 30.0, "kfed.flush": 60.0,
                   "kfed.fold": 20.0, "kfed.refresh": 20.0}
    # a span that ends where its sibling starts, and idle between flushes
    two = [("kfed.flush", 0.0, 10.0), ("kfed.prep", 0.0, 4.0),
           ("kfed.step", 4.0, 6.0), ("kfed.flush", 15.0, 20.0)]
    assert split(two, [(2.0, 17.0)]) == {
        "kfed.prep": 2.0, "kfed.step": 2.0, "kfed.flush": 6.0,
        spans.OUTSIDE: 5.0}
    assert split([], [(1.0, 3.0)]) == {spans.OUTSIDE: 2.0}


def _nested_spans(rng, lo, hi, depth, out):
    t = lo
    while t < hi - 2 and depth < 4:
        s = int(rng.integers(t, hi - 1))
        e = int(rng.integers(s + 1, min(hi, s + 200) + 1))
        out.append((f"kfed.d{depth}", float(s), float(e)))
        _nested_spans(rng, s, e, depth + 1, out)
        t = e + int(rng.integers(0, 50))
    return out


@pytest.mark.parametrize("seed", range(6))
def test_sum_identity_against_a_unit_grid(seed):
    """Random nested spans and device ops on an integer grid: the split
    matches counting each unit by the innermost span over it, and sums
    to the idle time of the window."""
    rng = np.random.default_rng(seed)
    lo, hi = 0, 2000
    sp = _nested_spans(rng, lo, hi, 0, [])
    ops = [(float(s), float(s + rng.integers(1, 30)))
           for s in rng.integers(lo - 20, hi, 120)]
    idle = trace.gaps([(max(s, lo), min(e, hi)) for s, e in ops
                       if min(e, hi) > max(s, lo)], lo, hi)
    got = split(sp, idle)
    busy = np.zeros(hi - lo, bool)
    for s, e in ops:
        busy[max(int(s), lo) - lo:max(min(int(e), hi) - lo, 0)] = True
    owner = np.full(hi - lo, spans.OUTSIDE, object)
    for name, s, e in sorted(sp, key=lambda x: (x[1], -x[2])):
        owner[int(s):int(e)] = name      # later start = inner
    want = {}
    for name in set(owner[~busy]):
        want[name] = float(np.sum((owner == name) & ~busy))
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(float(np.sum(~busy)))


def test_a_trace_without_spans_reduces_to_none():
    """The parent program opens no ``kfed.*`` span: nothing to read."""
    assert spans.reduce(trace.read(str(SMALL_TRACE))) is None
    assert spans.main([str(SMALL_TRACE)]) == 1
    assert spans.span_name("kfed.step#flush=3,rung=64#") == "kfed.step"


@pytest.fixture(scope="module")
def served_trace():
    """Three requests of 10 points and two of 100 through one flush of a
    k=8, k'=2, d=16 session (pads 16, 128; batch 4; refresh every 4),
    under the profiler on the CPU."""
    from repro.data.gaussian import late_device_stream, structured_devices
    from repro.fed.api import FederationPlan, Session
    fm = structured_devices(jax.random.PRNGKey(0), k=8, d=16, k_prime=2,
                            m0=4, n_per_comp_dev=20, sep=60.0)
    rr = Session(FederationPlan(k=8, k_prime=2, d=16)).run(
        jax.random.PRNGKey(1), fm.data).detail
    sess = Session.from_round(FederationPlan(
        k=8, k_prime=2, d=16, capacity=64, batch_size=4,
        bucket_sizes=(16, 128), refresh_every=4), rr)
    reqs = late_device_stream(fm.means, 2, 3, 1, n_range=(10, 11)) + \
        late_device_stream(fm.means, 2, 2, 2, n_range=(100, 101))
    sess.serve([r[0] for r in reqs], [r[2] for r in reqs])  # compile
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                sess.serve([r[0] for r in reqs], [r[2] for r in reqs])
        pd = trace.read(trace.find_xplane(d))
        events = [(spans.span_name(ev.name), ev.start_ns, ev.end_ns)
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith(spans.PREFIX)]
        return spans.reduce(pd), events, sess.stats()["flush"]


def test_cpu_trace_holds_every_span(served_trace):
    reduced, _, counters = served_trace
    assert set(reduced["durations"]) == NAMES
    assert reduced["devices"] == 0 and reduced["idle_s"] == {}
    assert len(reduced["durations"]["kfed.flush"]) == 1
    assert len(reduced["durations"]["kfed.step"]) == 2
    assert reduced["step"]["spans"] == 2
    assert counters["batches"] == 4       # the compile pass and this one


def test_cpu_trace_nests_refresh_in_fold(served_trace):
    _, events, _ = served_trace
    by = {}
    for name, s, e in events:
        by.setdefault(name, []).append((s, e))
    (fs, fe), = by["kfed.flush"]
    assert all(fs <= s and e <= fe for n, s, e in events
               if n != "kfed.flush")
    (rs, re_), = by["kfed.refresh"]
    assert any(s <= rs and re_ <= e for s, e in by["kfed.fold"])
    assert len(by["kfed.fold"]) == len(by["kfed.prep"]) == 2


def test_a_kept_trace_of_a_run_holds_the_program_spans(tmp_path, capsys):
    """A traced run on the CPU at a small size, its trace kept: the
    result line is the benchmark's own, and the kept file reduces to
    every span, one ``kfed.flush`` per flush of the window."""
    import test_chipbench_correct as tc
    from chipbench import harness
    from chipbench.run import run_cell
    cell = harness.Cell("femnist-backlog", tc.SMALL, tc.BACKLOG,
                        harness.load_json(ROOT / "chipbench" / "limits"
                                          / "femnist-backlog.json"),
                        tc.BENCH)
    res = run_cell(cell, tc.SEED, 1.0, True, jax.devices()[:1], tc.PEAKS,
                   t_start=time.perf_counter(), keep_trace=str(tmp_path))
    assert res["correct"]
    assert set(res["metrics"]) <= {m["name"] for m in tc.BENCH["per_layer"]}
    sp = spans.reduce(trace.read(trace.find_xplane(str(tmp_path))))
    assert set(sp["durations"]) >= NAMES - {"kfed.refresh"}
    assert len(sp["durations"]["kfed.flush"]) >= 1
    capsys.readouterr()
    assert spans.main([str(tmp_path)]) == 0
    line = capsys.readouterr().out.splitlines()[0]
    assert json.loads(line)["step"]["spans"] == sp["step"]["spans"]


# A trace recorded on one TPU v5e chip by ``record_spans.py``
# (``testdata/v5e_spans.xplane.pb``). Worked out by hand from its events
# (ns):
#
# * window [47,981,560, 80,583,220): 32,601,660; device busy 75,501 in
#   four modules (``jit_step`` [56,895,069, 56,913,036), the sin
#   [57,825,079, 57,853,036), ``jit_step`` [66,573,362, 66,591,113) and
#   [75,283,571, 75,295,421)), so idle 32,526,159;
# * spans: flush 1 [53,430,330, 70,227,290) holding prep [53,433,390,
#   57,758,740), step [57,830,730, 58,818,900), fold [58,824,600,
#   68,450,680) with refresh [59,071,390, 68,449,360), deliver
#   [68,454,020, 70,226,360); flush 2 [74,081,240, 77,577,070) holding
#   prep [74,087,370, 76,266,670), step [76,277,580, 76,545,470),
#   deliver [76,549,250, 77,576,610);
# * idle by innermost span: outside 5,448,770 + 3,853,950 + 3,006,150;
#   prep 3,461,682 + 845,704 + 1,196,204 + 971,250 + 5 (inside the first
#   step module, between its ops); step 965,866 + 267,890; fold 246,790
#   + 1,320; refresh 7,501,976 + 1,858,248 + 3; deliver 1,772,340 +
#   1,027,360; flush, between its children, 100,651 in all;
# * the first ``jit_step`` module ran, on the device clock, before the
#   ``kfed.step`` span that waited for it opened: the device's clock reads
#   0.99-1.86 ms behind the host's here (flush 2's step was dispatched
#   after 76,277,580 and its module read 75,283,571; the refresh's ran
#   to completion before 68,449,360 and its module ended at 66,591,113).
#   The refresh runs ``step`` too, so 3 modules meet 2 step spans and the
#   reducer pairs none.
SPANS_TRACE = ROOT / "chipbench" / "testdata" / "v5e_spans.xplane.pb"
IDLE_NS = {spans.OUTSIDE: 12_308_870, "kfed.flush": 100_651,
           "kfed.prep": 6_474_845, "kfed.step": 1_233_756,
           "kfed.fold": 248_110, "kfed.refresh": 9_360_227,
           "kfed.deliver": 2_799_700}


@pytest.fixture(scope="module")
def recorded():
    pd = trace.read(str(SPANS_TRACE))
    return spans.reduce(pd), trace.reduce(pd)


def test_recorded_idle_by_span(recorded):
    sp, tr = recorded
    assert sp["devices"] == tr["devices"] == 1
    assert sp["window_s"] == pytest.approx(32_601_660e-9, abs=1e-12)
    assert sp["idle_s"] == pytest.approx(
        {k: v * 1e-9 for k, v in IDLE_NS.items()}, abs=1e-12)
    assert sum(sp["idle_s"].values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"], abs=1e-12)
    assert tr["busy_s"] == pytest.approx(75_501e-9, abs=1e-12)


def test_recorded_durations_and_step_pairing(recorded):
    sp, _ = recorded
    d = sp["durations"]
    assert {k: len(v) for k, v in d.items()} == {
        "kfed.flush": 2, "kfed.prep": 2, "kfed.step": 2, "kfed.fold": 1,
        "kfed.refresh": 1, "kfed.deliver": 2}
    assert d["kfed.refresh"] == pytest.approx([9_377_970e-9], abs=1e-12)
    assert sp["step"] == {"spans": 2, "modules": 3, "min_lead_s": None,
                          "max_lead_s": None}


def test_recorded_summary_and_command_line(recorded, capsys):
    """The command's JSON is the hand-worked split in % of the window;
    the shares sum to the trace's device idle share."""
    sp, tr = recorded
    got = spans.summary(sp, tr)
    w = 32_601_660
    assert got["idle_share"] == pytest.approx(
        {k: 100.0 * v / w for k, v in IDLE_NS.items()}, abs=1e-9)
    assert got["sum"] == pytest.approx(got["device_idle_share"], abs=1e-9)
    assert got["device_idle_share"] == pytest.approx(
        100.0 * (w - 75_501) / w, abs=1e-9)
    assert got["refresh_ms"] == pytest.approx(9.37797, abs=1e-9)
    assert spans.main([str(SPANS_TRACE)]) == 0
    out = capsys.readouterr().out.splitlines()
    printed = json.loads(out[0])
    assert printed["idle_share"] == pytest.approx(got["idle_share"])
    assert printed["step"] == got["step"]
    assert "sum 99.768% against device idle 99.768%" in out[1]
    assert spans.main([]) == 2


def _plane(name, lines):
    from types import SimpleNamespace as NS
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=float(s), end_ns=float(e))
                            for n, s, e in evs]) for ln, evs in lines])


def test_reduce_pairs_steps_with_their_modules_in_order():
    """Two dispatches, two ``jit_step`` modules: each lead is the module's
    start less its span's; idle inside the window splits by span."""
    from types import SimpleNamespace as NS
    host = _plane("/host:CPU", [("python", [
        (trace.WINDOW_SPAN, 0, 100), ("kfed.flush#flush=1#", 10, 90),
        ("kfed.step#flush=1,rung=64,rows=3#", 20, 25),
        ("kfed.step#flush=1,rung=256,rows=64#", 40, 45)])])
    dev = _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_step(7)", 30, 38), ("jit_step(7)", 47, 80)]),
        ("XLA Ops", [("fusion", 30, 38), ("fusion", 47, 80)])])
    got = spans.reduce(NS(planes=[host, dev]))
    assert got["step"]["spans"] == got["step"]["modules"] == 2
    assert got["step"]["min_lead_s"] == pytest.approx(7e-9)
    assert got["step"]["max_lead_s"] == pytest.approx(10e-9)
    assert got["idle_s"] == pytest.approx(
        {spans.OUTSIDE: 20e-9, "kfed.flush": 29e-9, "kfed.step": 10e-9})
    assert got["durations"]["kfed.step"] == pytest.approx([5e-9, 5e-9])
