"""Set-up and measured window of one cell.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a
configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``), with the limits of its correctness check
(``limits/<cell>.json``). The harness finds every piece by name, so a
new cell, configuration, mix or metric is new files, not an edit.

Set-up, from the seed: the mixture's means and the round's population on
the device; the one-shot round (``Session.run`` as one jitted program);
a serving session seeded from it (``Session.from_round``); the pool of
late-device reports; a warm-up through the same session of every pad
shape the pool uses and of one refresh. The window then drives the
traffic's driver loop (``drivers/<driver>.py``) through
``Session.submit`` and ``Session.flush_versioned``.
"""
from __future__ import annotations

import importlib.util
import json
import time
from pathlib import Path
from typing import List, NamedTuple, Optional

import jax
import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WINDOW_SPAN = "chipbench.window"


class UnknownWorkload(LookupError):
    pass


class Cell(NamedTuple):
    name: str
    config: dict
    traffic: dict
    limits: dict
    bench: dict


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise UnknownWorkload(f"unknown workload {name!r}; "
                              f"BENCHMARK.json has: {names}")
    wl = found[0]
    return Cell(name, load_json(here / "configs" / f"{wl['config']}.json"),
                load_json(here / "traffic" / f"{wl['traffic']}.json"),
                load_json(here / "limits" / f"{name}.json"), bench)


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports. A
    metric without ``workloads`` is reported wherever the end-to-end
    metric it moves (or, end to end, every cell) is."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE):
    return load_module(here / "metrics" / f"{name}.py")


def driver(name: str, here: Path = HERE):
    return load_module(here / "drivers" / f"{name}.py")


# ------------------------------------------------------------------ record --

class Record:
    """What the window saw, on the host clock (``time.perf_counter``).

    ``requests``: one dict per request due in the window: ``rid``,
    ``item`` (pool index), ``n``, ``due``, ``flush`` (start of the flush
    that served it), ``done`` (labels in the caller's hands; None if
    never), ``version`` and ``labels``. ``flushes``: one dict per flush:
    ``began`` / ``submitted`` (its submits), ``start`` / ``end`` (its
    ``flush_versioned``) and ``requests``. ``late``: how
    late an open loop submitted each request past the time it could.
    ``trace``: the reduced device trace of a traced run (``trace.py``).
    ``compile_s``: host seconds spent building programs in the window.
    """

    def __init__(self):
        self.requests: List[dict] = []
        self.flushes: List[dict] = []
        self.t0 = self.t1 = None
        self.setup_s = None
        self.reused = 0
        self.late: List[float] = []
        self.trace: Optional[dict] = None
        self.compile_s: Optional[float] = None
        self.config: Optional[dict] = None
        self.peaks: Optional[dict] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def delivered(self) -> List[dict]:
        return [r for r in self.requests if r["done"] is not None]


class Feed:
    """The pool's items in order (the pool is already in the seed's
    order), cycled; a request id is new on every submit, so a reused item
    is a new request with the same points. ``cycles`` counts the passes
    started past the first."""

    def __init__(self, pool):
        self.pool = pool
        self.order = np.arange(len(pool))
        self.pos = 0
        self.cycles = 0

    def next(self) -> int:
        if self.pos == len(self.order):
            self.pos = 0
            self.cycles += 1
        i = int(self.order[self.pos])
        self.pos += 1
        return i


def submit(sess, pool, items, due) -> List[dict]:
    """Submit pool items; returns their request records (not yet
    appended to ``rec``)."""
    out = []
    for i, t in zip(items, due):
        x, kv = pool.item(i)
        out.append({"rid": sess.submit(x, kv), "item": i, "n": x.shape[0],
                    "due": t, "flush": None, "done": None,
                    "version": None, "labels": None})
    return out


def flush(sess, reqs: List[dict], rec: Record, began: float,
          submitted: float) -> None:
    """One ``flush_versioned`` serving ``reqs``, whose submits ran from
    ``began`` to ``submitted``; stamps each request and records the
    flush."""
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.flush"):
        out = sess.flush_versioned()
    end = time.perf_counter()
    for r in reqs:
        got = out.pop(r["rid"], None)
        if got is not None:
            r["labels"], r["version"] = got
            r["flush"], r["done"] = start, end
    rec.flushes.append({"began": began, "submitted": submitted,
                        "start": start, "end": end, "requests": len(reqs)})
    rec.requests.extend(reqs)


# ------------------------------------------------------------------- set-up --

def make_plan(config: dict, **override):
    from repro.fed.api import FederationPlan
    kw = dict(config["plan"])
    kw["bucket_sizes"] = tuple(kw["bucket_sizes"])
    kw.update(override)
    return FederationPlan(**kw)


def rung(n: int, ladder) -> int:
    for b in ladder:
        if n <= b:
            return b
    raise ValueError(f"a report of {n} points is past the top pad "
                     f"{ladder[-1]}")


class Served(NamedTuple):
    sess: object
    pool: object
    means: np.ndarray
    feed: Feed
    arrival_rng: np.random.Generator


def build(cell: Cell, seed: int, plan_override: Optional[dict] = None,
          log=print) -> Served:
    """Everything before the window, from ``seed``."""
    from repro.fed.api import Session

    from chipbench import population as pop_mod
    cfg, pop = cell.config, cell.config["population"]
    p = cfg["plan"]
    words = pop_mod.seed_words(seed, 8)
    key = lambda i: jax.random.PRNGKey(int(words[i]))   # noqa: E731
    plan = make_plan(cfg, **(plan_override or {}))
    means = pop_mod.mixture_means(key(0), k=p["k"], d=p["d"],
                                  sep=float(pop["sep"]))
    data = pop_mod.round_population(
        key(1), means, k=p["k"], k_prime=p["k_prime"], m0=pop["m0"],
        n_per_comp=pop["n_per_comp"], sigma=float(pop["sigma"]))

    def round_fn(k, x):
        return Session(plan).run(k, x).detail

    t = time.perf_counter()
    detail = jax.block_until_ready(jax.jit(round_fn)(key(2), data))
    log(f"setup: round Z={data.shape[0]} n={data.shape[1]} "
        f"d={data.shape[2]} in {time.perf_counter() - t:.3f} s")
    del data
    sess = Session.from_round(plan, detail, seed=int(words[3]))
    rng = np.random.default_rng(int(words[4]))
    t = time.perf_counter()
    pool = pop_mod.make_pool(cfg, cell.traffic, means, key(5), rng)
    log(f"setup: pool of {len(pool)} reports, {pool.points.shape[0]} "
        f"points, in {time.perf_counter() - t:.3f} s")
    feed = Feed(pool)
    warm_up(sess, pool, plan)
    return Served(sess, pool, np.asarray(means, np.float64), feed,
                  np.random.default_rng(int(words[6])))


def warm_up(sess, pool, plan) -> None:
    """Serve one full batch of every pad rung the pool uses, then full
    batches of its most common rung until a refresh has run: every
    program the window calls is then compiled (or loaded)."""
    ladder = tuple(plan.bucket_sizes)
    by_rung = {}
    for i in range(len(pool)):
        by_rung.setdefault(rung(int(pool.size[i]), ladder), []).append(i)
    B = plan.batch_size

    def batch(items):
        for j in range(B):
            sess.submit(*pool.item(items[j % len(items)]))
        sess.flush_versioned()

    for r in sorted(by_rung):
        batch(by_rung[r])
    common = max(by_rung, key=lambda r: len(by_rung[r]))
    while plan.refresh_every and sess.tau_version < 1:
        batch(by_rung[common])
    sess.stats()


# ------------------------------------------------------------------ window --

class CompileCounter:
    """Counts XLA compilations (or compile-cache loads) while active, and
    the host seconds that building programs took: tracing, lowering to
    MLIR and compiling."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    BUILD = (COMPILE, "/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.names: List[str] = []
        self.seconds = 0.0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if not self.active or event not in self.BUILD:
            return
        if event == self.COMPILE:
            self.names.append(str(kw.get("fun_name", "?")))
        self.seconds += float(duration)


def run_window(cell: Cell, served: Served, seconds: float, *,
               trace_dir: Optional[str] = None,
               compiles: Optional[CompileCounter] = None) -> Record:
    """Drive the cell's traffic for ``seconds`` (at least; a flush that
    is running at the end is finished and counted)."""
    rec = Record()
    drv = driver(cell.traffic["driver"])
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    if compiles:
        compiles.active = True
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            drv.drive(served, cell.traffic, seconds, rec)
    finally:
        if compiles:
            compiles.active = False
        if trace_dir:
            jax.profiler.stop_trace()
    rec.reused = served.feed.cycles
    rec.compile_s = compiles.seconds if compiles else None
    return rec


def read_fold(sess, version0: int, since0: int):
    """The fold state and refresh count the window left (``reference.Fold``),
    copied to the host."""
    from chipbench.reference import Fold
    st = sess.service.state
    return Fold(*(np.asarray(a) for a in (st.centers, st.mask, st.weights,
                                          st.received, st.epoch)),
                refreshes=sess.tau_version - version0, since_refresh=since0)
