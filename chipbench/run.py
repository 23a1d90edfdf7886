"""One run of one cell of the k-FED attach-service chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. From the seed the run builds the population, runs the
one-shot round, seeds a ``Session`` from it, makes the request pool and
warms up every shape the window uses (all of that is ``setup_s``); then
it drives the traffic through ``Session.submit`` and
``Session.flush_versioned`` for ``--seconds`` and checks every answer
due in the window, and the reports and tau versions the window left in
the service, against the plain reference (``reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read from a profiler trace
of the window), ``device`` (with ``--trace 1`` also ``busy_s`` and
``window_s``), with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared with its limit. The same checks are the last lines
of standard error. Without a TPU, or with fewer chips than the cell
asks for, the run prints no result and exits 1; an unknown workload
exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    from chipbench import plant
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plant", choices=plant.NAMES, default=None,
                    help="break the run on purpose (the correctness "
                         "check's control and faults; see plant.py)")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the trace file and its summary to DIR")
    return ap.parse_args(argv)


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def read_metrics(cell, rec, kind: str) -> dict:
    out = {}
    for m in harness.cell_metrics(cell.bench, cell.name, kind):
        value = harness.metric_reader(m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, *, plant_name=None, keep_trace=None,
             t_start: float = T_START) -> dict:
    """Set up, measure, check; returns the result object."""
    from chipbench import plant, reference
    from chipbench import trace as xtrace
    served = harness.build(cell, seed, plant.plan_override(plant_name),
                           log=log)
    plant.apply(plant_name, served.sess)
    compiles = harness.CompileCounter()
    stats = served.sess.stats()
    before, version0 = stats["plane_compiles"], stats["tau_version"]
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    setup_s = time.perf_counter() - t_start
    try:
        rec = harness.run_window(cell, served, seconds,
                                 trace_dir=trace_dir, compiles=compiles)
        rec.setup_s, rec.config, rec.peaks = setup_s, cell.config, peaks
        after = served.sess.stats()["plane_compiles"]
        dev = device_info(devices)
        if trace_dir:
            path = xtrace.find_xplane(trace_dir)
            pd = xtrace.read(path)
            rec.trace = xtrace.reduce(pd, program_files={
                f.name for f in (ROOT / "src" / "repro").rglob("*.py")})
            if keep_trace:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, Path(keep_trace) / Path(path).name)
                (Path(keep_trace) / "summary.txt").write_text(
                    xtrace.describe(pd))
            del pd
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    fold = harness.read_fold(served.sess, version0, stats["since_refresh"])
    pool, means = served.pool, served.means
    del served
    correct, checks, seen = reference.compare(rec.requests, pool, means,
                                              cell.config["plan"], fold,
                                              cell.limits)
    late = sorted(rec.late)
    took = sorted(f["end"] - f["start"] for f in rec.flushes)
    log(f"setup: {setup_s:.3f} s; window: {rec.window_s:.3f} s, "
        f"{len(rec.flushes)} flushes, {len(rec.requests)} requests, "
        f"pool passes past the first {rec.reused}")
    log(f"flush seconds: min {took[0]:.3f}, median "
        f"{took[len(took) // 2]:.3f}, max {took[-1]:.3f}")
    log(f"compiles in the window: {len(compiles.names)} "
        f"{sorted(set(compiles.names))}, built in {compiles.seconds:.3f} s; "
        f"plane_compiles {before} -> {after}")
    if late:
        log(f"generator late: median {late[len(late) // 2] * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms over {len(late)} requests")
    kind = "per_layer" if trace else "end_to_end"
    result = {"correct": bool(correct), "attempted": len(rec.requests),
              "failed": int(checks["unanswered"]["value"]),
              "metrics": read_metrics(cell, rec, kind), "device": dev}
    if rec.trace:
        dev["busy_s"] = rec.trace["busy_s"]
        dev["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
        log("trace modules: " + json.dumps(
            sorted(rec.trace["modules"].items(), key=lambda kv: -kv[1])[:12]))
    result["checks"] = checks
    log("not compared: " + ", ".join(f"{n} {v}" for n, v in seen.items()))
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = harness.load_cell(args.workload)
    except harness.UnknownWorkload as e:
        log(f"error: {e}")
        return 2
    chips = next(w["chips"] for w in cell.bench["workloads"]
                 if w["name"] == cell.name)
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"error: no TPU: JAX found no devices ({e})")
        return 1
    if devices[0].platform != "tpu":
        log(f"error: no TPU: JAX found {devices[0].platform}; this "
            f"benchmark measures the chip only")
        return 1
    if len(devices) < chips:
        log(f"error: the cell needs {chips} TPU chips, JAX found "
            f"{len(devices)}")
        return 1
    from chipbench.work import peaks
    from repro.utils.cache import use_compile_cache
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:chips], peaks(devices[0].device_kind),
                      plant_name=args.plant, keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
