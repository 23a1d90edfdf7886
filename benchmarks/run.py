"""Benchmark harness — one bench per paper table/figure (+ kernels +
roofline). Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale
  PYTHONPATH=src python -m benchmarks.run --only table1,fig3
  PYTHONPATH=src python -m benchmarks.run --list     # valid bench keys
  PYTHONPATH=src python -m benchmarks.run --json .   # + BENCH_<ts>.json

``--json OUT`` additionally writes a structured ``BENCH_<timestamp>.json``
perf record (rows + per-bench wall time + environment) next to the
unchanged CSV stdout; OUT may be a directory or an explicit .json path.

Every row is printed even when a bench fails, but the exit code is 1 if
any bench raised or printed an ``ERROR:`` row.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.utils.cache import use_compile_cache

BENCHES = {
    "table1": "benchmarks.bench_table1_gaussian",
    "fig1": "benchmarks.bench_fig1_separation",
    "fig2": "benchmarks.bench_fig2_heterogeneity",
    "fig3": "benchmarks.bench_fig3_communication",
    "table2": "benchmarks.bench_table2_personalization",
    "fig4": "benchmarks.bench_fig4_selection",
    "kernels": "benchmarks.bench_kernels",
    "attach": "benchmarks.bench_attach_throughput",
    "ablation_moe": "benchmarks.bench_ablation_moe",
    "roofline": "benchmarks.bench_roofline",
    "drift": "benchmarks.bench_drift",
    "route": "benchmarks.bench_route_serve",
    "encode": "benchmarks.bench_encode_serve",
}


def _parse_row(bench: str, row: str) -> dict:
    """CSV row -> structured record (derived may itself contain commas)."""
    parts = row.split(",", 2)
    rec = {"bench": bench, "name": parts[0]}
    try:
        rec["us_per_call"] = float(parts[1]) if len(parts) > 1 else None
    except ValueError:
        rec["us_per_call"] = None
    rec["derived"] = parts[2] if len(parts) > 2 else ""
    return rec


def _json_path(out: str, stamp: str) -> str:
    if out.endswith(".json"):
        parent = os.path.dirname(out)
        if parent:
            os.makedirs(parent, exist_ok=True)
        return out
    os.makedirs(out, exist_ok=True)
    return os.path.join(out, f"BENCH_{stamp}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench keys")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write a BENCH_<timestamp>.json perf record to "
                         "the OUT directory (or exact .json path)")
    ap.add_argument("--list", action="store_true",
                    help="print the valid bench keys and exit")
    args = ap.parse_args()
    if args.list:
        for key in BENCHES:
            print(key)
        return
    keys = list(BENCHES) if not args.only else args.only.split(",")
    unknown = [key for key in keys if key not in BENCHES]
    if unknown:
        print(f"error: unknown bench key(s): {', '.join(unknown)}\n"
              f"valid keys: {', '.join(BENCHES)}", file=sys.stderr)
        sys.exit(2)

    import importlib

    use_compile_cache()
    t_start = time.time()
    records, durations, failed = [], {}, []
    print("name,us_per_call,derived")
    for key in keys:
        mod = importlib.import_module(BENCHES[key])
        t0 = time.time()
        try:
            rows = mod.run(full=args.full)
        except Exception as e:  # keep the harness running
            rows = [f"{key},0,ERROR:{e!r}"]
        for r in rows:
            print(r)
            records.append(_parse_row(key, r))
        if any(rec["derived"].startswith("ERROR:")
               for rec in records if rec["bench"] == key):
            failed.append(key)
        durations[key] = round(time.time() - t0, 2)
        print(f"# {key} done in {durations[key]:.1f}s", file=sys.stderr)

    if args.json:
        import jax
        stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime(t_start))
        record = {
            "timestamp": stamp,
            "full": args.full,
            "benches": keys,
            "rows": records,
            "durations_s": durations,
            "total_s": round(time.time() - t_start, 2),
            "env": {
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
                # Interprets the serve-plane speedup rows: sharding the
                # batch axis over forced host devices is bounded by the
                # physical core count, not the device count.
                "cpu_count": os.cpu_count(),
                "xla_flags": os.environ.get("XLA_FLAGS", ""),
            },
        }
        path = _json_path(args.json, stamp)
        with open(path, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# perf record -> {path}", file=sys.stderr)
    if failed:
        print(f"error: bench(es) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
