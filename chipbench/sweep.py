"""Find the highest Poisson rate a cell's configuration sustains, once,
on the chip: one set-up, then one window per offered rate, in rising
order, on the same session.

    python3 chipbench/sweep.py --workload femnist-poisson --seed <n> \
        --seconds 30 --rates 10,20,30,40

For each rate it prints the offered and delivered counts, the latency
median and 95th percentile, and the median latency of the last quarter
of arrivals over that of the first: a queue that grows through the
window shows as a ratio well above 1. The cell's traffic file then
takes about 0.8 x the highest rate whose queue does not grow. Not part
of a benchmark run.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 1
    from repro.utils.cache import use_compile_cache
    use_compile_cache()
    cell = harness.load_cell(args.workload)
    served = harness.build(cell, args.seed,
                           log=lambda m: print(m, flush=True))
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, driver="poisson", rate_per_s=rate)
        rec = harness.run_window(cell._replace(traffic=traffic), served,
                                 args.seconds)
        lat = np.array([(r["done"] - r["due"]) * 1e3 for r in rec.requests])
        q = max(1, len(lat) // 4)
        growth = np.median(lat[-q:]) / np.median(lat[:q])
        print(f"rate {rate:g}/s: offered {len(rec.requests)}, delivered "
              f"{len(rec.delivered())} in {rec.window_s:.3f} s, p50 "
              f"{np.percentile(lat, 50):.1f} ms, p95 "
              f"{np.percentile(lat, 95):.1f} ms, last/first quarter "
              f"median {growth:.2f}, {len(rec.flushes)} flushes, "
              f"generator late max {max(rec.late) * 1e3:.1f} ms",
              flush=True)
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
