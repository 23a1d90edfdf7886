"""The least work the served mathematics needs, and the least time the
chip could take for it: the yardstick of the kernel layer's roofline
share, independent of how the program implements the step.

Per request of n real points (padding never counts), in d dimensions,
with k' local and k global centers:

* least FLOPs: 2·n·d·k' (one assignment pass of the local solve) plus
  2·k'·d·k (the Theorem 3.2 attach of its k' centers against tau);
* least bytes: 4·n·d (the points, read once) plus 4·n (the labels,
  written once); tau (4·k·d bytes) is read at least once per flush.

No count of iterations enters, so no sound change to the program can
push a share built on this past 100%. The FLOPs are held to the chip's
bf16 peak, which bounds f32 work from above. At the configurations'
shapes the bytes bind: a request of 227 points at d=784 needs about
0.71 MB (0.87 us at 819 GB/s) against 3.65 MFLOP (0.02 us at 197
TFLOP/s).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak table's entry for a ``device_kind``; unknown kinds are an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{path.name} (it has {sorted(table)})")
    return table[device_kind]


def least_flops(n: int, d: int, k: int, k_prime: int) -> float:
    return 2.0 * n * d * k_prime + 2.0 * k_prime * d * k


def least_bytes(n: int, d: int) -> float:
    return 4.0 * n * d + 4.0 * n


def least_seconds(ns, flushes: int, d: int, k: int, k_prime: int,
                  peak: dict) -> float:
    """Least time for serving requests of ``ns`` points over ``flushes``
    flushes: the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s."""
    flops = sum(least_flops(n, d, k, k_prime) for n in ns)
    nbytes = sum(least_bytes(n, d) for n in ns) + 4.0 * k * d * flushes
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
