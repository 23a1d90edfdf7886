"""The backlog cases of ``test_chipbench_correct.py`` at cifar100-3072's
shape, under ``cifar100-backlog``'s limits: d lane-aligned (128), k' not
a power of two with k' <= sqrt(k), and every report of one fixed size
far below d, padded to the one rung. A sound run is correct; the
program's bf16 path fails ``report_gap``, a fold that keeps its state
fails ``misfolded``, and a broken timed path fails
``mostly_wrong_requests``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.run import run_cell  # noqa: E402

CELL = "cifar100-backlog"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ALIGNED = {
    "plan": {"k": 25, "k_prime": 5, "d": 128, "batch_size": 8,
             "bucket_sizes": [32], "capacity": 64, "refresh_every": 16,
             "fold_policy": "lru"},
    "population": {"m0": 8, "n_per_comp": 4, "sep": 60.0, "sigma": 1.0},
    "late_devices": {"n": {"dist": "fixed", "value": 20}, "kv_min": 1},
}
PEAKS = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
SEED = 2 ** 31 + 7
BACKLOG = {"driver": "backlog", "per_flush": 32, "pool": 64}


def aligned_run(plant=None, seconds=1.0):
    cell = harness.Cell(CELL, ALIGNED, BACKLOG,
                        harness.load_json(ROOT / "chipbench" / "limits"
                                          / f"{CELL}.json"), BENCH)
    return run_cell(cell, SEED, seconds, False, jax.devices()[:1], PEAKS,
                    plant_name=plant, t_start=time.perf_counter())


def test_sound_backlog_run_is_correct():
    res = aligned_run()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 32
    assert set(res["metrics"]) == {"attach_devices_per_s", "setup_s"}
    checks = res["checks"]
    assert checks["mostly_wrong_requests"]["value"] == 0.0
    assert checks["misfolded"]["value"] == 0
    assert checks["refreshes_off_cadence"]["value"] == 0
    assert checks["report_gap"]["value"] < 1e-5


@pytest.mark.parametrize("fault", ["alter", "halfbatch"])
def test_broken_timed_path_is_not_correct(fault):
    res = aligned_run(plant=fault)
    assert not res["correct"]
    assert res["checks"]["mostly_wrong_requests"]["value"] > \
        res["checks"]["mostly_wrong_requests"]["limit"]


@pytest.mark.parametrize("plant, caught_by", [("bf16", "report_gap"),
                                              ("stale", "misfolded")])
def test_partition_blind_plants_are_not_correct(plant, caught_by):
    res = aligned_run(plant=plant)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["mostly_wrong_requests"]["value"] == 0.0
    assert checks[caught_by]["value"] > checks[caught_by]["limit"]
