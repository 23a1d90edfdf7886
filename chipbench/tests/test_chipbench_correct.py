"""The correctness check, driven through a whole run on the CPU at a
small size (the look for a chip skipped): a sound run is correct, and a
run whose timed path is broken underneath is not, each through the
number that is there to catch it.

The precision control (the program's bf16 path) and a fold that returns
its state unchanged leave every served partition as the generating
components make it at this separation; the reports the fold keeps catch
them.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, reference  # noqa: E402
from chipbench.run import run_cell  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SMALL = {
    "plan": {"k": 8, "k_prime": 2, "d": 16, "batch_size": 8,
             "bucket_sizes": [16, 64], "capacity": 64, "refresh_every": 16,
             "fold_policy": "lru"},
    "population": {"m0": 8, "n_per_comp": 8, "sep": 60.0, "sigma": 1.0},
    "late_devices": {"n": {"dist": "gamma", "mean": 30, "sd": 10, "lo": 4,
                           "hi": 64}, "kv_min": 1},
}
PEAKS = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
SEED = 2 ** 31 + 7


def small_run(cell_name, traffic, plant=None, seconds=1.0):
    cell = harness.Cell(cell_name, SMALL, traffic,
                        harness.load_json(ROOT / "chipbench" / "limits"
                                          / f"{cell_name}.json"), BENCH)
    return run_cell(cell, SEED, seconds, False, jax.devices()[:1], PEAKS,
                    plant_name=plant, t_start=time.perf_counter())


BACKLOG = {"driver": "backlog", "per_flush": 32, "pool": 64}


def test_sound_backlog_run_is_correct():
    res = small_run("femnist-backlog", BACKLOG)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 32
    assert set(res["metrics"]) == {"attach_devices_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    checks = res["checks"]
    assert checks["mostly_wrong_requests"]["value"] == 0.0
    assert checks["misfolded"]["value"] == 0
    assert checks["refreshes_off_cadence"]["value"] == 0
    assert checks["report_gap"]["value"] < 1e-5


def test_sound_poisson_run_is_correct():
    traffic = {"driver": "poisson", "rate_per_s": 40.0, "pool": 64}
    res = small_run("femnist-poisson", traffic)
    assert res["correct"] and res["attempted"] == 40
    assert set(res["metrics"]) == {"attach_p50_ms", "attach_p95_ms",
                                   "setup_s"}


@pytest.mark.parametrize("fault", ["alter", "halfbatch"])
def test_broken_timed_path_is_not_correct(fault):
    res = small_run("femnist-backlog", BACKLOG, plant=fault)
    assert not res["correct"]
    assert res["checks"]["mostly_wrong_requests"]["value"] > \
        res["checks"]["mostly_wrong_requests"]["limit"]


@pytest.mark.parametrize("plant, caught_by", [("bf16", "report_gap"),
                                              ("stale", "misfolded")])
def test_partition_blind_plants_are_not_correct(plant, caught_by):
    res = small_run("femnist-backlog", BACKLOG, plant=plant)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["mostly_wrong_requests"]["value"] == 0.0
    assert checks[caught_by]["value"] > checks[caught_by]["limit"]


def test_a_refresh_that_never_runs_is_off_cadence():
    fold = reference.Fold(*(np.zeros(1),) * 5, refreshes=0,
                          since_refresh=0)
    requests = [{"done": 1.0, "flush": 1.0}] * 200
    plan = {"capacity": 0, "refresh_every": 16, "batch_size": 8}
    values, _ = reference.fold_checks(requests, None, plan, fold)
    assert values["refreshes_off_cadence"] == 200 // 23
    ok, _ = reference.fold_checks(requests, None, plan,
                                  fold._replace(refreshes=10))
    assert ok["refreshes_off_cadence"] == 0


def test_set_gap_is_relative_and_symmetric():
    means = np.array([[3.0, 4.0], [0.0, 10.0]])
    assert reference.set_gap(means[::-1], means) == 0.0
    assert reference.set_gap(means + [0.05, 0.0], means) == \
        pytest.approx(0.01)
    assert reference.set_gap(means[:1], means) > 0.5


def test_match_renumbers_one_to_one():
    served = [5, 5, 3, 3, 3, 1]
    ref = [0, 0, 2, 2, 1, 1]
    assert reference.match(served, ref) == {3: 2, 5: 0, 1: 1}


def test_merges_names_a_center_shared_by_two_components():
    ref = np.repeat([0, 1, 2], 10)
    assert not reference.merges(ref + 3, ref)
    assert not reference.merges(np.r_[ref[:29] + 3, 3], ref)
    assert reference.merges(np.where(ref == 2, 4, ref + 3), ref)
