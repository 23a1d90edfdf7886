"""The benchmark's manifest: BENCHMARK.json, and the files it names.

Every cell names a configuration, a traffic mix (with its driver) and
the limits of its check; every metric has its reader, reads from the
source the manifest states, and moves an end-to-end metric its cells
report; every name and unit is legal. ``run.py`` refuses to run without
a TPU and on an unknown workload.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "chipbench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, work  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units_are_legal():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in METRICS]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) >= len(BENCH["configs"]) + len(CELLS)
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        assert len({g["name"] for g in group}) == len(group)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_load(cell):
    wl = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert wl["chips"] in (1, 4)
    c = harness.load_cell(cell)
    assert (HERE / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert set(c.limits) == {"mostly_wrong_requests", "unanswered",
                             "misfolded", "report_gap",
                             "refreshes_off_cadence"}
    entry = next(x for x in BENCH["configs"] if x["name"] == wl["config"])
    assert entry["file"] == f"chipbench/configs/{wl['config']}.json"
    assert entry["reduced"] == c.config["reduced"]
    harness.make_plan(c.config)          # a valid FederationPlan
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, cell,
                                                   "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(BENCH, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader_exists_and_states_its_source(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    reader = harness.metric_reader(metric)
    assert reader.SOURCE == m["source"]
    assert callable(reader.read)
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
        return
    moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS)


def test_peak_table_is_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 1.97e14 and p["hbm_bytes_per_s"] == 8.19e11
    assert "TPU v5e" in p["source"]
    with pytest.raises(work.UnknownDevice):
        work.peaks("cpu")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(HERE / "run.py"), *args],
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=300)


def test_run_refuses_without_a_tpu():
    out = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_refuses_an_unknown_workload():
    out = _run("--workload", "no-such-cell", "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert out.returncode != 0
    assert "unknown workload" in out.stderr
    assert out.stdout.strip() == ""
