"""The trace reduction on a small trace recorded on one TPU v5e chip
(``testdata/v5e_small.xplane.pb``): under a ``chipbench.window`` host
span, a jitted matmul-sum, a 10 ms sleep, a jitted sin, a 10 ms sleep
and the matmul-sum again.

Expected values, worked out by hand from the file's events (ns):

* window: [49,303,309, 73,906,659) -> 24,603,350 ns;
* device ops inside it: sine_multiply_fusion [60,253,934, 60,281,837)
  (27,903), then copy-start [72,232,912, 72,232,925) (13), copy-done
  [72,232,927, 72,238,853) (5,926) and convolution_reduce_fusion
  [72,238,854, 72,250,661) (11,807): busy 45,649 ns. The first
  matmul-sum ran at [48,464,136, 48,481,872) on the device clock, before
  the host span opened (the device clock lags the host's by about a
  millisecond), so it falls outside the window;
* modules: ``jit__lambda`` [60,253,931, 60,281,837) and [72,232,910,
  72,250,662): 27,906 + 17,752 = 45,658 ns;
* idle gaps: 60,281,837 -> 72,232,912 (11,951,075), 49,303,309 ->
  60,253,934 (10,950,625), 72,250,661 -> 73,906,659 (1,655,998).
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402

SMALL = ROOT / "chipbench" / "testdata" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.read(str(SMALL)))


def test_busy_union_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(24_603_350e-9, abs=1e-12)
    assert reduced["busy_s"] == pytest.approx(45_649e-9, abs=1e-12)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(1 - 45_649 / 24_603_350, abs=1e-12)


def test_per_module_time(reduced):
    assert reduced["modules"] == {
        "jit__lambda": pytest.approx(45_658e-9, abs=1e-12)}
    assert trace.module_seconds(reduced, ["jit__lambda"]) == \
        pytest.approx(45_658e-9, abs=1e-12)
    assert trace.module_seconds(reduced, ["jit_step"]) is None


def test_top_ops_and_idle_gaps(reduced):
    top = reduced["device_ops"][0]
    assert top[0].startswith("%sine_multiply_fusion")
    assert top[1] == pytest.approx(27_903e-9, abs=1e-12)
    assert [g[1] for g in reduced["idle_gaps"][:3]] == pytest.approx(
        [11_951_075e-9, 10_950_625e-9, 1_655_998e-9], abs=1e-12)


def test_interval_arithmetic():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)]
    assert trace.union_length(iv) == 4.0
    assert trace.gaps(iv, -1.0, 8.0) == [(-1.0, 0.0), (3.0, 5.0),
                                         (6.0, 8.0)]
    assert trace.module_key("jit_step(9724910788877101858)") == "jit_step"
    assert trace.frame_file("$stream.py:689 _serve_batch") == "stream.py"
