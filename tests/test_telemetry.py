"""Flush telemetry (fed/telemetry.py, DESIGN.md §12): the serving layer's
cumulative flush counters and the ``kfed.*`` phase boundaries they are
timed at.

The counters are exact functions of the served stream (batches, padded
rows and points, refreshes); the phase seconds are self times, so a
nested phase's time is taken out of its parent's. Neither rides a
checkpoint: a restored service starts at zero.
"""
import numpy as np
import pytest

import jax

from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed import telemetry
from repro.fed.api import FederationPlan, Session

K, KP, D = 8, 2, 16


@pytest.fixture(scope="module")
def fixture_round():
    fm = structured_devices(jax.random.PRNGKey(0), k=K, d=D, k_prime=KP,
                            m0=4, n_per_comp_dev=20, sep=60.0)
    rr = Session(FederationPlan(k=K, k_prime=KP, d=D)).run(
        jax.random.PRNGKey(1), fm.data).detail
    return fm, rr


def _session(rr) -> Session:
    return Session.from_round(FederationPlan(
        k=K, k_prime=KP, d=D, capacity=64, batch_size=4,
        bucket_sizes=(16, 128), refresh_every=4, autoscale="off"), rr)


def _requests(fm):
    """Three requests of 10 points and two of 100."""
    small = late_device_stream(fm.means, KP, 3, 1, n_range=(10, 11))
    large = late_device_stream(fm.means, KP, 2, 2, n_range=(100, 101))
    return [r[0] for r in small + large], [r[2] for r in small + large]


def test_flush_counters_count_batches_rows_and_padding(fixture_round):
    """One flush of 3 x 10 and 2 x 100 points over pads (16, 128) at
    batch 4: one batch per rung, each padded by repeat to 4 rows, so
    8 rows and 4 x 16 + 4 x 128 = 576 points stepped for 5 devices and
    230 real points; the 5 folds cross the refresh cadence of 4 once."""
    fm, rr = fixture_round
    sess = _session(rr)
    reqs, kvs = _requests(fm)
    sess.serve(reqs, kvs)
    st = sess.stats()
    f = st["flush"]
    assert {k: f[k] for k in ("flushes", "batches", "rows_stepped",
                              "points_stepped", "refreshes")} == {
        "flushes": 1, "batches": 2, "rows_stepped": 8,
        "points_stepped": 576, "refreshes": 1}
    assert st["served_devices"] == 5 and st["served_points"] == 230
    assert sess.tau_version == 1
    phases = {f"{p}_s" for p in telemetry.PHASES}
    assert phases <= set(f) and all(f[p] >= 0.0 for p in phases)
    assert f["refresh_s"] > 0.0 and f["step_s"] > 0.0
    assert not any(k.startswith("last_") for k in st["autoscale"])


def test_flush_counters_count_projection_iterations(fixture_round):
    """Each delivered real row adds its step-1 iteration count: the
    mixture's rows all converge (none reaches the cap), each in at
    least one step; the three repeat-padding rows add nothing."""
    from repro.core.local_kmeans import PROJ_MAX_ITERS
    fm, rr = fixture_round
    sess = _session(rr)
    reqs, kvs = _requests(fm)
    sess.serve(reqs, kvs)
    f = sess.stats()["flush"]
    assert f["proj_capped"] == 0
    assert 1 <= f["proj_iters_max"] <= PROJ_MAX_ITERS
    assert 5 <= f["proj_iters"] <= 5 * f["proj_iters_max"]
    sess.serve(reqs, kvs)
    g = sess.stats()["flush"]
    assert g["proj_iters"] == 2 * f["proj_iters"]
    assert g["proj_iters_max"] == f["proj_iters_max"]


def test_phase_seconds_are_self_time(monkeypatch):
    """A nested phase's seconds come out of its parent's: fold 0-6 s
    holding a refresh 1-4 s gives fold 3 s and refresh 3 s; the flush
    around them keeps only its own 2 s."""
    ticks = iter([0.0, 1.0, 2.0, 5.0, 7.0, 8.0])
    monkeypatch.setattr(telemetry.time, "perf_counter",
                        lambda: next(ticks))
    tel = telemetry.FlushTelemetry()
    tel.flushes += 1
    with tel.phase("flush"):              # 0 .. 8
        with tel.phase("fold"):           # 1 .. 7
            with tel.phase("refresh"):    # 2 .. 5
                pass
    assert tel.seconds["refresh"] == 3.0
    assert tel.seconds["fold"] == 3.0
    assert tel.seconds["flush"] == 2.0
    assert tel.stats()["flush_s"] == 2.0 and tel.stats()["flushes"] == 1


def test_phase_counts_time_when_the_body_raises(monkeypatch):
    """A phase that raises still closes its span and books its time,
    and its parent stays consistent."""
    ticks = iter([0.0, 1.0, 3.0, 4.0])
    monkeypatch.setattr(telemetry.time, "perf_counter",
                        lambda: next(ticks))
    tel = telemetry.FlushTelemetry()
    with pytest.raises(RuntimeError):
        with tel.phase("flush"):
            with tel.phase("step"):
                raise RuntimeError("boom")
    assert tel.seconds["step"] == 2.0 and tel.seconds["flush"] == 2.0
    assert tel._inner == []


def test_counters_never_ride_a_checkpoint(fixture_round, tmp_path):
    """Observability only: a restore starts every counter at zero, and
    the restored service serves the rest bitwise like the live one."""
    fm, rr = fixture_round
    live = _session(rr)
    reqs, kvs = _requests(fm)
    live.serve(reqs[:3], kvs[:3])
    path = live.save(str(tmp_path / "ck.npz"))
    restored = Session.restore(path, live.plan)
    f = restored.stats()["flush"]
    assert all(v == 0 for v in f.values()), f
    for a, b in zip(live.serve(reqs[3:], kvs[3:]),
                    restored.serve(reqs[3:], kvs[3:])):
        np.testing.assert_array_equal(a, b)
    assert restored.stats()["flush"]["batches"] == 1
    assert live.stats()["flush"]["batches"] == 2


@pytest.mark.parametrize("drift,capacity", [("off", 40), ("decay", 48)])
def test_refresh_builds_algorithm_2_once(fixture_round, drift, capacity):
    """Algorithm 2 is one program compiled once per fold-state shape:
    the first refresh builds it, and the later ones (each at its own
    ``now_epoch`` under ``decay``) trace nothing and compile nothing.
    A capacity no other test folds at makes that first build this
    test's own."""
    fm, rr = fixture_round
    sess = Session.from_round(FederationPlan(
        k=K, k_prime=KP, d=D, capacity=capacity, batch_size=4,
        bucket_sizes=(16, 128), refresh_every=4, autoscale="off",
        drift=drift, drift_half_life=8 if drift == "decay" else 0), rr)
    reqs, kvs = _requests(fm)
    compiled = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    sess.serve(reqs[:4], kvs[:4])
    f = sess.stats()["flush"]
    assert (f["refreshes"], f["refresh_builds"]) == (1, 1)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for _ in range(3):
            sess.serve(reqs[:4], kvs[:4])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    f = sess.stats()["flush"]
    assert (f["refreshes"], f["refresh_builds"]) == (4, 1)
    assert compiled == []


def test_staging_rings_stop_allocating_after_warm_up(fixture_round):
    """The first flush makes one ring per batch shape (two shapes here,
    of ``STAGING_DEPTH`` slots each); later flushes of the same mix
    allocate nothing and prep every batch into a retained slot."""
    from repro.fed.stream import STAGING_DEPTH
    fm, rr = fixture_round
    sess = _session(rr)
    reqs, kvs = _requests(fm)
    sess.serve(reqs, kvs)
    f0 = sess.stats()["flush"]
    assert (f0["staging_allocs"], f0["staging_reuses"]) == (
        2 * STAGING_DEPTH, 0)
    for _ in range(3):
        sess.serve(reqs, kvs)
    f1 = sess.stats()["flush"]
    assert f1["staging_allocs"] == f0["staging_allocs"]
    assert f1["staging_reuses"] == f1["batches"] - f0["batches"] == 6
    assert 0 <= f1["staging_waits"] <= f1["staging_reuses"]


def test_request_keys_match_the_eager_fold_in():
    """The compiled key derivation gives bitwise the keys of the eager
    ``vmap(fold_in)`` over the ids cast to uint32, wrap-around
    included."""
    import jax.numpy as jnp
    from repro.fed.stream import _request_keys
    base = jax.random.PRNGKey(20260)
    rids = np.array([0, 1, 7, 4095, 2 ** 31 - 1, 2 ** 31 + 5,
                     2 ** 32 - 1, 2 ** 32 + 3], np.int64)
    eager = jax.vmap(lambda r: jax.random.fold_in(base, r))(
        jnp.asarray(rids, jnp.uint32))
    got = _request_keys(base, rids.astype(np.uint32))
    assert got.dtype == eager.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(eager))
