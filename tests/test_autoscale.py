"""The load-adaptive serve plane (fed/autoscale.py over fed/plane.py +
fed/stream.py, DESIGN.md §12).

Covers the three controller promises:

  * decisions are PURE functions of (policy, queue snapshot, persisted
    controller state) — unit-tested directly on ``decide`` — and never
    change per-request labels (scaling is result-neutral; only
    refresh/version boundaries track the batch shape);
  * every (shards, batch, bucket) triple compiles exactly once —
    steady-state traffic over an already-seen load shape never
    recompiles (``ServePlane.compile_count`` flat, the acceptance
    criterion);
  * the decision state rides the schema-v3 checkpoint, so a restore
    mid-stream replays labels, tau versions, fold state AND the
    decision sequence bitwise (property test), while v1/v2 checkpoints
    still restore.

The mesh tests build over whatever devices exist — the CI mesh leg
runs this file under ``--xla_force_host_platform_device_count={2,8}``
so shard-count switching is exercised on both a cramped and a roomy
grant; on one device the controller degenerates to batch/ladder
scaling only and every assertion still pins it.
"""
import warnings

import numpy as np
import pytest

import jax

from _hyp import given, settings, st

from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed import autoscale as A
from repro.fed.api import FederationPlan, PlanError, Session
from repro.fed.plane import ServePlaneError
from repro.fed.stream import ReproPerfWarning, StreamConfigError
from repro.utils.compat import make_mesh

K, KP, D = 16, 4, 24
NDEV = jax.device_count()


@pytest.fixture(scope="module")
def fixture_round():
    fm = structured_devices(jax.random.PRNGKey(0), k=K, d=D, k_prime=KP,
                            m0=4, n_per_comp_dev=25, sep=60.0)
    rr = Session(FederationPlan(k=K, k_prime=KP, d=D)).run(
        jax.random.PRNGKey(1), fm.data).detail
    return fm, rr


def _plan(**kw):
    base = dict(k=K, k_prime=KP, d=D, capacity=256, batch_size=8,
                bucket_sizes=(32, 64, 128))
    base.update(kw)
    return FederationPlan(**base)


def _requests(fm, count, seed, n_range=(10, 120)):
    stream = late_device_stream(fm.means, KP, count, seed,
                                n_range=n_range)
    return [r[0] for r in stream], [r[2] for r in stream]


def _serve_depths(sess, reqs, kvs, depths):
    """Submit `depth` requests per flush (the queue shapes the bench
    and the controller see), returning [(labels, version)] in request
    order."""
    out, i = [], 0
    for q in depths:
        rids = [sess.submit(reqs[(i + j) % len(reqs)],
                            kvs[(i + j) % len(kvs)]) for j in range(q)]
        i += q
        got = sess.flush_versioned()
        out.extend(got[r] for r in rids)
    return out


# ------------------------------------------------------ decision rule --


def test_decide_is_pure_and_tracks_queue_depth():
    """latency: the batch rung is the next power of two of the queue
    depth (capped at the plan ceiling), shards follow the batch within
    the grant — and the same inputs always produce the same decision."""
    base = (64, 256)
    prev = A.AutoscaleDecision(8, 64, base, 0)
    kw = dict(max_batch=64, granted=8, n_axes=1, base_ladder=base,
              prev=prev, streak=0)
    snap = A.QueueSnapshot(3, ((64, 3),))
    d1, s1 = A.decide("latency", snap, **kw)
    assert (d1.batch_size, d1.shards, d1.seq) == (4, 4, 1)
    assert A.decide("latency", snap, **kw) == (d1, s1)  # pure
    deep = A.QueueSnapshot(500, ((64, 500),))
    d2, _ = A.decide("latency", deep, **kw)
    assert (d2.batch_size, d2.shards) == (64, 8)  # ceiling + full grant
    d3, _ = A.decide("latency", A.QueueSnapshot(1, ((64, 1),)), **kw)
    assert (d3.batch_size, d3.shards) == (1, 1)


def test_off_controller_is_inert():
    """``off`` never reaches the decision rule: observe() returns the
    static plan decision untouched, seq stays 0, whatever the queue
    looks like."""
    ctl = A.AutoscaleController("off", max_batch=16, granted=4,
                                n_axes=1, base_ladder=(64,))
    static = ctl.decision
    for snap in (A.QueueSnapshot(1, ((64, 1),)),
                 A.QueueSnapshot(500, ((64, 500),))):
        assert ctl.observe(snap) == static
    assert ctl.decision.seq == 0 and ctl.streak == 0
    assert (static.shards, static.batch_size, static.ladder) == (
        4, 16, (64,))


def test_throughput_shrinks_only_after_streak():
    """throughput holds the full batch through a single shallow flush
    (a dip inside a burst) and only shrinks after SHRINK_STREAK
    consecutive ones; growth is instant."""
    base = (64,)
    kw = dict(max_batch=64, granted=8, n_axes=1, base_ladder=base)
    prev = A.AutoscaleDecision(8, 64, base, 0)
    shallow = A.QueueSnapshot(1, ((64, 1),))
    d1, s1 = A.decide("throughput", shallow, prev=prev, streak=0, **kw)
    assert d1.batch_size == 64 and s1 == 1          # held through dip 1
    d2, s2 = A.decide("throughput", shallow, prev=d1, streak=s1, **kw)
    assert d2.batch_size == 1 and s2 == 0           # shrunk on dip 2
    deep = A.QueueSnapshot(64, ((64, 64),))
    d3, s3 = A.decide("throughput", deep, prev=d2, streak=s2, **kw)
    assert d3.batch_size == 64 and s3 == 0          # instant growth


def test_shards_divide_batch_within_grant():
    assert A.shards_for(64, 8, 1) == 8
    assert A.shards_for(4, 8, 1) == 4
    assert A.shards_for(8, 6, 1) == 4    # non-pow2 grant: pow2 floor
    assert A.shards_for(12, 6, 1) == 6   # full grant when it divides
    assert A.shards_for(8, 6, 2) == 1    # multi-axis: 1 or full only
    assert A.shards_for(12, 6, 2) == 6


def test_ladder_rebuckets_oversized_backlog():
    """Oversized queue entries fragment across geometric rungs under
    latency (tight pads) but coalesce into ONE rung under throughput —
    or under latency once the oversized backlog alone fills a batch."""
    base = (32,)
    hist = ((32, 2), (64, 1), (128, 1), (256, 1))
    snap = A.QueueSnapshot(5, hist)
    assert A._ladder_for("latency", snap, 8, base) == (32, 64, 128, 256)
    assert A._ladder_for("throughput", snap, 8, base) == (32, 256)
    assert A._ladder_for("latency", snap, 2, base) == (32, 256)
    none = A.QueueSnapshot(2, ((32, 2),))
    assert A._ladder_for("throughput", none, 8, base) == base


def test_snapshot_queue_histogram():
    snap = A.snapshot_queue([5, 30, 33, 70, 300], (32, 64))
    assert snap.pending == 5
    assert snap.hist == ((32, 2), (64, 1), (128, 1), (512, 1))
    assert A.bucket_of(65, (32, 64)) == 128 and A.bucket_of(64, (64,)) == 64


def test_validation_named_errors():
    with pytest.raises(PlanError, match="autoscale"):
        _plan(autoscale="bogus")
    with pytest.raises(PlanError,
                       match="batch_size.*power of two"):
        _plan(autoscale="latency", batch_size=12)
    with pytest.raises(A.AutoscaleError, match="autoscale"):
        A.AutoscaleController("nope", max_batch=8, granted=1, n_axes=1,
                              base_ladder=(64,))
    _plan(autoscale="latency")  # valid knob constructs


# ------------------------------------------------- end-to-end serving --


def test_labels_invariant_under_autoscale(fixture_round):
    """Scaling is result-neutral: per-request labels and the folded
    state match the static plan bitwise for the same stream (versions
    too, with no refresh cadence)."""
    fm, rr = fixture_round
    reqs, kvs = _requests(fm, 17, seed=3)
    depths = [1, 2, 8, 5, 1]
    static = Session.from_round(_plan(), rr)
    auto = Session.from_round(_plan(autoscale="latency"), rr)
    out_a = _serve_depths(static, reqs, kvs, depths)
    out_b = _serve_depths(auto, reqs, kvs, depths)
    for (la, va), (lb, vb) in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
        assert va == vb == 0
    for x, y in zip(jax.tree.leaves(static.service.state),
                    jax.tree.leaves(auto.service.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    st_ = auto.stats()["autoscale"]
    assert st_["decisions"] == len(depths)
    assert st_["batch_size"] == 1  # last flush had depth 1
    assert static.stats()["autoscale"]["decisions"] == 0  # off: static


def test_steady_state_never_recompiles(fixture_round):
    """Acceptance criterion: after one warm-up pass over a ramp load
    shape, repeating the ramp (any number of times) adds ZERO compiled
    signatures — the (shards, batch, bucket) step cache absorbs every
    scaling decision."""
    fm, rr = fixture_round
    reqs, kvs = _requests(fm, 32, seed=5, n_range=(100, 128))
    ramp = [1, 2, 4, 8]
    sess = Session.from_round(_plan(autoscale="latency",
                                    refresh_every=4), rr)
    _serve_depths(sess, reqs, kvs, ramp)          # warm-up
    warm = sess.stats()["plane_compiles"]
    for _ in range(3):
        _serve_depths(sess, reqs, kvs, ramp)      # steady state
    assert sess.stats()["plane_compiles"] == warm
    assert sess.stats()["autoscale"]["decisions"] == 4 * len(ramp)
    assert sess.tau_version > 0                   # refreshes really ran


def test_sharded_autoscale_matches_single_host(fixture_round):
    """Shard-count switching is result-neutral AND decision-neutral:
    the sharded-grant session makes the same (batch, ladder) decisions
    and serves bitwise-identical labels/versions as the single-host
    session (the CI mesh leg runs this at 2 and 8 devices)."""
    fm, rr = fixture_round
    reqs, kvs = _requests(fm, 2 * NDEV + 9, seed=7)
    depths = [1, NDEV, 2 * NDEV + 3, 2, 3]
    kw = dict(batch_size=16, refresh_every=3, refresh="async",
              autoscale="latency")
    single = Session.from_round(_plan(**kw), rr)
    shard = Session.from_round(_plan(**kw, serve_axes=("data",)), rr,
                               mesh=make_mesh((NDEV,), ("data",)))
    out_a = _serve_depths(single, reqs, kvs, depths)
    out_b = _serve_depths(shard, reqs, kvs, depths)
    for (la, va), (lb, vb) in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
        assert va == vb
    for x, y in zip(jax.tree.leaves(single.service.state),
                    jax.tree.leaves(shard.service.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    sa = single.stats()["autoscale"]
    sb = shard.stats()["autoscale"]
    assert sa["batch_size"] == sb["batch_size"]
    assert sa["ladder"] == sb["ladder"]
    assert sb["granted_shards"] == NDEV
    assert sb["shards"] <= NDEV


def test_oversized_coalesce_end_to_end(fixture_round):
    """Under throughput, a flush with multi-rung oversized backlog
    re-buckets into ONE coalesced rung (one jit shape) and still serves
    the exact labels of the static geometric ladder."""
    fm, rr = fixture_round
    stream = late_device_stream(fm.means, KP, 6, 9, n_range=(40, 290))
    reqs, kvs = [r[0] for r in stream], [r[2] for r in stream]
    static = Session.from_round(_plan(bucket_sizes=(32,)), rr)
    auto = Session.from_round(
        _plan(bucket_sizes=(32,), autoscale="throughput"), rr)
    with pytest.warns(ReproPerfWarning, match="largest configured"):
        out_a = static.serve(reqs, kvs)
    with pytest.warns(ReproPerfWarning, match="largest configured"):
        out_b = auto.serve(reqs, kvs)
    for la, lb in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
    ladder = auto.stats()["autoscale"]["ladder"]
    assert len(ladder) == 2 and ladder[0] == 32   # base + ONE rung
    assert ladder[1] >= max(r.shape[0] for r in reqs)


def test_oversized_warning_latches_per_ladder_rung(fixture_round):
    """The oversized-pad warning latches on the (active ladder, rung)
    pair, not a session-wide bool (bugfix): repeats of an already-
    warned shape are silent, a different rung warns once, and when the
    autoscaler COALESCES the ladder the re-bucketed shape warns once
    more under its new key — the old latch stayed silent forever after
    the first oversized request, hiding every later re-bucket."""
    fm, rr = fixture_round
    reqs, kvs = _requests(fm, 4, seed=21, n_range=(60, 61))
    big, bkv = _requests(fm, 2, seed=23, n_range=(130, 131))
    # static ladder: keyed per RUNG
    sess = Session.from_round(_plan(bucket_sizes=(32,)), rr)
    with pytest.warns(ReproPerfWarning, match="largest configured"):
        sess.serve(reqs[:1], kvs[:1])           # rung 64: warns once
    with warnings.catch_warnings():
        warnings.simplefilter("error", ReproPerfWarning)
        sess.serve(reqs[1:2], kvs[1:2])         # same key: latched
    with pytest.warns(ReproPerfWarning, match="largest configured"):
        sess.serve(big[:1], bkv[:1])            # rung 256: new key
    # autoscale coalesce: keyed per LADDER too
    auto = Session.from_round(
        _plan(bucket_sizes=(32,), autoscale="throughput"), rr)
    with pytest.warns(ReproPerfWarning, match="largest configured"):
        auto.serve(reqs[:1], kvs[:1])           # ladder (32, 64)
    with warnings.catch_warnings():
        warnings.simplefilter("error", ReproPerfWarning)
        auto.serve(reqs[1:2], kvs[1:2])         # same key: latched
    with pytest.warns(ReproPerfWarning, match="largest configured"):
        # multi-rung backlog coalesces the ladder; n=60 re-buckets to
        # the coalesced rung -> a NEW (ladder, rung) key warns again
        auto.serve([reqs[2], big[0]], [kvs[2], bkv[0]])
    assert len(auto.stats()["autoscale"]["ladder"]) == 2
    with warnings.catch_warnings():
        warnings.simplefilter("error", ReproPerfWarning)
        auto.serve([reqs[3], big[1]], [kvs[3], bkv[1]])  # latched anew


def test_plane_rejects_out_of_grant_shards(fixture_round):
    fm, rr = fixture_round
    sess = Session.from_round(_plan(), rr)
    plane = sess.service.plane
    with pytest.raises(ServePlaneError, match="shards"):
        plane._plane_for(plane.n_shards + 1)
    # Stage params whose presence disagrees with the plan (heads and
    # encoder off here) are refused before anything is traced.
    with pytest.raises(ServePlaneError, match="heads"):
        plane.step(sess.service.tau, None, np.zeros((1, 8, D)), None,
                   None, heads=sess.service.tau)


def test_mixed_rung_flush_right_sizes_each_group(fixture_round):
    """A flush spread across several pad rungs must not pad every
    bucket group up to the WHOLE queue's depth: each group's batch
    right-sizes to its own power-of-two rung under the decision's
    ceiling (repeat-padding rows are real compute)."""
    fm, rr = fixture_round
    sess = Session.from_round(_plan(autoscale="latency",
                                    batch_size=64), rr)
    for rung_lo in (10, 40, 100):       # 4 requests in each base rung
        stream = late_device_stream(fm.means, KP, 4, rung_lo,
                                    n_range=(rung_lo, rung_lo + 1))
        for data, _, kv in stream:
            sess.submit(data, kv)
    got = sess.flush()                  # depth 12 -> decision rung 16
    assert len(got) == 12
    assert sess.stats()["autoscale"]["batch_size"] == 16  # the ceiling
    steps = {sig[2][0] for sig in sess.service.plane._signatures
             if sig[0] == "step"}
    assert steps == {4}                 # every group executed at 4


def test_multi_axis_grant_right_sizes_to_one_shard(fixture_round):
    """A multi-axis serve grant has no canonical sub-grant: a
    right-sized bucket group must drop to ONE shard (the shard rule),
    never to an intermediate count the plane rejects mid-flush — and
    labels still match the single-host session bitwise."""
    fm, rr = fixture_round
    shape = (2, NDEV // 2) if NDEV % 2 == 0 else (1, NDEV)
    mesh = make_mesh(shape, ("a", "b"))
    kw = dict(autoscale="latency", batch_size=8)
    shard = Session.from_round(_plan(**kw, serve_axes=("a", "b")), rr,
                               mesh=mesh)
    single = Session.from_round(_plan(**kw), rr)
    reqs, kvs = [], []
    for rung_lo, count in ((10, 6), (40, 2)):   # mixed rungs: the
        stream = late_device_stream(fm.means, KP, count, rung_lo,
                                    n_range=(rung_lo, rung_lo + 1))
        reqs += [r[0] for r in stream]
        kvs += [r[2] for r in stream]
    out_a = single.serve(reqs, kvs)             # 2-request group right-
    out_b = shard.serve(reqs, kvs)              # sizes below the grant
    for la, lb in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
    used = {sig[1] for sig in shard.service.plane._signatures
            if sig[0] == "step"}
    assert used <= {1, NDEV}                    # never an intermediate


def test_restore_reconciles_decision_with_restoring_plan(fixture_round,
                                                         tmp_path):
    """A v3 checkpoint written under one plan restores under another:
    ``off`` serves at the RESTORING plan's static shape (never the
    writer's), and an adaptive controller clamps the batch rung to the
    new ceiling and recomputes shards from the new grant — no stale
    out-of-grant decision can crash the first flush."""
    fm, rr = fixture_round
    writer = Session.from_round(_plan(batch_size=64), rr)   # off, B=64
    reqs, kvs = _requests(fm, 6, seed=21)
    writer.serve(reqs, kvs)
    path = str(tmp_path / "wide.npz")
    writer.save(path)
    narrow = Session.restore(path, _plan(batch_size=8))
    for a, b in zip(writer.serve(reqs, kvs), narrow.serve(reqs, kvs)):
        np.testing.assert_array_equal(a, b)
    st = narrow.stats()["autoscale"]
    assert st["batch_size"] == 8 and st["max_batch"] == 8
    ctl = A.AutoscaleController("latency", max_batch=8, granted=2,
                                n_axes=1, base_ladder=(64,))
    ctl.load_state(np.asarray([8, 64, 5, 1]), np.asarray([64]))
    assert ctl.decision.batch_size == 8     # clamped to the ceiling
    assert ctl.decision.shards == 2         # recomputed from the grant
    assert ctl.decision.seq == 5 and ctl.streak == 1


# --------------------------------------------- checkpoint replay (v3) --


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 4), cut=st.integers(1, 3))
def test_decision_sequence_replays_bitwise_from_checkpoint(seed, cut):
    """Property (satellite acceptance): interrupt an autoscaled stream
    at ANY flush boundary, checkpoint, restore — the replica replays
    the remaining stream with bitwise-identical labels, tau versions,
    fold state, and the SAME decision sequence as the uninterrupted
    run."""
    fm = structured_devices(jax.random.PRNGKey(0), k=K, d=D, k_prime=KP,
                            m0=4, n_per_comp_dev=25, sep=60.0)
    rr = Session(FederationPlan(k=K, k_prime=KP, d=D)).run(
        jax.random.PRNGKey(1), fm.data).detail
    plan = _plan(autoscale="latency", refresh_every=3, refresh="async",
                 fold_policy="lru", capacity=24,
                 bucket_sizes=(32, 64))
    stream = late_device_stream(fm.means, KP, 20, 100 + seed,
                                n_range=(10, 150))
    reqs, kvs = [r[0] for r in stream], [r[2] for r in stream]
    depths = [1, 5, 2, 7, 1, 4]

    live = Session.from_round(plan, rr)
    ref = Session.from_round(plan, rr)
    out_ref = _serve_depths(ref, reqs, kvs, depths)   # uninterrupted

    out_live = _serve_depths(live, reqs, kvs, depths[:cut])
    import tempfile
    import os
    path = os.path.join(tempfile.mkdtemp(), "autoscale_v3.npz")
    live.save(path)
    replica = Session.restore(path, plan)
    served = sum(depths[:cut])
    # clients re-submit the remaining stream to both
    rest = [reqs[i % len(reqs)] for i in range(served, sum(depths))]
    rkvs = [kvs[i % len(kvs)] for i in range(served, sum(depths))]
    out_live += _serve_depths(live, rest, rkvs, depths[cut:])
    out_rep = _serve_depths(replica, rest, rkvs, depths[cut:])
    assert len(out_live) == len(out_ref)
    for (la, va), (lb, vb) in zip(out_live[served:], out_rep):
        np.testing.assert_array_equal(la, lb)
        assert va == vb
    for (la, va), (lb, vb) in zip(out_ref, out_live):
        np.testing.assert_array_equal(la, lb)
        assert va == vb
    for a, b in ((live, replica), (live, ref)):
        assert (a.service.autoscaler.decision
                == b.service.autoscaler.decision)
        assert a.service.autoscaler.streak == b.service.autoscaler.streak
        for x, y in zip(jax.tree.leaves(a.service.state),
                        jax.tree.leaves(b.service.state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_v3_checkpoint_schema_and_mismatch_error(fixture_round,
                                                 tmp_path):
    from repro.checkpoint.store import npz_keys
    fm, rr = fixture_round
    plan = _plan(autoscale="latency")
    sess = Session.from_round(plan, rr)
    reqs, kvs = _requests(fm, 5, seed=11)
    _serve_depths(sess, reqs, kvs, [2, 3])
    path = str(tmp_path / "v3.npz")
    sess.save(path)
    keys = npz_keys(path)
    assert {"autoscale_id", "autoscale_state",
            "autoscale_ladder"} <= keys
    assert "tau_meta" in keys                      # rides NEXT to v2 tau
    replica = Session.restore(path, plan)
    assert (replica.service.autoscaler.decision
            == sess.service.autoscaler.decision)
    with pytest.raises(StreamConfigError, match="autoscale"):
        Session.restore(path, _plan(autoscale="throughput"))


def test_v3_autoscale_checkpoint_restores_under_drift(fixture_round,
                                                      tmp_path):
    """A true v3 archive (autoscale decision state, 4-field pre-drift
    server, no epoch stamps) restores into an autoscaled AND
    drift-enabled v4 plan: the decision state replays bitwise while
    the drift layer starts from defaults, and serving continues with
    the labels the source session produces."""
    from repro.checkpoint.store import save_pytree
    from repro.fed.policy import POLICY_IDS
    from repro.fed.stream import AUTOSCALE_IDS, _ServerStateV3
    fm, rr = fixture_round
    src = Session.from_round(_plan(autoscale="latency"), rr)
    reqs, kvs = _requests(fm, 5, seed=29)
    _serve_depths(src, reqs, kvs, [2, 3])
    svc = src.service
    path = str(tmp_path / "v3_drift.npz")
    save_pytree(path, {
        "tau_bufs": svc._taubuf.bufs,
        "tau_meta": svc._taubuf.meta_array(),
        "server": _ServerStateV3(svc.state.centers, svc.state.mask,
                                 svc.state.weights, svc.state.received),
        "counters": svc._counters(),
        "policy_id": np.asarray(POLICY_IDS["drop"], np.int64),
        "policy": {},
        "autoscale_id": np.asarray(AUTOSCALE_IDS["latency"], np.int64),
        **svc.autoscaler.state_arrays()})
    rep = Session.restore(path, _plan(autoscale="latency", drift="decay",
                                      drift_half_life=64))
    assert rep.service.autoscaler.decision == svc.autoscaler.decision
    dstats = rep.stats()["drift"]
    assert dstats["mode"] == "decay" and dstats["events"] == 0
    more, mkv = _requests(fm, 4, seed=31)
    for a, b in zip(src.serve(more, mkv), rep.serve(more, mkv)):
        np.testing.assert_array_equal(a, b)


def test_v1_and_v2_checkpoints_restore_under_autoscale(fixture_round,
                                                       tmp_path):
    """Pre-v3 checkpoints (no autoscale arrays) restore into an
    autoscaled plan with a fresh static decision — and pre-v2 (single
    tau) still restore too."""
    from repro.checkpoint.store import save_pytree
    from repro.fed.policy import POLICY_IDS
    fm, rr = fixture_round
    sess = Session.from_round(_plan(), rr)
    reqs, kvs = _requests(fm, 4, seed=13)
    _serve_depths(sess, reqs, kvs, [4])
    svc = sess.service
    plan = _plan(autoscale="latency")
    common = {"server": svc.state, "counters": svc._counters(),
              "policy_id": np.asarray(POLICY_IDS["drop"], np.int64),
              "policy": {}}
    v2 = str(tmp_path / "v2.npz")
    save_pytree(v2, {"tau_bufs": svc._taubuf.bufs,
                     "tau_meta": svc._taubuf.meta_array(), **common})
    v1 = str(tmp_path / "v1.npz")
    save_pytree(v1, {"tau": svc.tau, **common})
    more, mkv = _requests(fm, 6, seed=17)
    want = sess.serve(more, mkv)
    for path in (v2, v1):
        replica = Session.restore(path, plan)
        assert replica.service.autoscaler.decision.seq == 0
        np.testing.assert_array_equal(np.asarray(replica.tau_centers),
                                      np.asarray(sess.tau_centers))
        for a, b in zip(want, replica.serve(more, mkv)):
            np.testing.assert_array_equal(a, b)
