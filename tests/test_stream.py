"""Streaming attachment through the Session lifecycle (fed/api.py over
fed/stream.py, DESIGN.md §9–§10): batched Theorem 3.2 serving,
consistency with the full round, incremental folding + refresh, and
checkpointed crash recovery."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, Session
from repro.utils.metrics import clustering_accuracy

K, KP, D = 16, 4, 24


@pytest.fixture(scope="module")
def fixture_round():
    fm = structured_devices(jax.random.PRNGKey(0), k=K, d=D, k_prime=KP,
                            m0=4, n_per_comp_dev=25, sep=60.0)
    rr = Session(FederationPlan(k=K, k_prime=KP, d=D)).run(
        jax.random.PRNGKey(1), fm.data).detail
    return fm, rr


def _plan(**kw):
    base = dict(k=K, k_prime=KP, d=D, capacity=256, batch_size=4,
                bucket_sizes=(32, 64, 128))
    base.update(kw)
    return FederationPlan(**base)


def _session(rr, **kw) -> Session:
    """A serving session over the module fixture's finished round."""
    return Session.from_round(_plan(**kw), rr)


def _requests(fm, count, seed, n_lo=10, n_hi=120):
    """Heterogeneous (n, k') late devices from the round's mixture."""
    stream = late_device_stream(fm.means, KP, count, seed,
                                n_range=(n_lo, n_hi))
    return ([r[0] for r in stream], [r[1] for r in stream],
            [r[2] for r in stream])


def test_service_serves_heterogeneous_requests(fixture_round):
    """Mixed (n, k') requests land in the right clusters; reports fold."""
    fm, rr = fixture_round
    sess = _session(rr)
    reqs, truths, kvs = _requests(fm, 9, seed=3)
    labels = sess.serve(reqs, kvs)
    for lbl, truth, req in zip(labels, truths, reqs):
        assert lbl.shape == (req.shape[0],)
        assert clustering_accuracy(lbl, truth, K) > 0.97
    st = sess.stats()
    Z = fm.data.shape[0]
    assert st["served_devices"] == 9
    assert st["served_points"] == sum(r.shape[0] for r in reqs)
    assert st["folded"] == Z + 9  # round reports + streamed reports


def test_participating_device_attach_matches_round(fixture_round):
    """Theorem 3.2 consistency: a device that DID participate gets the
    same point labels from the serving attach path as the full round's
    induced labeling gave it."""
    fm, rr = fixture_round
    Z = fm.data.shape[0]
    # The round's per-device local-solve keys (fed.engine.local_stage).
    keys = jax.random.split(jax.random.PRNGKey(1), Z)
    attach = Session.from_tau(_plan(), rr.agg.tau_centers).attach_fn()
    for z in [0, 5, Z - 1]:
        pts = attach(keys[z], fm.data[z])
        np.testing.assert_array_equal(np.asarray(pts),
                                      np.asarray(rr.labels[z]))


def test_batched_service_matches_round_labels(fixture_round):
    """The batched service path agrees with the round's induced labels
    when fed participating devices' own data (fresh local solves —
    label agreement, the Theorem 3.2 guarantee on separated data)."""
    fm, rr = fixture_round
    sess = _session(rr, bucket_sizes=(128,))
    zs = [1, 4, 7, 10]
    labels = sess.serve([np.asarray(fm.data[z]) for z in zs])
    for lbl, z in zip(labels, zs):
        np.testing.assert_array_equal(lbl, np.asarray(rr.labels[z]))


def test_batched_vs_one_at_a_time_bitwise(fixture_round):
    """Serving a batch of B requests is bitwise identical to serving
    them one at a time: request PRNG streams are keyed by request id,
    never by batch composition."""
    fm, rr = fixture_round
    reqs, _, kvs = _requests(fm, 7, seed=5)
    batched = _session(rr, batch_size=4)
    single = _session(rr, batch_size=1)
    out_b = batched.serve(reqs, kvs)
    out_s = single.serve(reqs, kvs)
    for a, b in zip(out_b, out_s):
        np.testing.assert_array_equal(a, b)
    # The folded server states agree bitwise too.
    for la, lb in zip(jax.tree.leaves(batched.service.state),
                      jax.tree.leaves(single.service.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_checkpoint_restore_serve_bitwise(fixture_round, tmp_path):
    """Crash recovery: checkpoint mid-stream, restore, serve the rest —
    bitwise identical labels AND fold state vs the uninterrupted
    session (acceptance criterion)."""
    fm, rr = fixture_round
    live = _session(rr, refresh_every=6)  # cross a refresh mid-stream
    reqs, _, kvs = _requests(fm, 10, seed=9)
    live.serve(reqs[:5], kvs[:5])
    path = str(tmp_path / "attach_ck.npz")
    live.save(path)
    restored = Session.restore(path, live.plan)
    np.testing.assert_array_equal(np.asarray(live.tau_centers),
                                  np.asarray(restored.tau_centers))
    out_live = live.serve(reqs[5:], kvs[5:])
    out_rest = restored.serve(reqs[5:], kvs[5:])
    for a, b in zip(out_live, out_rest):
        np.testing.assert_array_equal(a, b)
    for la, lb in zip(jax.tree.leaves(live.service.state),
                      jax.tree.leaves(restored.service.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert restored.stats()["served_devices"] == 10  # 5 restored + 5 new


def test_pre_v4_checkpoints_restore_into_drift_enabled_plan(
        fixture_round, tmp_path):
    """Schema-migration matrix (DESIGN.md §14): v1 (single tau), v2
    (double-buffered tau) and v3 (+autoscale) archives — all carrying
    the 4-field pre-drift server state, no epoch stamps — restore into
    a drift-enabled v4 plan with drift state default-initialized (zero
    epochs, zero split/retire counters, zero mass) and serve bitwise
    what a drift=off restore of the same archive serves (drift is
    strictly additive). A v4 archive refuses a drift-mode mismatch
    with a named config error."""
    from repro.checkpoint.store import npz_keys, save_pytree
    from repro.fed.policy import POLICY_IDS
    from repro.fed.stream import (AUTOSCALE_IDS, StreamConfigError,
                                  _ServerStateV3)
    fm, rr = fixture_round
    base = _session(rr)
    reqs, _, kvs = _requests(fm, 10, seed=19)
    base.serve(reqs[:4], kvs[:4])
    svc = base.service
    old_srv = _ServerStateV3(svc.state.centers, svc.state.mask,
                             svc.state.weights, svc.state.received)
    common = {"server": old_srv, "counters": svc._counters(),
              "policy_id": np.asarray(POLICY_IDS["drop"], np.int64),
              "policy": {}}
    bufs = {"tau_bufs": svc._taubuf.bufs,
            "tau_meta": svc._taubuf.meta_array()}
    v1 = str(tmp_path / "v1.npz")
    save_pytree(v1, {"tau": svc.tau, **common})
    v2 = str(tmp_path / "v2.npz")
    save_pytree(v2, {**bufs, **common})
    v3 = str(tmp_path / "v3.npz")
    save_pytree(v3, {**bufs, **common,
                     "autoscale_id": np.asarray(AUTOSCALE_IDS["off"],
                                                np.int64),
                     **svc.autoscaler.state_arrays()})
    drift_kw = dict(drift="split_merge", drift_half_life=512,
                    drift_retire_frac=0.2)
    restored = None
    for path in (v1, v2, v3):
        assert "server/.epoch" not in npz_keys(path)   # truly pre-v4
        restored = Session.restore(path, _plan(**drift_kw))
        plain = Session.restore(path, _plan())
        d = restored.service
        assert (d._drift_events, d._drift_moves, d._drift_last) \
            == (0, 0, 0)
        np.testing.assert_array_equal(d._drift_mass,
                                      np.zeros((K,), np.float32))
        np.testing.assert_array_equal(np.asarray(d.state.epoch),
                                      np.zeros((256,), np.int32))
        np.testing.assert_array_equal(np.asarray(restored.tau_centers),
                                      np.asarray(base.tau_centers))
        out_d = restored.serve(reqs[4:], kvs[4:])
        out_p = plain.serve(reqs[4:], kvs[4:])
        for a, b in zip(out_d, out_p):
            np.testing.assert_array_equal(a, b)
        assert restored.stats()["drift"]["mode"] == "split_merge"
    v4 = str(tmp_path / "v4.npz")
    restored.save(v4)
    assert {"drift_id", "drift_state", "drift_mass",
            "server/.epoch"} <= npz_keys(v4)
    with pytest.raises(StreamConfigError, match="drift"):
        Session.restore(v4, _plan())


def test_refresh_refolds_round_plus_stream(fixture_round):
    """The refresh cadence re-finalizes Algorithm 2 over round + stream
    reports; serving quality holds across the tau swap."""
    fm, rr = fixture_round
    sess = _session(rr, refresh_every=3)
    reqs, truths, kvs = _requests(fm, 8, seed=13)
    labels = sess.serve(reqs, kvs)
    for lbl, truth in zip(labels, truths):
        assert clustering_accuracy(lbl, truth, K) > 0.97
    st = sess.stats()
    assert st["since_refresh"] < 3  # cadence fired
    assert np.all(np.isfinite(np.asarray(sess.tau_centers)))
    # An explicit refresh equals finalize over the current fold state.
    from repro.core import server as S
    agg = S.finalize(sess.service.state, K)
    sess.refresh()
    np.testing.assert_array_equal(np.asarray(sess.tau_centers),
                                  np.asarray(agg.tau_centers))


def test_capacity_overflow_serves_without_folding(fixture_round):
    """Requests past the fold capacity are still served (Theorem 3.2
    needs no state), just not folded (the drop admission policy)."""
    fm, rr = fixture_round
    Z = fm.data.shape[0]
    sess = _session(rr, capacity=Z + 2)
    reqs, truths, kvs = _requests(fm, 5, seed=17)
    labels = sess.serve(reqs, kvs)
    for lbl, truth in zip(labels, truths):
        assert clustering_accuracy(lbl, truth, K) > 0.97
    assert sess.stats()["folded"] == Z + 2


def test_submit_interleaved_with_serve_not_lost(fixture_round):
    """serve() must not swallow the results of requests that were
    already pending from submit(): they stay queued for the next
    flush()."""
    fm, rr = fixture_round
    sess = _session(rr)
    reqs, truths, kvs = _requests(fm, 2, seed=21)
    rid0 = sess.submit(reqs[0], kvs[0])
    sess.serve([reqs[1]], [kvs[1]])  # flushes rid0 too, must not drop it
    assert sess.stats()["undelivered"] == 1
    got = sess.flush()
    assert set(got) == {rid0}
    assert clustering_accuracy(got[rid0], truths[0], K) > 0.97


def test_flush_failure_requeues_and_keeps_results(fixture_round,
                                                  monkeypatch):
    """A batch failure mid-flush must not lose work: computed results
    stay in the undelivered buffer, unserved requests requeue."""
    fm, rr = fixture_round
    sess = _session(rr, batch_size=1)
    reqs, truths, kvs = _requests(fm, 2, seed=23, n_lo=10, n_hi=20)
    for r, kv in zip(reqs, kvs):
        sess.submit(r, kv)
    svc = sess.service
    orig, calls = svc._serve_batch, []

    def boom(batch, n_pad, out, decision):
        if calls:
            raise RuntimeError("boom")
        calls.append(1)
        orig(batch, n_pad, out, decision)

    monkeypatch.setattr(svc, "_serve_batch", boom)
    with pytest.raises(RuntimeError):
        sess.flush()
    st = sess.stats()
    assert st["pending"] == 1 and st["undelivered"] == 1
    monkeypatch.setattr(svc, "_serve_batch", orig)
    got = sess.flush()  # retry serves the requeued request, delivers both
    assert len(got) == 2
    for lbl, truth in zip(got.values(), truths):
        assert lbl.shape[0] == truth.shape[0]


def test_fold_drops_out_of_range_ids():
    """aggregate_incremental must DROP an over-capacity device id, not
    clip it onto (and corrupt) the last slot."""
    from repro.core import server as S
    st = S.init_state(4, 2, 3)
    good = jnp.ones((1, 2, 3))
    st = S.aggregate_incremental(st, jnp.asarray([3]), good,
                                 jnp.ones((1, 2), bool))
    bad = jnp.full((1, 2, 3), 7.0)
    st = S.aggregate_incremental(st, jnp.asarray([4]), bad,
                                 jnp.ones((1, 2), bool))
    np.testing.assert_array_equal(np.asarray(st.centers[3]),
                                  np.ones((2, 3)))
    np.testing.assert_array_equal(np.asarray(st.received),
                                  [False, False, False, True])


# ------------------------------------------------ host staging rings --

def _fresh_fill(batch, B, n_pad, s_pad, d, k_prime):
    """A batch's host inputs built from scratch: ``np.zeros``, each
    request copied to the front of its row, rows past the batch
    repeating its last request."""
    data = np.zeros((B, n_pad, s_pad, d) if s_pad else (B, n_pad, d),
                    np.float32)
    tmask = np.zeros((B, n_pad, s_pad), bool) if s_pad else None
    pmask = np.zeros((B, n_pad), bool)
    kv = np.full((B,), k_prime, np.int32)
    rids = np.zeros((B,), np.int64)
    for i in range(B):
        rid, arr, k_valid = batch[min(i, len(batch) - 1)]
        n = arr.shape[0]
        if s_pad:
            data[i, :n, :arr.shape[1]] = arr
            tmask[i, :n, :arr.shape[1]] = True
        else:
            data[i, :n] = arr
        pmask[i, :n] = True
        kv[i] = k_valid
        rids[i] = rid
    return data, tmask, pmask, kv, rids


def _spy_staging(svc, monkeypatch):
    """Record, after every prep, the batch and a copy of its staging
    arrays."""
    seen, orig = [], svc._stage

    def spy(batch, B, n_pad, s_pad):
        st = orig(batch, B, n_pad, s_pad)
        seen.append((list(batch), B, n_pad, s_pad,
                     [None if x is None else x.copy() for x in (
                         st.data, st.tmask, st.pmask, st.kv, st.rids)]))
        return st

    monkeypatch.setattr(svc, "_stage", spy)
    return seen


@pytest.mark.parametrize("encoded", [False, True])
def test_staging_fill_is_bitwise_a_fresh_fill(fixture_round, encoded,
                                              monkeypatch):
    """Six batches of one shape, so each of the two ring slots is
    written long -> short -> long (points and, encoded, tokens), some
    batches short of rows and padded by repeat: after every prep the
    reused arrays equal a fresh ``np.zeros`` fill byte for byte."""
    rng = np.random.default_rng(31)
    if encoded:
        d, kp, seq = 16, 3, 16
        sess = Session.from_tau(FederationPlan(
            k=8, k_prime=kp, d=d, capacity=128, batch_size=4,
            bucket_sizes=(16, 32), encoder="qwen1.5-0.5b",
            encode_seq_len=seq), rng.normal(size=(8, d)).astype(np.float32))
        # (n, seq) extents inside the one (32, 16) rung.
        long_, short = ((30, 32), (15, 16)), ((17, 19), (9, 11))
    else:
        _, rr = fixture_round
        d, kp = D, KP
        sess = _session(rr)
        long_, short = ((58, 64),), ((33, 36),)
    seen = _spy_staging(sess.service, monkeypatch)

    def request(ext):
        shape = [int(rng.integers(lo, hi + 1)) for lo, hi in ext]
        return rng.normal(size=shape + [d]).astype(np.float32)

    for ext, count in ((long_, 4), (long_, 3), (short, 1), (short, 2),
                       (long_, 3), (long_, 4)):
        sess.serve([request(ext) for _ in range(count)],
                   [int(rng.integers(1, kp + 1)) for _ in range(count)])
    assert len(seen) == 6
    assert len({(B, n_pad, s_pad) for _, B, n_pad, s_pad, _ in seen}) == 1
    for batch, B, n_pad, s_pad, got in seen:
        want = _fresh_fill(batch, B, n_pad, s_pad, d, kp)
        for g, w in zip(got, want):
            if w is None:
                assert g is None
                continue
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.tobytes() == w.tobytes()
    f = sess.stats()["flush"]
    assert (f["staging_allocs"], f["staging_reuses"]) == (2, 5)


class _Running:
    """A step's labels as a device still running would hold them: not
    ready until something blocks on them."""

    def __init__(self, labels):
        self.labels, self.done = labels, False

    def is_ready(self):
        return self.done

    def block_until_ready(self):
        self.done = True
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.labels, dtype)


def test_ring_wrap_within_a_flush_keeps_labels(fixture_round,
                                               monkeypatch):
    """One flush of four same-shape batches reuses each ring slot
    before any label reaches the host. Each step's labels stay "running"
    until waited on: no slot is written while the step that read it
    runs, each reuse waits once, and every request gets the labels it
    gets served alone."""
    from repro.fed import stream
    fm, rr = fixture_round
    reqs, _, kvs = _requests(fm, 8, seed=41, n_lo=33, n_hi=64)
    sess = _session(rr, batch_size=2)
    plane, running, last = sess.service.plane, [], {}
    step, write = plane.step, stream._Staging.write

    def lazy_step(*a, **kw):
        out = step(*a, **kw)
        running.append(_Running(out[0]))
        last[last.pop("writing")] = running[-1]
        return (running[-1],) + tuple(out[1:])

    def checked_write(st, batch):
        assert last.get(id(st)) is None or last[id(st)].done
        last["writing"] = id(st)
        write(st, batch)

    monkeypatch.setattr(plane, "step", lazy_step)
    monkeypatch.setattr(stream._Staging, "write", checked_write)
    got = sess.serve(reqs, kvs)
    f = sess.stats()["flush"]
    assert (f["batches"], f["staging_allocs"], f["staging_reuses"],
            f["staging_waits"]) == (4, 2, 3, 2)
    assert [r.done for r in running] == [True, True, False, False]
    monkeypatch.undo()
    alone = _session(rr, batch_size=2)
    for r, kv, lbl in zip(reqs, kvs, got):
        np.testing.assert_array_equal(alone.serve([r], [kv])[0], lbl)


def test_failed_batch_releases_its_staging_slot(fixture_round,
                                                monkeypatch):
    """A step that fails after its batch was staged requeues the batch
    and leaves the ring usable: the retry serves every request the
    labels of a clean session, through the same two slots."""
    fm, rr = fixture_round
    reqs, _, kvs = _requests(fm, 3, seed=23, n_lo=10, n_hi=20)
    clean = _session(rr, batch_size=1).serve(reqs, kvs)
    sess = _session(rr, batch_size=1)
    for r, kv in zip(reqs, kvs):
        sess.submit(r, kv)
    plane, calls = sess.service.plane, []
    orig = plane.step

    def boom(*a, **kw):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("boom")
        return orig(*a, **kw)

    monkeypatch.setattr(plane, "step", boom)
    with pytest.raises(RuntimeError):
        sess.flush()
    st = sess.stats()
    assert st["pending"] == 2 and st["undelivered"] == 1
    monkeypatch.setattr(plane, "step", orig)
    got = sess.flush()
    assert len(got) == 3
    for rid, want in zip(sorted(got), clean):
        np.testing.assert_array_equal(got[rid], want)
    f = sess.stats()["flush"]
    assert (f["staging_allocs"], f["staging_reuses"]) == (2, 3)
