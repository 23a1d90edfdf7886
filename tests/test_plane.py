"""The sharded streaming serve plane + double-buffered versioned tau
(fed/plane.py over fed/stream.py, DESIGN.md §11).

Covers the refresh-vs-serve consistency window: every served label maps
to exactly one tau version, pre-swap requests read the old buffer and
post-swap the new, and a checkpoint restored mid-window replays the
same version assignments bitwise. The mesh tests build over whatever
devices exist — run them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI mesh
leg) for real sharding; on one device the sharded plane degenerates to
the single-host plane and the parity assertions still pin it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import server as S
from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, PlanError, Session
from repro.fed.plane import TauBuffer
from repro.fed.policy import make_policy
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


def _mesh():
    return make_mesh((NDEV,), ("data",))


def _plan(**kw):
    base = dict(k=K, k_prime=KP, d=D, capacity=256,
                batch_size=2 * NDEV, bucket_sizes=(32, 64, 128))
    base.update(kw)
    return FederationPlan(**base)


def _requests(fm, count, seed, n_hi=120):
    stream = late_device_stream(fm.means, KP, count, seed,
                                n_range=(10, n_hi))
    return ([r[0] for r in stream], [r[1] for r in stream],
            [r[2] for r in stream])


# ----------------------------------------------------- sharded plane --


def test_sharded_serve_bitwise_matches_single_host(fixture_round):
    """Fixed tau version: per-request labels AND the folded server
    state of the sharded plane are bitwise identical to the single-host
    plane (acceptance criterion)."""
    fm, rr = fixture_round
    reqs, _, kvs = _requests(fm, 3 * NDEV + 1, seed=3)
    single = Session.from_round(_plan(), rr)
    shard = Session.from_round(_plan(serve_axes=("data",)), rr,
                               mesh=_mesh())
    out_a = single.serve_versioned(reqs, kvs)
    out_b = shard.serve_versioned(reqs, kvs)
    for (la, va), (lb, vb) in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
        assert va == vb == 0
    for x, y in zip(jax.tree.leaves(single.service.state),
                    jax.tree.leaves(shard.service.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert shard.service.stats()["serve_shards"] == NDEV


@pytest.mark.parametrize("policy", ["lru", "weighted_reservoir"])
def test_sharded_fold_policies_match_single_host(fixture_round, policy):
    """Admission is shard-deterministic: under lru/weighted_reservoir
    the sharded plane folds exactly the same slots as the single-host
    plane (policy state AND server state bitwise)."""
    fm, rr = fixture_round
    kw = dict(capacity=8, fold_policy=policy)
    reqs, _, kvs = _requests(fm, 2 * NDEV + 3, seed=7)
    single = Session.from_round(_plan(**kw), rr)
    shard = Session.from_round(_plan(**kw, serve_axes=("data",)), rr,
                               mesh=_mesh())
    for sess in (single, shard):
        sess.serve(reqs, kvs)
    pa = single.service.policy.state_arrays()
    pb = shard.service.policy.state_arrays()
    assert sorted(pa) == sorted(pb)
    for name in pa:
        np.testing.assert_array_equal(pa[name], pb[name])
    for x, y in zip(jax.tree.leaves(single.service.state),
                    jax.tree.leaves(shard.service.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_session_topology_parity_direct(fixture_round):
    """Replicated/sharded shard_map rounds agree bitwise with the vmap
    simulation, directly on this process's devices (the CI mesh leg
    runs this at 8 forced host devices; tier-1 subprocess children
    cover it too)."""
    fm, _ = fixture_round
    Z = fm.data.shape[0]
    if Z % NDEV:
        pytest.skip(f"{Z} devices not divisible over {NDEV} shards")
    sim = Session(FederationPlan(k=K, k_prime=KP, d=D)).run(
        jax.random.PRNGKey(1), fm.data)
    mesh = _mesh()
    for topology in ("replicated", "sharded"):
        out = Session(FederationPlan(k=K, k_prime=KP, d=D,
                                     topology=topology), mesh=mesh).run(
            jax.random.PRNGKey(1), fm.data)
        np.testing.assert_array_equal(np.asarray(out.labels),
                                      np.asarray(sim.labels))


def test_aggregate_incremental_sharded_matches_sequential():
    """The collective fold path == the sequential fold primitive,
    bitwise, for a batch sharded over this process's devices."""
    from jax.sharding import PartitionSpec as P
    from repro.utils.compat import shard_map
    kp, d = 3, 5
    B = 4 * NDEV
    cap = B  # distinct ids, some past capacity (exercises the drop)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.permutation(B + 4)[:B], jnp.int32)
    centers = jnp.asarray(rng.normal(size=(B, kp, d)), jnp.float32)
    mask = jnp.asarray(rng.random((B, kp)) < 0.8)
    w = jnp.asarray(rng.random((B, kp)), jnp.float32)
    st0 = S.init_state(cap, kp, d)
    seq = S.aggregate_incremental(st0, ids, centers, mask, weights=w)
    mesh = _mesh()
    spec = P(("data",))
    fn = shard_map(
        lambda st, i, c, m, wt: S.aggregate_incremental_sharded(
            st, i, c, m, ("data",), weights=wt),
        mesh=mesh, in_specs=(P(), spec, spec, spec, spec),
        out_specs=P())
    got = fn(st0, ids, centers, mask, w)
    for a, b in zip(jax.tree.leaves(seq), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_axes_validation():
    with pytest.raises(PlanError, match="serve_axes"):
        FederationPlan(k=K, k_prime=KP, d=D, serve_axes=())
    with pytest.raises(PlanError, match="mesh"):
        Session(FederationPlan(k=K, k_prime=KP, d=D,
                               serve_axes=("data",)))
    with pytest.raises(PlanError, match="not in the mesh"):
        Session(FederationPlan(k=K, k_prime=KP, d=D,
                               serve_axes=("model",)), mesh=_mesh())
    if NDEV > 1:
        with pytest.raises(PlanError, match="divisible"):
            Session(FederationPlan(k=K, k_prime=KP, d=D, batch_size=1,
                                   serve_axes=("data",)), mesh=_mesh())


# ------------------------------------------- versioned tau / refresh --


def test_every_label_maps_to_exactly_one_version(fixture_round):
    """Sync refresh: versions are recorded per request, bump exactly
    once per swap, and pre-swap requests used the old buffer while
    post-swap requests use the new (satellite acceptance)."""
    fm, rr = fixture_round
    sess = Session.from_round(_plan(batch_size=2, refresh_every=2,
                                    bucket_sizes=(128,)), rr)
    reqs, _, kvs = _requests(fm, 6, seed=5)
    tau0 = np.asarray(sess.tau_centers)
    out = sess.serve_versioned(reqs, kvs)
    versions = [v for _, v in out]
    # batch 1 (2 folds) served at v0, then swap; batch 2 at v1; etc.
    assert versions == [0, 0, 1, 1, 2, 2]
    assert sess.tau_version == 3
    assert not np.array_equal(tau0, np.asarray(sess.tau_centers))


def test_async_refresh_defers_swap_to_flush_boundary(fixture_round):
    """Async refresh: the cadence mid-flush stages the standby buffer
    without touching in-flight serving (old version throughout), and
    the next flush commits ONE atomic version bump."""
    fm, rr = fixture_round
    sess = Session.from_round(_plan(batch_size=2, refresh_every=2,
                                    refresh="async",
                                    bucket_sizes=(128,)), rr)
    reqs, _, kvs = _requests(fm, 6, seed=9)
    out1 = sess.serve_versioned(reqs, kvs)
    assert [v for _, v in out1] == [0] * 6  # swap never lands mid-flush
    st = sess.stats()
    assert st["refresh_pending"] and st["tau_version"] == 0
    out2 = sess.serve_versioned(reqs[:2], kvs[:2])
    assert [v for _, v in out2] == [1, 1]   # committed at the boundary
    assert sess.tau_version == 1


def test_async_swap_serves_against_standby_content(fixture_round):
    """The committed buffer really is the staged re-finalization: after
    the boundary swap, serving tau equals finalize() over the fold
    state at staging time."""
    fm, rr = fixture_round
    sess = Session.from_round(_plan(batch_size=2, refresh_every=64,
                                    refresh="async",
                                    bucket_sizes=(128,)), rr)
    reqs, _, kvs = _requests(fm, 2, seed=11)
    sess.serve(reqs, kvs)
    svc = sess.service
    svc._stage_refresh()
    want = S.finalize(svc.state, K).tau_centers
    np.testing.assert_array_equal(
        np.asarray(svc._taubuf.standby), np.asarray(want))
    old = np.asarray(sess.tau_centers)
    assert not np.array_equal(old, np.asarray(want))
    sess.serve(reqs, kvs)  # boundary: commit
    np.testing.assert_array_equal(np.asarray(sess.tau_centers),
                                  np.asarray(want))
    assert sess.tau_version == 1


def test_checkpoint_restore_mid_window_replays_versions_bitwise(
        fixture_round, tmp_path):
    """Crash recovery inside a refresh window: the staged standby
    buffer, the pending flag, and the version counter all ride the
    checkpoint, so the replica replays the SAME labels and the SAME
    version assignments (satellite acceptance)."""
    fm, rr = fixture_round
    plan = _plan(batch_size=2, refresh_every=2, refresh="async",
                 bucket_sizes=(128,))
    live = Session.from_round(plan, rr)
    reqs, _, kvs = _requests(fm, 8, seed=13)
    live.serve(reqs[:4], kvs[:4])           # cadence fired: mid-window
    assert live.stats()["refresh_pending"]
    path = str(tmp_path / "midwindow.npz")
    live.save(path)
    replica = Session.restore(path, plan)
    assert replica.stats()["refresh_pending"]
    out_live = live.serve_versioned(reqs[4:], kvs[4:])
    out_rep = replica.serve_versioned(reqs[4:], kvs[4:])
    for (la, va), (lb, vb) in zip(out_live, out_rep):
        np.testing.assert_array_equal(la, lb)
        assert va == vb
    np.testing.assert_array_equal(
        np.asarray(live.service._taubuf.bufs),
        np.asarray(replica.service._taubuf.bufs))
    assert (live.service._taubuf.version
            == replica.service._taubuf.version)


def test_legacy_v1_checkpoint_still_restores(fixture_round, tmp_path):
    """A pre-plane checkpoint (single ``tau`` key) restores as version
    0 with both buffers equal — old checkpoints keep replaying."""
    from repro.checkpoint.store import save_pytree
    from repro.fed.policy import POLICY_IDS
    fm, rr = fixture_round
    sess = Session.from_round(_plan(), rr)
    reqs, _, kvs = _requests(fm, 2, seed=17)
    sess.serve(reqs, kvs)
    svc = sess.service
    path = str(tmp_path / "v1.npz")
    save_pytree(path, {"tau": svc.tau, "server": svc.state,
                       "counters": svc._counters(),
                       "policy_id": np.asarray(POLICY_IDS["drop"],
                                               np.int64),
                       "policy": {}})
    replica = Session.restore(path, sess.plan)
    np.testing.assert_array_equal(np.asarray(replica.tau_centers),
                                  np.asarray(sess.tau_centers))
    assert replica.tau_version == 0
    more, _, mkv = _requests(fm, 3, seed=19)
    for a, b in zip(sess.serve(more, mkv), replica.serve(more, mkv)):
        np.testing.assert_array_equal(a, b)


def test_tau_buffer_transitions():
    buf = TauBuffer.fresh(np.zeros((2, 3), np.float32))
    assert (buf.active, buf.version, buf.pending) == (0, 0, False)
    staged = buf.stage(np.ones((2, 3), np.float32))
    assert staged.pending and staged.version == 0
    np.testing.assert_array_equal(np.asarray(staged.tau),
                                  np.zeros((2, 3)))  # serving untouched
    np.testing.assert_array_equal(np.asarray(staged.standby),
                                  np.ones((2, 3)))
    done = staged.commit()
    assert (done.active, done.version, done.pending) == (1, 1, False)
    np.testing.assert_array_equal(np.asarray(done.tau), np.ones((2, 3)))
    rt = TauBuffer.from_arrays(np.asarray(done.bufs), done.meta_array())
    assert (rt.active, rt.version, rt.pending) == (1, 1, False)


# ------------------------------------------------- bucket ladder -----


def test_oversized_bucket_geometric_ladder_and_warn_per_rung(
        fixture_round):
    """Requests above the largest bucket pad to a geometric (doubling)
    ladder — O(log) distinct jit shapes instead of one per rounded-up
    n — and warn once per (active ladder, rung) under the NAMED perf
    category (``ReproPerfWarning``) so filterwarnings can target it.
    Each new oversized pad shape is visible exactly once and repeats
    are silent — the old once-per-service latch hid every rung after
    the first (bugfix, see also tests/test_autoscale.py for the
    post-coalesce ladder half of the key)."""
    from repro.fed.stream import ReproPerfWarning
    fm, rr = fixture_round
    sess = Session.from_round(_plan(bucket_sizes=(32, 64)), rr)
    svc = sess.service
    assert svc._bucket(10) == 32 and svc._bucket(64) == 64
    with pytest.warns(ReproPerfWarning, match="largest configured bucket"):
        assert svc._bucket(65) == 128
    with pytest.warns(ReproPerfWarning, match="largest configured bucket"):
        assert svc._bucket(129) == 256
    # distinct oversized n values share pads -> shared jit signatures,
    # and an already-warned (ladder, rung) key stays silent
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error", ReproPerfWarning)
        assert svc._bucket(66) == 128
        assert svc._bucket(200) == svc._bucket(256) == 256
    with pytest.warns(ReproPerfWarning, match="largest configured bucket"):
        assert svc._bucket(3000) == 4096


# --------------------------------------------- tier-1 mesh child -----


PLANE_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np

from repro.utils.compat import make_mesh
from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, Session

mesh = make_mesh((8,), ("data",))
fm = structured_devices(jax.random.PRNGKey(0), k=16, d=24, k_prime=4,
                        m0=4, n_per_comp_dev=25, sep=60.0)
rr = Session(FederationPlan(k=16, k_prime=4, d=24)).run(
    jax.random.PRNGKey(1), fm.data).detail
base = dict(k=16, k_prime=4, d=24, capacity=256, batch_size=8,
            bucket_sizes=(32, 64, 128), refresh_every=5, refresh="async")
stream = late_device_stream(fm.means, 4, 13, 5, n_range=(10, 120))
reqs, kvs = [r[0] for r in stream], [r[2] for r in stream]
single = Session.from_round(FederationPlan(**base), rr)
shard = Session.from_round(FederationPlan(**base, serve_axes=("data",)),
                           rr, mesh=mesh)
for sess in (single, shard):
    out1 = sess.serve_versioned(reqs, kvs)
    out2 = sess.serve_versioned(reqs[:4], kvs[:4])
    sess.result = out1 + out2
for (la, va), (lb, vb) in zip(single.result, shard.result):
    np.testing.assert_array_equal(la, lb)
    assert va == vb, (va, vb)
for x, y in zip(jax.tree.leaves(single.service.state),
                jax.tree.leaves(shard.service.state)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
assert shard.service.stats()["serve_shards"] == 8
assert shard.tau_version == single.tau_version >= 1
print("OK sharded plane parity")
"""


@pytest.mark.slow
def test_sharded_plane_parity_subprocess():
    """8-shard serve plane == single-host, bitwise (labels, versions,
    fold state), across an async refresh window — with REAL sharding
    (8 forced host devices, hence the subprocess; acceptance
    criterion)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", PLANE_CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK sharded plane parity" in out.stdout


# ------------------------------------------------- admission batch ---


def test_admit_batch_equals_sequential_admits():
    """FoldPolicy.admit_batch == the sequential admit loop with
    within-batch evictions suppressed (last write wins), for every
    policy — the contract that makes one batched scatter equal
    sequential folding."""
    rng = np.random.default_rng(0)
    for policy in ("drop", "lru", "weighted_reservoir"):
        for trial in range(5):
            cap = int(rng.integers(1, 8))
            rids = rng.integers(0, 3 * cap, size=int(rng.integers(1, 20)))
            w = rng.uniform(0.1, 5.0, size=len(rids))
            a = make_policy(policy, cap, seed=3)
            b = make_policy(policy, cap, seed=3)
            got, granted = a.admit_batch(rids, w)
            slot_of, want_granted = {}, 0
            for i, rid in enumerate(rids):
                s = b.admit(int(rid), float(w[i]))
                if s is not None:
                    slot_of[s] = i
                    want_granted += 1
            want = np.full((len(rids),), -1, np.int64)
            for s, i in slot_of.items():
                want[i] = s
            np.testing.assert_array_equal(got, want)
            assert granted == want_granted  # cadence counts grants


# --------------------------------- encoder=off bitwise parity (§17) ---


@pytest.mark.parametrize("seed", [23, 29, 31])
def test_encoder_off_plan_is_bitwise_inert(fixture_round, seed):
    """§17 acceptance: ``encoder="off"`` plans replay the existing
    serve/fold path bitwise — the encode fields are inert (even
    non-default ``encode_dtype``/``encode_seq_len``), no encode planes
    are compiled, and labels, versions, tau buffers, and every fold
    state leaf match the pre-§17 default plan exactly."""
    fm, rr = fixture_round
    kw = dict(batch_size=2, refresh_every=3, refresh="async",
              bucket_sizes=(32, 64, 128))
    base = Session.from_round(_plan(**kw), rr)
    off = Session.from_round(_plan(**kw, encoder="off",
                                   encode_dtype="bf16",
                                   encode_seq_len=999), rr)
    reqs, _, kvs = _requests(fm, 7, seed=seed)
    out_a = base.serve_versioned(reqs, kvs)
    out_b = off.serve_versioned(reqs, kvs)
    for (la, va), (lb, vb) in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
        assert va == vb
    for x, y in zip(jax.tree.leaves(base.service.state),
                    jax.tree.leaves(off.service.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(
        np.asarray(base.service._taubuf.bufs),
        np.asarray(off.service._taubuf.bufs))
    # The one entry cache holds only the plain step's entry: the plan's
    # step is _make_step's own function, no encode stage composed in.
    entries = off.service.plane._planes
    assert list(entries) == [1]
    assert (entries[1][0].__wrapped__.__qualname__
            == "_make_step.<locals>.step")
    assert off.service.encoder is None
    assert off.service.stats()["encoder"]["mode"] == "off"


def test_plain_step_lowers_to_jit_step_module():
    """With heads and encoder off, the single-host entry is the plain
    program: it lowers to the XLA module ``jit_step`` (the name the
    benchmark's serve-step and fold/refresh device-trace metrics read),
    with the plain five-argument signature and no wrapper."""
    from repro.fed.plane import ServePlane
    B, n = 4, 16
    cfg = _plan(batch_size=B, bucket_sizes=(n,)).stream_config()
    step = ServePlane(cfg)._plane_for(1)[0]
    Sd = jax.ShapeDtypeStruct
    text = step.lower(Sd((K, D), jnp.float32), Sd((B, 2), jnp.uint32),
                      Sd((B, n, D), jnp.float32), Sd((B, n), jnp.bool_),
                      Sd((B,), jnp.int32)).as_text()
    assert "module @jit_step" in text


def test_encoder_off_checkpoint_roundtrips_with_default_plan(
        fixture_round, tmp_path):
    """A checkpoint written by an explicit ``encoder="off"`` plan
    restores under the default plan (and vice versa) — the off mode
    adds no schema surface."""
    fm, rr = fixture_round
    sess = Session.from_round(_plan(encoder="off"), rr)
    reqs, _, kvs = _requests(fm, 3, seed=37)
    sess.serve(reqs, kvs)
    path = str(tmp_path / "off.npz")
    sess.save(path)
    replica = Session.restore(path, _plan())
    np.testing.assert_array_equal(np.asarray(replica.tau_centers),
                                  np.asarray(sess.tau_centers))
    more, _, mkv = _requests(fm, 2, seed=41)
    for a, b in zip(sess.serve(more, mkv), replica.serve(more, mkv)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------- fused step under shard_map ---


def test_fused_step_matches_staged_step_sharded():
    """DESIGN.md §13 acceptance: the fused solve+attach serve step is
    bitwise identical to the pre-fusion three-stage composition UNDER
    THE PLANE'S OWN SHARDING — shard_mapped over the full mesh exactly
    as ServePlane._plane_for wires it (the CI mesh job runs this at 2
    and 8 forced devices; at 1 device both reduce to the jitted step)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.local_kmeans import batched_local_kmeans
    from repro.fed.plane import _make_step
    from repro.utils.compat import shard_map as _shard_map

    B, n = 2 * NDEV, 48
    cfg = _plan(batch_size=B, bucket_sizes=(n,),
                local_kw={"approx_iters": 2, "max_iters": 7},
                serve_axes=("data",) if NDEV > 1 else None).stream_config()

    def legacy(tau, keys, data, point_mask, k_valid):
        loc = batched_local_kmeans(keys, data, k_max=cfg.k_prime,
                                   k_valid=k_valid, point_mask=point_mask,
                                   **cfg.local_kw)
        ctr = jax.vmap(lambda c, m: S.assign_new_device(c, m, tau))(
            loc.centers, loc.center_mask)
        labels = S.induced_labels(ctr, loc.assign)
        return (labels, loc.centers, loc.center_mask,
                S.core_weights(loc.core_counts))

    fused = _make_step(cfg)
    if NDEV > 1:
        spec = P(("data",))
        ins = (P(), spec, spec, spec, spec)
        mesh = _mesh()
        fused = _shard_map(fused, mesh=mesh, in_specs=ins,
                           out_specs=(spec,) * 5)
        legacy = _shard_map(legacy, mesh=mesh, in_specs=ins,
                            out_specs=(spec,) * 4)

    rng = np.random.default_rng(NDEV)
    tau = jnp.asarray(rng.normal(size=(K, D)) * 4, jnp.float32)
    data = jnp.asarray(rng.normal(size=(B, n, D)) * 3, jnp.float32)
    pm = jnp.asarray(rng.random((B, n)) < 0.9)
    kv = jnp.asarray(rng.integers(1, KP + 1, size=(B,)), jnp.int32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(5), jnp.arange(B))

    got = jax.jit(fused)(tau, keys, data, pm, kv)
    want = jax.jit(legacy)(tau, keys, data, pm, kv)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
