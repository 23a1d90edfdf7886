"""The declarative federation API (fed/api.py, DESIGN.md §10):
FederationPlan validation, Session-vs-legacy bitwise parity on all
three topologies, FoldPolicy admission properties (drop pinned to the
historical behavior, lru / weighted_reservoir capacity invariants),
and the warn-once deprecation contract of the legacy shims."""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st

from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, PlanError, Session, SessionError
from repro.fed.policy import make_policy
from repro.fed.stream import StreamConfig, StreamConfigError
from repro.utils.deprecation import reset_legacy_warnings

K, KP, D = 16, 4, 24
PLAN = FederationPlan(k=K, k_prime=KP, d=D)


@pytest.fixture(scope="module")
def fixture_data():
    return structured_devices(jax.random.PRNGKey(0), k=K, d=D, k_prime=KP,
                              m0=4, n_per_comp_dev=20, sep=60.0)


def _legacy(fn, *args, **kw):
    """Call a deprecated entry point with its warning suppressed (the
    shims are exactly what these tests compare Session against). The
    warn-once registry is re-armed afterwards so a stray legacy call
    elsewhere in the suite still trips the pytest.ini error rule."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        out = fn(*args, **kw)
    reset_legacy_warnings()
    return out


# ------------------------------------------------------- validation --


def test_plan_validation_names_field_and_accepted_values():
    cases = [
        (dict(k=0, k_prime=1, d=2), "FederationPlan.k="),
        (dict(k=4, k_prime=9, d=2), "k_prime"),
        (dict(k=4, k_prime=0, d=2), "k_prime"),
        (dict(k=4, k_prime=2, d=0), "FederationPlan.d="),
        (dict(k=4, k_prime=2, d=2, topology="ring"), "topology"),
        (dict(k=4, k_prime=2, d=2, mesh_axes=()), "mesh_axes"),
        (dict(k=4, k_prime=2, d=2, fold_capacity=0), "fold_capacity"),
        (dict(k=4, k_prime=2, d=2, capacity=0), "capacity"),
        (dict(k=4, k_prime=2, d=2, batch_size=0), "batch_size"),
        (dict(k=4, k_prime=2, d=2, refresh_every=-1), "refresh_every"),
        (dict(k=4, k_prime=2, d=2, bucket_sizes=(64, 32)),
         "bucket_sizes"),
        (dict(k=4, k_prime=2, d=2, bucket_sizes=()), "bucket_sizes"),
        (dict(k=4, k_prime=2, d=2, fold_policy="fifo"), "fold_policy"),
    ]
    for kw, frag in cases:
        with pytest.raises(PlanError) as ei:
            FederationPlan(**kw)
        assert frag in str(ei.value), (kw, str(ei.value))
    # the topology error enumerates the accepted values
    with pytest.raises(PlanError, match="simulated"):
        FederationPlan(k=4, k_prime=2, d=2, topology="ring")
    with pytest.raises(PlanError, match="weighted_reservoir"):
        FederationPlan(k=4, k_prime=2, d=2, fold_policy="fifo")


def test_stream_config_validation_names_field():
    good = dict(k=4, k_prime=2, d=3, capacity=8)
    StreamConfig(**good)
    for kw, frag in [(dict(good, bucket_sizes=(64, 64)), "bucket_sizes"),
                     (dict(good, k_prime=5), "k_prime"),
                     (dict(good, capacity=0), "capacity"),
                     (dict(good, batch_size=0), "batch_size"),
                     (dict(good, fold_policy="fifo"), "fold_policy")]:
        with pytest.raises(StreamConfigError) as ei:
            StreamConfig(**kw)
        assert frag in str(ei.value), str(ei.value)


def test_session_lifecycle_errors():
    with pytest.raises(PlanError, match="mesh"):
        Session(FederationPlan(k=4, k_prime=2, d=2,
                               topology="replicated"))
    sess = Session(PLAN)
    with pytest.raises(SessionError, match="finalized round"):
        sess.serve([np.zeros((4, D), np.float32)])
    with pytest.raises(SessionError, match="fold"):
        sess.finalize()
    with pytest.raises(SessionError, match="key"):
        sess.fold([0, 1])
    with pytest.raises(PlanError, match="feature dim"):
        sess.run(jax.random.PRNGKey(0), jnp.zeros((2, 4, D + 1)))


# -------------------------------------- Session-vs-legacy parity -----


def test_session_run_bitwise_equals_kfed(fixture_data):
    """Simulated topology: Session.run == the legacy core.kfed.kfed
    shim, bitwise, incl. participation masks and core-count weighting
    (acceptance criterion)."""
    from repro.core.kfed import kfed
    fm = fixture_data
    Z = fm.data.shape[0]
    part = jnp.asarray(~np.isin(np.arange(Z), [3, 12]))
    variants = [
        (PLAN, {}),
        (PLAN, dict(participation=part)),
        (PLAN.with_options(weight_by_core_counts=True), {}),
        (PLAN.with_options(weight_by_core_counts=True),
         dict(participation=part)),
    ]
    for plan, kw in variants:
        mine = Session(plan).run(jax.random.PRNGKey(1), fm.data, **kw)
        old = _legacy(kfed, jax.random.PRNGKey(1), fm.data, k=K,
                      k_prime=KP,
                      weight_by_core_counts=plan.weight_by_core_counts,
                      **kw)
        np.testing.assert_array_equal(np.asarray(mine.labels),
                                      np.asarray(old.labels))
        np.testing.assert_array_equal(np.asarray(mine.tau_centers),
                                      np.asarray(old.agg.tau_centers))
        np.testing.assert_array_equal(
            np.asarray(mine.detail.agg.center_labels),
            np.asarray(old.agg.center_labels))


def test_session_fold_finalize_bitwise_equals_async(fixture_data):
    """Session.fold/finalize == the legacy run_round_async shim ==
    Session.run with participation = union(cohorts), bitwise."""
    from repro.fed.engine import EngineConfig, run_round_async
    fm = fixture_data
    cohorts = [[15, 3, 9], [0, 1, 2, 4, 5, 6, 7, 8], [3, 9],  # retry
               [10, 11, 12, 13]]
    sess = Session(PLAN).begin(jax.random.PRNGKey(1), fm.data)
    for c in cohorts:
        sess.fold(c)
    mine = sess.finalize()
    old = _legacy(run_round_async, jax.random.PRNGKey(1), fm.data,
                  EngineConfig(k=K, k_prime=KP), cohorts)
    np.testing.assert_array_equal(np.asarray(mine.labels),
                                  np.asarray(old.labels))
    part = jnp.zeros((fm.data.shape[0],), bool)
    for c in cohorts:
        part = part.at[jnp.asarray(c)].set(True)
    sync = Session(PLAN).run(jax.random.PRNGKey(1), fm.data,
                             participation=part)
    np.testing.assert_array_equal(np.asarray(mine.labels),
                                  np.asarray(sync.labels))
    np.testing.assert_array_equal(np.asarray(mine.tau_centers),
                                  np.asarray(sync.tau_centers))


def test_session_attach_fn_bitwise_equals_make_kfed_attach(fixture_data):
    from repro.launch.serve import make_kfed_attach
    fm = fixture_data
    sess = Session(PLAN)
    rr = sess.run(jax.random.PRNGKey(1), fm.data)
    legacy_fn = _legacy(make_kfed_attach, rr.tau_centers, KP)
    mine_fn = sess.attach_fn()
    for z in [0, 7]:
        key = jax.random.PRNGKey(100 + z)
        np.testing.assert_array_equal(
            np.asarray(mine_fn(key, fm.data[z])),
            np.asarray(legacy_fn(key, fm.data[z])))


def test_session_serve_bitwise_equals_attach_service(fixture_data):
    """Session streaming == legacy AttachService.from_round/serve/
    save/restore, bitwise (labels AND fold state)."""
    from repro.fed.stream import AttachService
    fm = fixture_data
    plan = PLAN.with_options(capacity=256, batch_size=4,
                             bucket_sizes=(32, 64, 128))
    sess = Session(plan)
    rr = sess.run(jax.random.PRNGKey(1), fm.data).detail
    svc = _legacy(AttachService.from_round, rr, plan.stream_config())
    stream = late_device_stream(fm.means, KP, 7, 5)
    reqs, kvs = [r[0] for r in stream], [r[2] for r in stream]
    a = sess.serve(reqs, kvs)
    b = svc.serve(reqs, kvs)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for la, lb in zip(jax.tree.leaves(sess.service.state),
                      jax.tree.leaves(svc.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


CHILD = r"""
import os, warnings
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.compat import make_mesh
from repro.core.distributed import kfed_shard_map
from repro.data.gaussian import structured_devices
from repro.fed.api import FederationPlan, Session

mesh = make_mesh((8,), ("data",))
fm = structured_devices(jax.random.PRNGKey(0), k=16, d=24, k_prime=4,
                        m0=4, n_per_comp_dev=20, sep=60.0)
part = np.ones(16, bool); part[[3, 12]] = False
part = jnp.asarray(part)

for topology in ("replicated", "sharded"):
    for kw in ({}, {"participation": part}):
        for weighted in (False, True):
            plan = FederationPlan(k=16, k_prime=4, d=24,
                                  topology=topology,
                                  weight_by_core_counts=weighted)
            mine = Session(plan, mesh=mesh).run(
                jax.random.PRNGKey(1), fm.data, **kw)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                lbl, tau = kfed_shard_map(
                    mesh, fm.data, 16, 4, key=jax.random.PRNGKey(1),
                    server=topology, weight_by_core_counts=weighted,
                    **kw)
            np.testing.assert_array_equal(np.asarray(mine.labels),
                                          np.asarray(lbl))
            np.testing.assert_array_equal(np.asarray(mine.tau_centers),
                                          np.asarray(tau))

# simulated-vs-replicated cross-topology agreement (same key)
sim = Session(FederationPlan(k=16, k_prime=4, d=24)).run(
    jax.random.PRNGKey(1), fm.data)
rep = Session(FederationPlan(k=16, k_prime=4, d=24,
                             topology="replicated"),
              mesh=mesh).run(jax.random.PRNGKey(1), fm.data)
np.testing.assert_array_equal(np.asarray(sim.labels),
                              np.asarray(rep.labels))
print("OK session topology parity")
"""


@pytest.mark.slow
def test_session_topology_parity_subprocess():
    """Session-vs-legacy bitwise parity on the replicated and sharded
    shard_map topologies, incl. participation + weighting (acceptance
    criterion; 8 forced host devices, so subprocess)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK session topology parity" in out.stdout


# ------------------------------------------------ fold policies ------


@settings(max_examples=15, deadline=None)
@given(cap=st.integers(1, 8), n=st.integers(1, 50),
       seed=st.integers(0, 2 ** 16))
def test_property_drop_policy_pins_historical_behavior(cap, n, seed):
    """drop admits slot==rid for rid < capacity and nothing else —
    exactly the pre-policy over-capacity rule, for any id sequence."""
    rng = np.random.default_rng((cap, n, seed))
    rids = rng.integers(0, 3 * cap, size=n)
    pol = make_policy("drop", cap)
    got = [pol.admit(int(r)) for r in rids]
    want = [int(r) if r < cap else None for r in rids]
    assert got == want


@settings(max_examples=15, deadline=None)
@given(cap=st.integers(1, 8), n=st.integers(1, 60),
       seed=st.integers(0, 2 ** 16))
def test_property_lru_policy_keeps_most_recent(cap, n, seed):
    """lru always admits, never exceeds capacity, and retains exactly
    the last `cap` distinct ids by most-recent admission."""
    rng = np.random.default_rng((cap, n, seed, 1))
    rids = rng.integers(0, 2 * cap + 4, size=n)
    pol = make_policy("lru", cap)
    for r in rids:
        assert pol.admit(int(r)) is not None  # lru never drops
    last_seen = {}
    for i, r in enumerate(rids):
        last_seen[int(r)] = i
    want = set(sorted(last_seen, key=last_seen.get)[-cap:])
    held = {int(r) for r in pol._slot_rid if r >= 0}
    assert held == want
    assert len(held) <= cap


@settings(max_examples=15, deadline=None)
@given(cap=st.integers(1, 6), n=st.integers(1, 40),
       seed=st.integers(0, 2 ** 16))
def test_property_weighted_reservoir_exact_topk(cap, n, seed):
    """A-ES invariant: the held set equals the exact top-capacity of
    all distinct ids by (key, id) — independent of arrival order —
    and re-delivery is slot-stable."""
    rng = np.random.default_rng((cap, n, seed, 2))
    rids = rng.integers(0, 2 * cap + 6, size=n)
    w_of = {int(r): float(rng.uniform(0.1, 10.0))
            for r in np.unique(rids)}
    pol = make_policy("weighted_reservoir", cap, seed=seed)
    for r in rids:
        pol.admit(int(r), w_of[int(r)])
    keys = {r: (pol.key_of(r, w), r) for r, w in w_of.items()}
    want = set(sorted(keys, key=keys.get)[-min(cap, len(keys)):])
    held = {int(r) for r in pol._slot_rid if r >= 0}
    assert held == want
    # arrival-order invariance
    pol2 = make_policy("weighted_reservoir", cap, seed=seed)
    for r in rng.permutation(np.unique(rids)):
        pol2.admit(int(r), w_of[int(r)])
    assert {int(r) for r in pol2._slot_rid if r >= 0} == want
    # re-delivery of a held id keeps its slot
    if held:
        r0 = next(iter(held))
        s0 = pol._index[r0]
        assert pol.admit(r0, w_of[r0]) == s0


@settings(max_examples=15, deadline=None)
@given(cap=st.integers(1, 6), n=st.integers(1, 24),
       seed=st.integers(0, 2 ** 16), pol_i=st.integers(0, 2))
def test_property_admit_padded_sentinel_never_aliases(cap, n, seed,
                                                      pol_i):
    """Degenerate-batch sentinel contract (bugfix): for ANY batch —
    including one that is entirely duplicates of a single hot request
    id, or fully declined — the padded slot vector contains each live
    slot at most ONCE, every declined/padding row is exactly the
    out-of-capacity sentinel, and the slots granted match a sequential
    admit-then-fold oracle's final occupancy."""
    name = ["drop", "lru", "weighted_reservoir"][pol_i]
    rng = np.random.default_rng((cap, n, seed, 3))
    batches = [rng.integers(0, 2 * cap + 4, size=n),       # generic
               np.full((n,), int(rng.integers(0, 2 * cap)))]  # all-hot
    for rids in batches:
        w = rng.uniform(0.1, 10.0, size=n)
        pol = make_policy(name, cap, seed=seed)
        oracle = make_policy(name, cap, seed=seed)
        total = n + int(rng.integers(0, 4))
        full, granted = pol.admit_padded(rids, w, total=total)
        # oracle: sequential admits into a dict fold state
        fold = {}
        o_granted = 0
        for r, wi in zip(rids, w):
            s = oracle.admit(int(r), float(wi))
            if s is not None:
                o_granted += 1
                fold[s] = int(r)
        assert granted == o_granted
        assert full.shape == (total,)
        live = full[full < cap]
        assert len(set(live.tolist())) == len(live)   # no aliasing
        assert np.all(full[(full >= cap)] == cap)     # sentinel exact
        assert np.all(full[n:] == cap)                # padding rows
        # executing the vector as one scatter lands the oracle's state
        got = {int(full[i]): int(rids[i]) for i in range(n)
               if full[i] < cap}
        assert got == fold


@pytest.mark.parametrize("policy", ["lru", "weighted_reservoir"])
def test_policy_service_respects_capacity_and_checkpoints(
        fixture_data, tmp_path, policy):
    """End-to-end: an over-capacity stream folds at most `capacity`
    reports under lru/weighted_reservoir (vs drop's served-not-folded),
    and checkpoint -> restore replays serving AND admission bitwise."""
    fm = fixture_data
    plan = PLAN.with_options(capacity=8, batch_size=4,
                             bucket_sizes=(32, 64, 128),
                             fold_policy=policy)
    sess = Session(plan)
    sess.run(jax.random.PRNGKey(1), fm.data)
    stream = late_device_stream(fm.means, KP, 9, 5)
    sess.serve([r[0] for r in stream], [r[2] for r in stream])
    st = sess.stats()
    assert st["folded"] <= 8
    assert st["served_devices"] == 9          # over-capacity still served
    assert st["fold_policy"] == policy

    path = str(tmp_path / f"{policy}.npz")
    sess.save(path)
    replica = Session.restore(path, plan)
    more = late_device_stream(fm.means, KP, 4, 11)
    a = sess.serve([r[0] for r in more], [r[2] for r in more])
    b = replica.serve([r[0] for r in more], [r[2] for r in more])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for la, lb in zip(jax.tree.leaves(sess.service.state),
                      jax.tree.leaves(replica.service.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    pa = sess.service.policy.state_arrays()
    pb = replica.service.policy.state_arrays()
    assert sorted(pa) == sorted(pb)
    for name in pa:
        np.testing.assert_array_equal(pa[name], pb[name])


def test_second_run_reseeds_serving_layer(fixture_data):
    """A new finalized round invalidates the session's serving layer:
    attach/serve always answer against the LATEST tau centers."""
    fm = fixture_data
    sess = Session(PLAN)
    sess.run(jax.random.PRNGKey(1), fm.data)
    sess.attach(np.asarray(fm.data[0]))  # builds the round-1 service
    out2 = sess.run(jax.random.PRNGKey(2), fm.data)
    np.testing.assert_array_equal(np.asarray(sess.tau_centers),
                                  np.asarray(out2.tau_centers))
    lbl = sess.attach(np.asarray(fm.data[2]))
    np.testing.assert_array_equal(lbl, np.asarray(out2.labels[2]))


def test_restore_refuses_policy_mismatch(fixture_data, tmp_path):
    """A checkpoint records its admission policy; restoring under a
    different fold_policy is a named error, never silent slot-state
    corruption."""
    fm = fixture_data
    lru = PLAN.with_options(capacity=8, fold_policy="lru")
    sess = Session(lru)
    sess.run(jax.random.PRNGKey(1), fm.data)
    sess.attach(np.asarray(fm.data[1]))
    path = str(tmp_path / "lru.npz")
    sess.save(path)
    with pytest.raises(StreamConfigError, match="fold_policy"):
        Session.restore(path, lru.with_options(fold_policy="drop"))


def test_drop_service_over_capacity_served_not_folded(fixture_data):
    """The drop policy end-to-end: ids past capacity are served but the
    fold state holds exactly the first-come ids (historical rule)."""
    fm = fixture_data
    Z = fm.data.shape[0]
    plan = PLAN.with_options(capacity=Z + 2, batch_size=4,
                             bucket_sizes=(32, 64, 128))
    sess = Session(plan)
    sess.run(jax.random.PRNGKey(1), fm.data)
    stream = late_device_stream(fm.means, KP, 5, 17)
    out = sess.serve([r[0] for r in stream], [r[2] for r in stream])
    assert len(out) == 5
    received = np.asarray(sess.service.state.received)
    assert received.sum() == Z + 2
    assert received[:Z + 2].all()             # slots == request ids


# ------------------------------------------------- deprecation -------


def test_legacy_shims_warn_once_naming_session(fixture_data):
    """Each legacy entry point emits exactly ONE DeprecationWarning per
    process, naming its Session replacement; repeat calls are silent
    (the tier-1 suites otherwise run warning-clean — enforced globally
    by the pytest.ini filterwarnings error rule)."""
    from repro.core.kfed import kfed
    fm = fixture_data
    reset_legacy_warnings()
    with pytest.warns(DeprecationWarning, match="Session.run"):
        kfed(jax.random.PRNGKey(1), fm.data, k=K, k_prime=KP)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        kfed(jax.random.PRNGKey(1), fm.data, k=K, k_prime=KP)
    assert not [w for w in rec
                if issubclass(w.category, DeprecationWarning)
                and "repro legacy" in str(w.message)]
    reset_legacy_warnings()


def test_new_surface_is_warning_clean(fixture_data):
    """The Session lifecycle never routes through a deprecation shim."""
    fm = fixture_data
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        sess = Session(PLAN.with_options(capacity=64, batch_size=2,
                                         bucket_sizes=(32, 64, 128)))
        sess.run(jax.random.PRNGKey(1), fm.data)
        sess.attach(np.asarray(fm.data[0]))
        s2 = Session(PLAN).begin(jax.random.PRNGKey(1), fm.data)
        s2.fold(list(range(fm.data.shape[0])))
        s2.finalize()
    assert not [w for w in rec
                if issubclass(w.category, DeprecationWarning)
                and "repro legacy" in str(w.message)], (
        [str(w.message) for w in rec])


# ---------------------------------------------------- bench CLI ------


def test_bench_cli_unknown_key_and_list():
    """`benchmarks.run --only <typo>` names the bad key + valid keys and
    exits non-zero; `--list` prints the keys (ROADMAP open item)."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    bad = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "tabel1"],
        env=env, cwd=root, capture_output=True, text=True, timeout=120)
    assert bad.returncode != 0
    assert "tabel1" in bad.stderr and "table1" in bad.stderr
    lst = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--list"],
        env=env, cwd=root, capture_output=True, text=True, timeout=120)
    assert lst.returncode == 0
    assert "table1" in lst.stdout and "attach" in lst.stdout


@pytest.mark.parametrize("failing", ["raises", "error_row", None])
def test_bench_cli_exit_code(monkeypatch, capsys, failing):
    """`benchmarks.run` prints every bench's rows, and exits 1 when a
    bench raised or printed an ``ERROR:`` row (0 otherwise)."""
    import types

    from benchmarks import run as bench_run

    def fake(key, rows):
        def run(full=False):
            if key == "raises":
                raise RuntimeError("bench blew up")
            return rows
        mod = types.ModuleType(f"fake_bench_{key}")
        mod.run = run
        monkeypatch.setitem(sys.modules, mod.__name__, mod)
        return mod.__name__

    benches = {"good": fake("good", ["good_row,1.0,fine"]),
               "raises": fake("raises", []),
               "error_row": fake("error_row",
                                 ["child,0,ERROR:'child failed'"])}
    keys = ["good"] + ([failing] if failing else [])
    monkeypatch.setattr(bench_run, "BENCHES", benches)
    monkeypatch.setattr(bench_run, "use_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["run", "--only", ",".join(keys)])
    if failing:
        with pytest.raises(SystemExit) as exc:
            bench_run.main()
        assert exc.value.code == 1
    else:
        bench_run.main()
    out = capsys.readouterr()
    assert "good_row,1.0,fine" in out.out
    if failing:
        assert "ERROR:" in out.out
        assert f"bench(es) failed: {failing}" in out.err
