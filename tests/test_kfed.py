"""End-to-end tests of Algorithm 1 + Algorithm 2 on the paper's synthetic
construction (Section 4.1)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kfed as K
from repro.core import server as S
from repro.core.local_kmeans import local_kmeans
from repro.data.gaussian import structured_devices
from repro.fed.api import FederationPlan, Session
from repro.utils.metrics import clustering_accuracy


def _setup(key=0, k=16, d=32, k_prime=4, m0=3, n=25, sep=60.0):
    fm = structured_devices(jax.random.PRNGKey(key), k=k, d=d,
                            k_prime=k_prime, m0=m0, n_per_comp_dev=n,
                            sep=sep)
    return fm


def _kfed(key, data, k, k_prime, **kw):
    """End-to-end k-FED through the Session surface; returns the
    detailed RoundResult (a superset of the legacy KFedResult)."""
    plan = FederationPlan(k=k, k_prime=k_prime, d=int(data.shape[-1]))
    return Session(plan).run(key, data, **kw).detail


def test_local_kmeans_recovers_device_clusters():
    fm = _setup()
    res = local_kmeans(jax.random.PRNGKey(1), fm.data[0], k_max=4)
    acc = clustering_accuracy(np.asarray(res.assign),
                              np.asarray(fm.labels[0]) % 4, 4)
    assert acc > 0.99


def test_kfed_recovers_target_clustering():
    fm = _setup()
    out = _kfed(jax.random.PRNGKey(2), fm.data, 16, 4)
    acc = clustering_accuracy(np.asarray(out.labels),
                              np.asarray(fm.labels), 16)
    assert acc > 0.98


def test_kfed_seeds_one_center_per_target_cluster():
    """Lemma 6: max-min seeding picks exactly one device center per target
    cluster under the separation assumptions."""
    fm = _setup(sep=100.0)
    out = _kfed(jax.random.PRNGKey(3), fm.data, 16, 4)
    # Identify each seed's true cluster by nearest target mean.
    seeds = np.asarray(out.agg.seed_centers)
    means = np.asarray(fm.means)
    d = ((seeds[:, None] - means[None]) ** 2).sum(-1)
    assert len(set(d.argmin(1).tolist())) == 16


def test_seed_device_passes_over_a_report_that_split_a_component():
    """Algorithm 2 seeds M with one device's report. A device whose points
    missed one of its k^(z) components splits another in its local solve;
    seeded from it, M spends two seeds on one component and tau merges
    two others. Of the devices with the most local clusters, the server
    seeds from the best separated report."""
    rng = np.random.default_rng(0)
    k, kp, d = 8, 4, 16
    means = rng.normal(size=(k, d))
    means *= 60.0 / np.sqrt(min(((a - b) ** 2).sum()
                                for i, a in enumerate(means)
                                for b in means[i + 1:]))
    comps = [[0, 1, 2, 3], [4, 5, 6, 7]] * 4
    centers = np.stack([means[c] for c in comps])
    centers += 0.05 * rng.normal(size=centers.shape)
    centers[0, 3] = means[0] + 2.0 * rng.normal(size=d) / np.sqrt(d)
    mask = np.ones((len(comps), kp), bool)
    mask[1, 3] = False                    # fewer clusters: never the seed
    agg = S.aggregate(jnp.asarray(centers, jnp.float32), jnp.asarray(mask),
                      k)
    assert int(agg.z0) not in (0, 1)
    seeds = np.asarray(agg.seed_centers)
    near = ((seeds[:, None] - means[None]) ** 2).sum(-1).argmin(1)
    assert sorted(near.tolist()) == list(range(k))
    sep = np.asarray(S.report_separation(jnp.asarray(centers, jnp.float32),
                                         jnp.asarray(mask)))
    assert sep[0] < 10.0 and sep[2:].min() > 1000.0
    kz = jnp.asarray(mask.sum(1))
    assert int(S.seed_device(kz, jnp.asarray(sep))) == 2 + int(
        np.argmax(sep[2:]))
    # Fewer than two valid centers: +inf, and the count still comes first.
    one = np.zeros_like(mask)
    one[:, 0] = True
    assert np.isinf(np.asarray(S.report_separation(
        jnp.asarray(centers, jnp.float32), jnp.asarray(one)))).all()
    assert int(S.seed_device(jnp.asarray([1, 2, 2]),
                             jnp.asarray([np.inf, 5.0, 7.0]))) == 2


def test_kfed_heterogeneous_k_valid():
    """Devices with different k^(z) (some clusters missing)."""
    fm = _setup()
    # Drop one component from device 0 by masking its points.
    pm = np.ones(fm.labels.shape, bool)
    pm[0] = np.asarray(fm.labels[0] % 4) != 2
    kv = np.asarray(fm.k_valid).copy()
    kv[0] = 3
    out = _kfed(jax.random.PRNGKey(4), fm.data, 16, 4,
                k_valid=jnp.asarray(kv), point_mask=jnp.asarray(pm))
    acc = clustering_accuracy(np.asarray(out.labels)[pm],
                              np.asarray(fm.labels)[pm], 16)
    assert acc > 0.97


def test_induced_labels_definition():
    center_labels = jnp.array([[2, 0, -1], [1, 1, 3]])
    local_assign = jnp.array([[0, 1, -1], [2, 0, 1]])
    lbl = K.induced_labels(center_labels, local_assign)
    np.testing.assert_array_equal(np.asarray(lbl),
                                  [[2, 0, -1], [3, 1, 1]])


def test_assign_new_device_matches_existing_clustering():
    """Theorem 3.2: a straggler joining later is assigned correctly with
    no network-wide recomputation."""
    fm = _setup(sep=80.0)
    # Hold out the last device.
    out = _kfed(jax.random.PRNGKey(5), fm.data[:-1], 16, 4)
    loc = local_kmeans(jax.random.PRNGKey(6), fm.data[-1], k_max=4)
    lbl = K.assign_new_device(loc.centers, loc.center_mask,
                              out.agg.tau_centers)
    point_lbl = K.induced_labels(lbl[None], loc.assign[None])[0]
    # Consistency: new-device points land in the cluster holding the same
    # target component (compare against full-network run).
    full = _kfed(jax.random.PRNGKey(5), fm.data, 16, 4)
    # Map both labelings to target labels for comparison.
    acc_joint = clustering_accuracy(
        np.concatenate([np.asarray(out.labels).ravel(),
                        np.asarray(point_lbl).ravel()]),
        np.asarray(fm.labels).ravel(), 16)
    assert acc_joint > 0.97
    assert full is not None


def test_kmeans_cost_of_labels_matches_manual():
    x = jax.random.normal(jax.random.PRNGKey(7), (30, 4))
    lb = jnp.concatenate([jnp.zeros(15, jnp.int32), jnp.ones(15, jnp.int32)])
    cost = float(K.kmeans_cost_of_labels(x, lb, 2))
    manual = 0.0
    xn = np.asarray(x)
    for r in range(2):
        pts = xn[np.asarray(lb) == r]
        manual += ((pts - pts.mean(0)) ** 2).sum()
    np.testing.assert_allclose(cost, manual, rtol=1e-5)
