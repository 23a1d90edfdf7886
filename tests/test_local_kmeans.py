"""Algorithm 1 step 1: the converged block iteration against a float64
SVD of the same masked request matrix.

For each case the largest principal angle (sin theta_max) between the
iteration's top-k_valid right singular subspace and numpy's float64 one
must be within max(1e-5, 10 x the angle of an f32 ``jnp.linalg.svd`` of
the same data): the iteration is an f32 SVD's top subspace, not an
approximation of it. Also: finite on an all-zero (padding) row, a row's
result inside a batch is bitwise the row run alone, and the core-set
seeds ``local_prepare`` builds on the projection match those of the
full-SVD projection.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import local_kmeans as L

D, K, KP = 784, 64, 8


def _means(rng, sep=60.0):
    mu = rng.normal(size=(K, D))
    d2 = ((mu[:, None] - mu[None]) ** 2).sum(-1) + np.eye(K) * 1e30
    return mu * sep / np.sqrt(d2.min())


def _request(rng, mu, n_pad, n_valid, kv):
    """A FEMNIST-shaped report: ``n_valid`` points of ``kv`` random
    mixture components (sigma = 1), zero-padded to ``n_pad`` rows."""
    comps = rng.choice(K, kv, replace=False)
    A = np.zeros((n_pad, D), np.float32)
    A[:n_valid] = mu[rng.choice(comps, n_valid)] + rng.normal(
        size=(n_valid, D))
    return A, np.arange(n_pad) < n_valid


def _degenerate(rng, n_pad, top):
    """Rank-12 data whose two largest singular values are equal (to the
    rounding of the f32 cast)."""
    s = np.array([top, top] + list(np.geomspace(top / 3, top / 30, 10)))
    U = np.linalg.qr(rng.normal(size=(n_pad, s.size)))[0]
    V = np.linalg.qr(rng.normal(size=(D, s.size)))[0]
    return (U * s) @ V.T + 1e-3 * rng.normal(size=(n_pad, D))


def _sin_max(ref, V):
    """Largest principal angle's sine between the column spans."""
    Q1, Q2 = np.linalg.qr(ref)[0], np.linalg.qr(V)[0]
    return float(np.linalg.norm(Q2 - Q1 @ (Q1.T @ Q2), 2))


_sub = jax.jit(jax.vmap(lambda a, k: L.top_right_subspace(a, k, KP)))
_svd32 = jax.jit(jax.vmap(lambda a: jnp.linalg.svd(a, full_matrices=False)[2]))


def _check(rows, kvs):
    """Angles of the iteration and of an f32 SVD against float64, per
    row, over the top min(k_valid, rank) singular vectors."""
    Am = np.stack(rows).astype(np.float32)
    V, iters = _sub(jnp.asarray(Am), jnp.asarray(kvs, jnp.int32))
    V, iters = np.asarray(V), np.asarray(iters)
    assert np.isfinite(V).all()
    assert (iters >= 1).all() and (iters < L.PROJ_MAX_ITERS).all(), iters
    for i, kv in enumerate(kvs):
        _, s, Vt = np.linalg.svd(Am[i].astype(np.float64),
                                 full_matrices=False)
        r = min(int(kv), int((s > s[0] * 1e-6).sum()))
        ref = Vt[:r].T
        got = _sin_max(ref, V[i][:, :r])
        if got > 1e-5:  # the f32 SVD's angle can only raise the bound
            W32 = np.asarray(_svd32(jnp.asarray(Am[i][None])))[0]
            f32 = _sin_max(ref, W32[:r].T)
            assert got <= 10 * f32, (i, kv, got, f32)
        # The columns past k_valid are the next ones, ordered; the mask
        # for k_valid < k_max is applied by project_top_k.
        assert np.allclose(np.linalg.norm(V[i][:, :r], axis=0), 1.0,
                           atol=1e-5)


@pytest.mark.parametrize("n_pad", [64, 256, 1024, 224])
def test_mixture_subspace_matches_float64_svd(n_pad):
    """FEMNIST-shaped mixtures, k_valid 1..8, the rows filled to about
    the pad (224: the round's n, unpadded)."""
    rng = np.random.default_rng(n_pad)
    mu = _means(rng)
    rows, kvs = [], []
    for kv in range(1, KP + 1):
        n_valid = n_pad if n_pad == 224 else int(
            rng.integers(n_pad // 4 + 1, n_pad + 1))
        A, pm = _request(rng, mu, n_pad, n_valid, kv)
        rows.append(A * pm[:, None])
        kvs.append(kv)
    _check(rows, kvs)


@pytest.mark.parametrize("n_pad", [64, 256, 1024])
def test_sixteen_valid_points(n_pad):
    """A report of 16 points: rank at most 16 = the block width."""
    rng = np.random.default_rng(16 + n_pad)
    mu = _means(rng)
    rows, kvs = [], []
    for kv in (1, 4, 8):
        A, pm = _request(rng, mu, n_pad, 16, kv)
        rows.append(A * pm[:, None])
        kvs.append(kv)
    _check(rows, kvs)


def test_fewer_valid_points_than_clusters():
    """n_valid < k_valid: the rank bounds the subspace; the valid rows'
    projection equals the float64 one."""
    rng = np.random.default_rng(3)
    mu = _means(rng)
    A, pm = _request(rng, mu, 64, 3, 6)
    _check([A * pm[:, None]], [6])
    got, _ = L.project_top_k(jnp.asarray(A), 6, KP, jnp.asarray(pm))
    Vt = np.linalg.svd(A[:3].astype(np.float64), full_matrices=False)[2]
    want = A[:3] @ Vt.T @ Vt
    np.testing.assert_allclose(np.asarray(got)[:3], want,
                               rtol=0, atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("kv", [2, 3])
def test_near_degenerate_top_pair(kv):
    """Two equal top singular values: the subspace holding both (and the
    next, for k_valid 3) is what the iteration must find."""
    rng = np.random.default_rng(kv)
    for n_pad, top in ((256, 400.0), (1024, 50.0)):
        _check([_degenerate(rng, n_pad, top)], [kv])


def test_all_zero_padding_row_is_finite():
    """A batch's padding row (no valid point) projects to zeros, with
    no NaN, in one step."""
    A = jnp.zeros((1024, D), jnp.float32)
    pm = jnp.zeros((1024,), bool)
    for kv in (1, KP):
        got, iters = L.project_top_k(A, kv, KP, pm)
        assert int(iters) == 1
        assert np.isfinite(np.asarray(got)).all()
        assert not np.asarray(got).any()


def test_row_in_batch_is_bitwise_the_row_alone():
    """Rows that stop at different steps: each row's projection and
    count inside the batch are bitwise what the row gives alone."""
    rng = np.random.default_rng(11)
    mu = _means(rng)
    rows, pms, kvs = [], [], []
    for n_valid, kv in ((256, 8), (40, 3), (16, 8), (0, 5), (200, 1)):
        A, pm = _request(rng, mu, 256, n_valid, kv) if n_valid else (
            np.zeros((256, D), np.float32), np.zeros((256,), bool))
        rows.append(A)
        pms.append(pm)
        kvs.append(kv)
    proj = jax.jit(jax.vmap(lambda a, k, m: L.project_top_k(a, k, KP, m)))
    got, iters = proj(jnp.asarray(np.stack(rows)),
                      jnp.asarray(kvs, jnp.int32), jnp.asarray(np.stack(pms)))
    assert len(set(np.asarray(iters).tolist())) > 1
    for i in range(len(rows)):
        one, it = proj(jnp.asarray(rows[i][None]),
                       jnp.asarray(kvs[i:i + 1], jnp.int32),
                       jnp.asarray(pms[i][None]))
        np.testing.assert_array_equal(np.asarray(one)[0],
                                      np.asarray(got)[i])
        assert int(it[0]) == int(iters[i])


def _svd_project(A, k_valid, k_max, point_mask=None):
    """The full-SVD projection the iteration replaced."""
    Af = A.astype(jnp.float32)
    Am = Af if point_mask is None else Af * point_mask[:, None]
    Vt = jnp.linalg.svd(Am, full_matrices=False)[2]
    rows = min(k_max, Vt.shape[0])
    V = jnp.zeros((k_max, A.shape[1]), jnp.float32).at[:rows].set(Vt[:rows])
    V = V * (jnp.arange(k_max) < k_valid)[:, None]
    return (Af @ V.T) @ V, jnp.int32(0)


def test_core_set_seeds_match_svd_path(monkeypatch):
    """local_prepare's core-set means on seeded FEMNIST-shaped requests
    are those of the full-SVD projection, to f32 rounding."""
    rng = np.random.default_rng(5)
    mu = _means(rng)
    rows, pms, kvs = [], [], []
    for kv in range(1, KP + 1):
        A, pm = _request(rng, mu, 256, int(rng.integers(65, 257)), kv)
        rows.append(A)
        pms.append(pm)
        kvs.append(kv)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(9), jnp.arange(len(rows)))
    args = (keys, jnp.asarray(np.stack(rows)))
    kw = dict(k_max=KP, k_valid=jnp.asarray(kvs, jnp.int32),
              point_mask=jnp.asarray(np.stack(pms)))
    new = L.batched_local_prepare(*args, **kw)
    monkeypatch.setattr(L, "project_top_k", _svd_project)
    old = L.batched_local_prepare(*args, **kw)
    np.testing.assert_array_equal(np.asarray(new.center_mask),
                                  np.asarray(old.center_mask))
    np.testing.assert_array_equal(np.asarray(new.core_counts),
                                  np.asarray(old.core_counts))
    np.testing.assert_allclose(np.asarray(new.theta), np.asarray(old.theta),
                               rtol=1e-5, atol=1e-4)
    assert (np.asarray(new.proj_iters) >= 1).all()


# cifar100-3072's reports: n = 100 points of k^(z) in 1..10 components,
# padded to the one rung of 128, at d = 3,072 (TFF CIFAR-100). A report
# is short and wide, about 10 points per component against a noise bulk
# whose top singular value is about sqrt(3072) + sqrt(100).
CIFAR_D, CIFAR_K, CIFAR_KP, CIFAR_N, CIFAR_PAD = 3072, 100, 10, 100, 128


def _cifar_means(rng):
    mu = rng.normal(size=(CIFAR_K, CIFAR_D))
    sq = (mu * mu).sum(1)
    d2 = sq[:, None] + sq[None] - 2.0 * mu @ mu.T + np.eye(CIFAR_K) * 1e30
    return mu * 60.0 / np.sqrt(d2.min())


def _cifar_report(rng, mu, comps):
    A = np.zeros((CIFAR_PAD, CIFAR_D), np.float32)
    A[:CIFAR_N] = mu[rng.choice(comps, CIFAR_N)] + rng.normal(
        size=(CIFAR_N, CIFAR_D))
    return A


def test_short_wide_reports_stop_under_the_cap():
    """Every row stops before ``PROJ_MAX_ITERS``, and its kept subspace
    lies within the Davis-Kahan bound of a float64 SVD's. At the stop
    each kept Ritz residual is at most PROJ_TOL eps theta_1, so the sine
    of the largest principal angle is at most sqrt(r) PROJ_TOL eps
    theta_1 / (theta_r - theta_{r+1}) (eigenvalues of Am^T Am); twice
    that, for the f32 rounding of Am and of the residual itself."""
    rng = np.random.default_rng(3072)
    mu = _cifar_means(rng)
    rows, kvs = [], []
    for kv in list(range(1, CIFAR_KP + 1)) * 2:
        rows.append(_cifar_report(rng, mu, rng.choice(CIFAR_K, kv,
                                                      replace=False)))
        kvs.append(kv)
    sub = jax.jit(jax.vmap(
        lambda a, k: L.top_right_subspace(a, k, CIFAR_KP)))
    V, iters = sub(jnp.asarray(np.stack(rows)), jnp.asarray(kvs, jnp.int32))
    V, iters = np.asarray(V), np.asarray(iters)
    assert (iters >= 1).all() and (iters < L.PROJ_MAX_ITERS).all(), iters
    eps = float(np.finfo(np.float32).eps)
    for i, kv in enumerate(kvs):
        _, s, Vt = np.linalg.svd(rows[i].astype(np.float64),
                                 full_matrices=False)
        theta = s * s
        bound = 2.0 * np.sqrt(kv) * L.PROJ_TOL * eps * theta[0] / (
            theta[kv - 1] - theta[kv])
        assert bound < 1e-3, (i, kv, bound)   # the regime has its gap
        got = _sin_max(Vt[:kv].T, V[i][:, :kv])
        assert got <= bound, (i, kv, got, bound)


def test_missing_component_stops_on_the_determined_subspace():
    """k^(z) = 10 of which 9 components drew points: the 10th singular
    value lies in the noise bulk, with no gap to the 11th, and its
    direction converges too slowly for the cap (64 steps on a v5e). The
    row stops once the 9 determined directions pass the residual test,
    with those within the Davis-Kahan bound of float64 (as above), and
    the 10th Ritz value within 1 + PROJ_GAP of the 11th singular value:
    a rank-10 approximation as good as the float64 SVD's to that
    factor."""
    rng = np.random.default_rng(733)
    mu = _cifar_means(rng)
    rows = [_cifar_report(rng, mu, rng.choice(CIFAR_K, 9, replace=False))
            for _ in range(4)]
    sub = jax.jit(jax.vmap(
        lambda a, k: L.top_right_subspace(a, k, CIFAR_KP)))
    V, iters = sub(jnp.asarray(np.stack(rows)),
                   jnp.full((len(rows),), CIFAR_KP, jnp.int32))
    V, iters = np.asarray(V), np.asarray(iters)
    assert (iters < L.PROJ_MAX_ITERS // 2).all(), iters
    eps = float(np.finfo(np.float32).eps)
    for i, A in enumerate(rows):
        A64 = A.astype(np.float64)
        _, s, Vt = np.linalg.svd(A64, full_matrices=False)
        theta = s * s
        assert theta[8] > 2.0 * theta[9] > 1.9 * theta[10]
        bound = 2.0 * 3.0 * L.PROJ_TOL * eps * theta[0] / (
            theta[8] - theta[9])
        assert _sin_max(Vt[:9].T, V[i][:, :9]) <= bound
        v = V[i][:, 9]
        ritz = float(np.sum((A64 @ v) ** 2))
        assert theta[10] / (1.0 + L.PROJ_GAP) <= ritz <= theta[9] * (
            1.0 + 1e-6)
