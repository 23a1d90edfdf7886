"""Algorithm 1 — the local (per-device) k-means solve of k-FED.

Faithful to Awasthi & Sheffet (2012) as stated in the paper:

  1. Project the device data A^(z) onto the span of its top-k^(z) right
     singular vectors.
  2. Run a standard approximation algorithm on the projected data
     (k-means++ seeding + a few Lloyd polish steps — any O(1)-approx
     qualifies for the paper's "10-approximation" role).
  3. Form the 1/3-margin core sets
        S_r = { i : ||Ahat_i - nu_r|| <= (1/3) ||Ahat_i - nu_s||  forall s }
     and re-center on their means theta_r = mu(S_r).
  4. Run Lloyd steps on the ORIGINAL data until convergence.

Fixed-shape + masked so it vmaps over devices with heterogeneous k^(z)
(k_valid) and n^(z) (point_mask).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lloyd import kmeans_pp_init, lloyd, update_centers
from repro.kernels import ops
from repro.kernels.ref import MASKED_DIST


# Step 1's projection is a block subspace iteration on Am^T Am, run to a
# convergence test (``top_right_subspace``). Every matmul in it runs at
# Precision.HIGHEST (f32; never the TPU's single bf16 pass). A row stops
# once every kept Ritz pair's residual meets the backward-error bound
# ||Am^T Am v_i - theta_i v_i|| <= PROJ_TOL * eps_f32 * theta_1, or at
# PROJ_MAX_ITERS steps.
#
# A kept pair whose Ritz value is below (1 + PROJ_GAP) times the first
# dropped one's is not held to that bound: the data leave its direction
# undetermined among dropped ones of about the same value (the noise
# bulk, when a device holds fewer components than its k^(z)), and any
# of them gives a rank-k^(z) approximation as good to that factor. At a
# ratio of 1 + PROJ_GAP or more the error falls by about that factor per
# step, and 1.25^64 > 1e6 brings it from the start to the f32 floor
# within PROJ_MAX_ITERS.
PROJ_TOL = 16.0
PROJ_GAP = 0.25
PROJ_MAX_ITERS = 64

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(spec, a, b):
    # einsum's dot_general contracts in place, with no transpose that a
    # batch could lay out differently: a row's bits are those it has alone.
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def _start_basis(d: int, p: int) -> np.ndarray:
    """A fixed orthonormal (d, p) start, the same for every request."""
    i = np.arange(d, dtype=np.float64)[:, None]
    j = np.arange(p, dtype=np.float64)[None, :]
    V = np.cos(0.37 * (i + 1.0) * (j + 1.0)) + 1e-3 * (i - j)
    return np.linalg.qr(V)[0].astype(np.float32)


def top_right_subspace(Am: jax.Array, k_valid, k_max: int):
    """The top-k_max right singular vectors of ``Am`` as (d, k_max)
    columns in descending order, and the number of iterations taken.

    A block of p = min(2 k_max, n, d) columns steps V <- qr(Am^T (Am V));
    after each step the p x p Rayleigh-Ritz problem orders the Ritz
    vectors, and the loop ends once the first min(k_valid, p) of them
    pass the residual test above, or lie within the gap of the first
    dropped Ritz value. Columns past p are zero. Finite on an
    all-zero matrix and on one of rank below p; under vmap a finished
    row's carry stays as it was, so each row depends on itself alone."""
    n, d = Am.shape
    p = min(2 * k_max, n, d)
    k_live = jnp.minimum(jnp.asarray(k_valid, jnp.int32), p)
    live = jnp.arange(p) < k_live
    tol = PROJ_TOL * float(np.finfo(np.float32).eps)

    def body(carry):
        V, _, it, _ = carry
        W = _dot("nd,np->dp", Am, _dot("nd,dp->np", Am, V))  # Am^T Am V
        theta, S = jnp.linalg.eigh(_dot("dp,dq->pq", V, W))  # ascending
        theta, S = theta[::-1], S[:, ::-1]
        Y, GY = _dot("dp,pq->dq", V, S), _dot("dp,pq->dq", W, S)
        res = jnp.linalg.norm(GY - Y * theta[None, :], axis=0)
        ok = res <= tol * jnp.maximum(theta[0], 0.0)
        dropped = jnp.where(k_live < p, theta[jnp.minimum(k_live, p - 1)],
                            0.0)
        ok = ok | (theta < (1.0 + PROJ_GAP) * dropped)
        return jnp.linalg.qr(GY)[0], Y, it + 1, jnp.all(ok | ~live)

    def cond(carry):
        return ~carry[3] & (carry[2] < PROJ_MAX_ITERS)

    V0 = jnp.asarray(_start_basis(d, p))
    _, Y, iters, _ = jax.lax.while_loop(
        cond, body, (V0, V0, jnp.int32(0), jnp.bool_(False)))
    kept = min(k_max, p)
    V = jnp.zeros((d, k_max), jnp.float32).at[:, :kept].set(Y[:, :kept])
    return V, iters


def project_top_k(A: jax.Array, k_valid, k_max: int,
                  point_mask: Optional[jax.Array] = None):
    """Projection of rows of A onto the top-k_valid right singular
    subspace of the masked A, and the iterations it took
    (:func:`top_right_subspace`)."""
    Af = A.astype(jnp.float32)
    Am = Af if point_mask is None else Af * point_mask[:, None]
    V, iters = top_right_subspace(Am, k_valid, k_max)
    rmask = jnp.arange(k_max) < jnp.asarray(k_valid, jnp.int32)
    V = V * rmask[None, :]
    return ((Af @ V) @ V.T).astype(A.dtype), iters


def subspace_project(A: jax.Array, k_valid, k_max: int,
                     point_mask: Optional[jax.Array] = None,
                     iters: int = 12) -> jax.Array:
    """Block power (subspace) iteration on A^T A — the TPU-native variant
    of the SVD projection (matmul-only; no LAPACK on-device)."""
    n, d = A.shape
    Af = A.astype(jnp.float32)
    Am = Af if point_mask is None else Af * point_mask[:, None]

    # Deterministic full-rank start.
    i = jnp.arange(d, dtype=jnp.float32)[:, None]
    j = jnp.arange(k_max, dtype=jnp.float32)[None, :]
    V = jnp.cos(0.37 * (i + 1.0) * (j + 1.0)) + 1e-3 * (i - j)

    def body(_, V):
        W = Am.T @ (Am @ V)
        Q, _ = jnp.linalg.qr(W)
        return Q

    V = jax.lax.fori_loop(0, iters, body, jnp.linalg.qr(V)[0])  # (d, k_max)
    rmask = (jnp.arange(k_max) < jnp.asarray(k_valid, jnp.int32))
    V = V * rmask[None, :]
    return ((Af @ V) @ V.T).astype(A.dtype)


class LocalKMeansResult(NamedTuple):
    centers: jax.Array       # (k_max, d)  Theta^(z)
    center_mask: jax.Array   # (k_max,) bool
    assign: jax.Array        # (n,) int32 local cluster ids, -1 masked
    core_counts: jax.Array   # (k_max,) |S_r| from the 1/3-margin step


class LocalPrepared(NamedTuple):
    """Steps 1-3 of Algorithm 1: the core-set re-centered seeds that the
    step-4 convergence loop (now fused with the Theorem 3.2 attach in
    ``core.lloyd.lloyd_attach`` on the serve path) starts from."""
    theta: jax.Array         # (k_max, d) f32 core-set means
    center_mask: jax.Array   # (k_max,) bool
    core_counts: jax.Array   # (k_max,) |S_r| from the 1/3-margin step
    proj_iters: jax.Array    # () int32 step-1 iterations (0: fixed-count)


def split_local_kw(local_kw: dict):
    """Split a ``local_kmeans``-style kwargs dict into the kwargs of
    :func:`local_prepare` (steps 1-3) and the step-4 ``max_iters``
    bound consumed by the fused solve+attach."""
    kw = dict(local_kw)
    return kw, int(kw.pop("max_iters", 100))


def local_prepare(key: jax.Array, A: jax.Array, *, k_max: int,
                  k_valid: Optional[jax.Array] = None,
                  point_mask: Optional[jax.Array] = None,
                  approx_iters: int = 8,
                  use_subspace_iteration: bool = False) -> LocalPrepared:
    """Algorithm 1 steps 1-3 on one device: spectral projection,
    k-means++ + approximate Lloyd on the projected data, and the
    1/3-margin core-set re-centering. Bitwise-identical to the first
    three steps of :func:`local_kmeans` (it IS them, factored out)."""
    n, d = A.shape
    kv = jnp.asarray(k_max if k_valid is None else k_valid, jnp.int32)
    pm = jnp.ones((n,), bool) if point_mask is None else point_mask

    # -- Step 1: spectral projection.
    if use_subspace_iteration:
        Ahat = subspace_project(A, kv, k_max, point_mask=pm)
        proj_iters = jnp.int32(0)
    else:
        Ahat, proj_iters = project_top_k(A, kv, k_max, point_mask=pm)

    # -- Step 2: approximation algorithm on projected data.
    nu, cmask = kmeans_pp_init(key, Ahat, k_max, point_mask=pm, k_valid=kv)
    nu = lloyd(Ahat, nu, center_mask=cmask, point_mask=pm,
               max_iters=approx_iters).centers

    # -- Step 3: 1/3-margin core sets (distances, not squared distances).
    d2 = ops.pairwise_sq_dists(Ahat, nu)
    d2 = jnp.where(cmask[None, :], d2, MASKED_DIST)
    dd = jnp.sqrt(d2)
    r = jnp.argmin(dd, axis=1)
    dmin = jnp.min(dd, axis=1)
    second = jnp.min(
        jnp.where(jax.nn.one_hot(r, k_max, dtype=bool), jnp.inf, dd), axis=1)
    in_core = (dmin <= second / 3.0) & pm
    core_assign = jnp.where(in_core, r, -1)
    theta, core_counts = update_centers(A.astype(jnp.float32), core_assign,
                                        k_max, nu.astype(jnp.float32))
    return LocalPrepared(theta, cmask, core_counts, proj_iters)


def local_kmeans(key: jax.Array, A: jax.Array, *, k_max: int,
                 k_valid: Optional[jax.Array] = None,
                 point_mask: Optional[jax.Array] = None,
                 approx_iters: int = 8, max_iters: int = 100,
                 use_subspace_iteration: bool = False) -> LocalKMeansResult:
    """Algorithm 1 on one device. ``k_max`` static; ``k_valid`` may be a
    traced per-device k^(z) <= k_max."""
    n, d = A.shape
    pm = jnp.ones((n,), bool) if point_mask is None else point_mask
    prep = local_prepare(key, A, k_max=k_max, k_valid=k_valid,
                         point_mask=pm, approx_iters=approx_iters,
                         use_subspace_iteration=use_subspace_iteration)

    # -- Step 4: Lloyd on the original data until convergence.
    res = lloyd(A.astype(jnp.float32), prep.theta,
                center_mask=prep.center_mask, point_mask=pm,
                max_iters=max_iters)
    return LocalKMeansResult(res.centers.astype(A.dtype), prep.center_mask,
                             res.assign, prep.core_counts)


def _batched(fn, keys, data, k_max, k_valid, point_mask, kw):
    wrapped = lambda key, A, kv, pm: fn(
        key, A, k_max=k_max, k_valid=kv, point_mask=pm, **kw)
    Z = data.shape[0]
    if k_valid is None:
        k_valid = jnp.full((Z,), k_max, jnp.int32)
    if point_mask is None:
        point_mask = jnp.ones(data.shape[:2], bool)
    return jax.vmap(wrapped)(keys, data, k_valid, point_mask)


def batched_local_kmeans(keys, data, *, k_max: int, k_valid=None,
                         point_mask=None, **kw):
    """vmap of Algorithm 1 over the device axis: data (Z, n, d)."""
    return _batched(local_kmeans, keys, data, k_max, k_valid, point_mask, kw)


def batched_local_prepare(keys, data, *, k_max: int, k_valid=None,
                          point_mask=None, **kw):
    """vmap of Algorithm 1 steps 1-3 over the device axis (the serve
    plane pairs this with the fused ``lloyd_attach``)."""
    return _batched(local_prepare, keys, data, k_max, k_valid, point_mask, kw)
