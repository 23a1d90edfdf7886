"""Masked k-means primitives shared by Algorithm 1 (local) and Algorithm 2
(server) of k-FED.

Everything here is fixed-shape and mask-driven so it can be vmapped over
federated devices with heterogeneous ``k^(z)`` and ``n^(z)`` (padded points
carry ``point_mask == False``; padded centers carry ``center_mask ==
False``). This is the TPU-native adaptation of the paper's per-device
variable-size problems (DESIGN.md §4).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import HIGHEST


def assign_points(x: jax.Array, centers: jax.Array,
                  center_mask: Optional[jax.Array] = None,
                  point_mask: Optional[jax.Array] = None):
    """Nearest-center assignment; invalid points get label -1.

    Returns (assign (n,) int32, min_sq_dist (n,) f32).
    """
    idx, mind = ops.assign_argmin(x, centers, center_mask)
    if point_mask is not None:
        idx = jnp.where(point_mask, idx, -1)
        mind = jnp.where(point_mask, mind, 0.0)
    return idx, mind


def update_centers(x: jax.Array, assign: jax.Array, k: int,
                   old_centers: jax.Array):
    """Mean of assigned points per center; empty centers keep old value."""
    sums, cnt = ops.kmeans_update(x, assign, k)
    new = sums / jnp.maximum(cnt, 1.0)[:, None]
    new = jnp.where((cnt > 0)[:, None], new, old_centers.astype(jnp.float32))
    return new.astype(old_centers.dtype), cnt


def kmeans_cost(x: jax.Array, centers: jax.Array,
                center_mask: Optional[jax.Array] = None,
                point_mask: Optional[jax.Array] = None) -> jax.Array:
    """The k-means objective phi (eq. 1) of ``x`` against ``centers``."""
    _, mind = assign_points(x, centers, center_mask, point_mask)
    return jnp.sum(mind)


class LloydResult(NamedTuple):
    centers: jax.Array      # (k, d)
    assign: jax.Array       # (n,) int32, -1 for masked points
    iters: jax.Array        # ()
    converged: jax.Array    # () bool


def lloyd(x: jax.Array, centers0: jax.Array, *,
          center_mask: Optional[jax.Array] = None,
          point_mask: Optional[jax.Array] = None,
          max_iters: int = 100) -> LloydResult:
    """Lloyd iterations until the assignment is stable (or max_iters).

    This is the convergence loop of step 4 of Algorithm 1; with
    ``max_iters=1`` it is the single Lloyd round of step 7 of Algorithm 2.
    """
    k = centers0.shape[0]
    a0 = jnp.full((x.shape[0],), -2, jnp.int32)

    def cond(state):
        _, _, it, done = state
        return (~done) & (it < max_iters)

    def body(state):
        centers, prev, it, _ = state
        a, _ = assign_points(x, centers, center_mask, point_mask)
        centers, _ = update_centers(x, a, k, centers)
        return centers, a, it + 1, jnp.all(a == prev)

    centers, assign, iters, done = jax.lax.while_loop(
        cond, body, (centers0, a0, jnp.int32(0), jnp.bool_(False)))
    # One final assignment against the final centers.
    assign, _ = assign_points(x, centers, center_mask, point_mask)
    return LloydResult(centers, assign, iters, done)


def lloyd_attach(x: jax.Array, centers0: jax.Array, tau: jax.Array, *,
                 center_mask: Optional[jax.Array] = None,
                 point_mask: Optional[jax.Array] = None,
                 max_iters: int = 100, serve_dtype: str = "f32"):
    """FUSED serve step (DESIGN.md §13): the ``lloyd`` convergence loop
    of Algorithm 1 step 4, the Theorem 3.2 attach of its converged
    centers against ``tau``, and the Definition 3.3 induced point
    labels — one kernel dispatch per request batch instead of three.

    Batched: x (B, n, d), centers0 (B, k', d), tau (k, d) shared.
    Returns (labels (B, n) i32 — tau-indexed, -1 for masked points;
    min_sq_dist (B, n) f32; centers (B, k', d) f32; center_labels
    (B, k') i32). With ``serve_dtype="f32"`` the outputs are bitwise
    identical to the staged ``lloyd`` -> ``server.assign_new_device``
    -> ``server.induced_labels`` composition; ``"bf16"`` stores
    x/centers/tau in bfloat16 with f32 accumulation (tolerance-bounded,
    see tests/test_solve_attach.py).
    """
    return ops.solve_attach(x, centers0, tau, center_mask, point_mask,
                            max_iters=max_iters, dtype=serve_dtype)


def kmeans_pp_init(key: jax.Array, x: jax.Array, k: int, *,
                   point_mask: Optional[jax.Array] = None,
                   k_valid: Optional[jax.Array] = None):
    """k-means++ seeding (the "standard approximation algorithm" of
    Algorithm 1 step 2), masked and fixed-shape.

    Picks ``k_valid <= k`` centers (rest zero / masked out). Returns
    (centers (k, d), center_mask (k,) bool).
    """
    n, d = x.shape
    pm = jnp.ones((n,), bool) if point_mask is None else point_mask
    kv = jnp.asarray(k if k_valid is None else k_valid, jnp.int32)
    xf = jnp.asarray(x, jnp.float32)  # accept numpy inputs (bench paths)

    keys = jax.random.split(key, k)
    logits0 = jnp.where(pm, 0.0, -jnp.inf)
    i0 = jax.random.categorical(keys[0], logits0)
    c0 = xf[i0]
    centers = jnp.zeros((k, d), jnp.float32).at[0].set(c0)
    mind2 = jnp.where(pm, jnp.sum((xf - c0) ** 2, axis=1), 0.0)

    def body(carry, inp):
        centers, mind2 = carry
        t, kt = inp
        w = jnp.where(pm, mind2, 0.0)
        has_mass = jnp.any(w > 0)
        logits = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)
        logits = jnp.where(has_mass, logits, logits0)
        i = jax.random.categorical(kt, logits)
        newc = xf[i]
        take = t < kv
        centers = jnp.where(take, centers.at[t].set(newc), centers)
        d2 = jnp.sum((xf - newc) ** 2, axis=1)
        mind2 = jnp.where(take, jnp.minimum(mind2, d2), mind2)
        return (centers, mind2), None

    (centers, _), _ = jax.lax.scan(
        body, (centers, mind2), (jnp.arange(1, k), keys[1:]))
    center_mask = jnp.arange(k) < kv
    return centers.astype(x.dtype), center_mask


class LocalReducer:
    """Reduction strategy for a server that owns the full point set (the
    replicated execution: argmax/fetch/sum are plain local ops). The
    sharded execution substitutes collective equivalents — see
    ``core/server.ShardedReducer``; the greedy loop itself is shared."""

    def argmax(self, vals: jax.Array) -> jax.Array:
        return jnp.argmax(vals).astype(jnp.int32)

    def fetch_row(self, points: jax.Array, idx: jax.Array) -> jax.Array:
        return points[idx]

    def psum(self, x: jax.Array) -> jax.Array:
        return x


def maxmin_grow(pf: jax.Array, valid: jax.Array, chosen: jax.Array,
                mind2: jax.Array, count0: jax.Array, k: int,
                reducer=None) -> jax.Array:
    """The greedy farthest-point growth loop (steps 4-6 of Algorithm 2),
    shared by every server execution path. ``chosen`` holds the already
    selected (global) indices in slots < count0; ``mind2`` the distance of
    every local point to the current set M (-inf for invalid points).

    Incremental update via the matmul identity ||x||^2 - 2 x.c + ||c||^2
    (one read of ``pf`` per iteration instead of materializing the
    broadcast (x - c)^2). ``reducer`` supplies argmax / row-fetch — local
    for the replicated server, collective for the sharded one.
    """
    reducer = reducer or LocalReducer()
    p2 = jnp.sum(pf * pf, axis=1)                         # (m,)

    def body(t, carry):
        chosen, mind2 = carry
        grow = t >= count0
        cand = reducer.argmax(mind2)
        chosen = jnp.where(grow, chosen.at[t].set(cand), chosen)
        c = reducer.fetch_row(pf, cand)
        nd = jnp.maximum(p2 - 2.0 * jnp.matmul(pf, c, precision=HIGHEST)
                         + jnp.sum(c * c), 0.0)
        nd = jnp.where(valid, nd, -jnp.inf)
        mind2 = jnp.where(grow, jnp.minimum(mind2, nd), mind2)
        return chosen, mind2

    chosen, _ = jax.lax.fori_loop(0, k, body, (chosen, mind2))
    return chosen


def maxmin_seed(points: jax.Array, valid: jax.Array, init_sel: jax.Array,
                k: int) -> jax.Array:
    """Farthest-point (max-min) seeding, steps 2-6 of Algorithm 2.

    Starts from the already-selected set ``init_sel`` (one device's local
    centers, per the paper: "Pick any z and let M <- Theta^(z)") and
    greedily adds the point farthest from M until |M| = k.

    points: (m, d); valid/init_sel: (m,) bool. Returns chosen indices (k,).
    """
    pf = points.astype(jnp.float32)

    # Initial selected indices, in order (stable: selected first).
    order = jnp.argsort(jnp.where(init_sel & valid, 0, 1),
                        stable=True)
    count0 = jnp.sum(init_sel & valid).astype(jnp.int32)
    chosen = jnp.where(jnp.arange(k) < count0, order[:k], -1)

    # Distance of every point to the initial set M — against the <= k
    # initial points only (never the full (m, m) pairwise matrix: at
    # Z=4096, k'=16 that is a 17 GB intermediate; §Perf k-FED iter 1).
    init_pts = pf[order[:k]]                              # (k, d)
    init_ok = ((init_sel & valid)[order[:k]])             # (k,)
    d2 = ops.pairwise_sq_dists(pf, init_pts)              # (m, k)
    mind2 = jnp.min(jnp.where(init_ok[None, :], d2, jnp.inf), axis=1)
    mind2 = jnp.where(valid, mind2, -jnp.inf)  # invalid never picked

    return maxmin_grow(pf, valid, chosen, mind2, count0, k)
