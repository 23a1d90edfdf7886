"""The ONE k-FED server implementation (Algorithm 2 steps 2-8).

Every execution path routes through this module (DESIGN.md §4):

  * ``core.kfed.aggregate``          -> :func:`aggregate`
  * shard_map ``server="replicated"``-> :func:`aggregate` (after gather)
  * shard_map ``server="sharded"``   -> :func:`aggregate_sharded`

The replicated and sharded executions differ ONLY in the reducer handed
to the shared greedy max-min loop (``lloyd.maxmin_grow``) and the shared
one-round Lloyd update (:func:`lloyd_round`); the protocol arithmetic
exists exactly once. The optional per-center ``weights`` (the |S_r| core
set sizes from Algorithm 1) turn the Lloyd round into a weighted mean so
large devices are not diluted by small ones.

On top of the one-shot entry point the server exposes an incremental
fold — :func:`init_state` / :func:`aggregate_incremental` /
:func:`finalize` — so device cohorts can report asynchronously, in any
order, across multiple calls. The fold buffers reports keyed by device
id (the sufficient statistic of the one-shot protocol), which makes the
finalized aggregate bitwise independent of arrival order; the
non-commutative max-min seeding is deferred to :func:`finalize`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import lloyd as L
from repro.kernels import ops
from repro.kernels.ref import HIGHEST


class KFedAggregate(NamedTuple):
    seeds_idx: jax.Array       # (k,) indices into flattened (Z*k') centers
    seed_centers: jax.Array    # (k, d) the set M
    tau_centers: jax.Array     # (k, d) mu(tau_r) after the one Lloyd round
    center_labels: jax.Array   # (Z, k') tau-label of each device center, -1 pad
    z0: jax.Array              # () the device whose centers seeded M


# ---------------------------------------------------------------------------
# Shared stages.
# ---------------------------------------------------------------------------


def lloyd_round(x: jax.Array, fm: jax.Array, M: jax.Array, k: int, *,
                reducer=None, weights: Optional[jax.Array] = None,
                center_mask: Optional[jax.Array] = None):
    """Steps 7-8 of Algorithm 2: ONE Lloyd round of the device centers
    against the seeded set M. With ``weights`` (per-point, e.g. core set
    sizes |S_r|) the update is the weighted mean. ``reducer.psum``
    combines partial (sums, counts) across server shards (identity for
    the replicated server).

    Returns (tau (k, d) f32, labels (m,) int32).
    """
    reducer = reducer or L.LocalReducer()
    labels, _ = L.assign_points(x, M, center_mask=center_mask, point_mask=fm)
    w = None if weights is None else weights.astype(jnp.float32)
    sums, cnt = ops.kmeans_update(x.astype(jnp.float32), labels, k, w)
    sums = reducer.psum(sums)
    cnt = reducer.psum(cnt)
    # Divide by the ACTUAL mass whenever it is positive. Historically
    # this clamped to max(cnt, 1): identical for unweighted counts and
    # the >= 1 core-set weights, but fractional masses (decayed fold
    # weights can land in (0, 1)) would silently shrink the mean toward
    # the origin instead of averaging — and a zero-mass center must
    # keep its seed coordinates, never divide 0/0 into NaN.
    tau = jnp.where((cnt > 0)[:, None],
                    sums / jnp.where(cnt > 0, cnt, 1.0)[:, None],
                    M.astype(jnp.float32))
    return tau, labels


def induced_labels(center_labels: jax.Array,
                   local_assign: jax.Array) -> jax.Array:
    """Definition 3.3: point i on device z with local cluster s gets label
    tau(theta_s^(z)). center_labels: (Z, k'), local_assign: (Z, n)."""
    safe = jnp.clip(local_assign, 0, center_labels.shape[1] - 1)
    lbl = jnp.take_along_axis(center_labels, safe, axis=1)
    return jnp.where(local_assign >= 0, lbl, -1)


def assign_new_device(new_centers: jax.Array, new_mask: jax.Array,
                      ref_centers: jax.Array) -> jax.Array:
    """Theorem 3.2: a device joining after clustering is assigned by
    nearest-neighbor matching of its local centers against the k retained
    server centers — O(k' * k) distance computations, no other device
    involved. new_centers: (k', d); ref_centers: (k, d)."""
    labels, _ = L.assign_points(new_centers, ref_centers,
                                point_mask=new_mask)
    return labels


def core_weights(core_counts: jax.Array) -> jax.Array:
    """Per-center weights for the server Lloyd round: the Algorithm 1
    core set sizes |S_r|, clamped to >= 1 so a degenerate (empty-core)
    center still anchors its own cluster."""
    return jnp.maximum(core_counts.astype(jnp.float32), 1.0)


def attach_absent_devices(center_labels: jax.Array,
                          device_centers: jax.Array,
                          center_mask: jax.Array,
                          tau_centers: jax.Array,
                          participation: jax.Array) -> jax.Array:
    """Post-hoc attachment of devices that missed the round: their center
    labels come from the Theorem 3.2 nearest-center rule against the
    retained tau centers, with zero extra communication rounds."""
    post = jax.vmap(lambda c, m: assign_new_device(c, m, tau_centers))(
        device_centers, center_mask)
    return jnp.where(participation[:, None], center_labels, post)


@jax.jit
def report_separation(centers: jax.Array, mask: jax.Array) -> jax.Array:
    """(Z,) f32: the smallest squared distance between two valid centers
    of each device's report (+inf with fewer than two). A device whose
    points miss one of its k^(z) components splits another in its local
    solve, and two of its centers then lie within one component's noise.
    """
    c = centers.astype(jnp.float32)
    sq = jnp.sum(c * c, axis=-1)                          # (Z, k')
    gram = jnp.einsum("zid,zjd->zij", c, c, precision=HIGHEST)
    d2 = sq[:, :, None] + sq[:, None, :] - 2.0 * gram
    kp = centers.shape[1]
    pair = (mask[:, :, None] & mask[:, None, :]
            & ~jnp.eye(kp, dtype=bool)[None])
    return jnp.min(jnp.where(pair, d2, jnp.inf), axis=(1, 2))


@jax.jit
def seed_device(kz: jax.Array, separation: jax.Array) -> jax.Array:
    """Algorithm 2's "pick any z": of the devices with the most local
    clusters, the one whose closest two centers lie farthest apart
    (first among ties), so that M does not spend two seeds on one
    component of a report that split it."""
    most = kz == jnp.max(kz)
    return jnp.argmax(jnp.where(most, separation, -1.0)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Replicated execution (also the vmap simulation path).
# ---------------------------------------------------------------------------


_traces = 0   # times JAX has traced Algorithm 2's program (see traces)


def traces() -> int:
    """How many times :func:`aggregate`'s program has been traced in this
    process: its Python body runs only when JAX traces it, once per
    (shape, ``k``, weights present) and again after ``ops.set_backend``,
    never on a cached call. The serving layer reads it around each refresh
    (``refresh_builds``)."""
    return _traces


@functools.partial(jax.jit, static_argnames=("k",))
def aggregate(device_centers: jax.Array, center_mask: jax.Array, k: int, *,
              weights: Optional[jax.Array] = None) -> KFedAggregate:
    """Steps 2-8 of Algorithm 2 on a full (Z, k', d) center tensor, as
    one program compiled once per shape (``k`` static; centers, mask and
    weights traced). The round, ``finalize`` and every refresh run this
    same compiled arithmetic, so they agree bitwise.

    ``weights``: optional (Z, k') per-center weights for the Lloyd round
    (masked centers never contribute regardless — their labels are -1).
    """
    global _traces
    _traces += 1
    Z, kp, d = device_centers.shape
    flat = device_centers.reshape(Z * kp, d)
    fm = center_mask.reshape(Z * kp)

    # "Pick any z": the device with most local clusters (maximizes the
    # seeded set, minimizes max-min iterations), best separated first.
    kz = jnp.sum(center_mask, axis=1)
    z0 = seed_device(kz, report_separation(device_centers, center_mask))
    init_sel = ((jnp.arange(Z) == z0)[:, None] & center_mask).reshape(-1)

    seeds_idx = L.maxmin_seed(flat, fm, init_sel, k)
    M = flat[seeds_idx]

    w = None if weights is None else weights.reshape(Z * kp)
    tau, labels = lloyd_round(flat, fm, M, k, weights=w)
    return KFedAggregate(seeds_idx, M, tau.astype(device_centers.dtype),
                         labels.reshape(Z, kp), z0)


# ---------------------------------------------------------------------------
# Sharded execution: same stages, collective reducer.
# ---------------------------------------------------------------------------

_BIG = jnp.int32(2 ** 30)


class ShardedReducer:
    """Collective counterpart of ``lloyd.LocalReducer``: each shard owns
    rows [base, base + m_loc) of the global point set. argmax resolves
    ties to the smallest global index (= first occurrence), matching the
    replicated ``jnp.argmax``."""

    def __init__(self, axes, base, m_loc):
        self.axes, self.base, self.m_loc = axes, base, m_loc

    def argmax(self, vals: jax.Array) -> jax.Array:
        lmax = jnp.max(vals)
        larg = jnp.argmax(vals).astype(jnp.int32)
        gmax = jax.lax.pmax(lmax, self.axes)
        return jax.lax.pmin(
            jnp.where(lmax >= gmax, self.base + larg, _BIG), self.axes)

    def fetch_row(self, points: jax.Array, gidx: jax.Array) -> jax.Array:
        mine = (gidx >= self.base) & (gidx < self.base + self.m_loc)
        row = jnp.clip(gidx - self.base, 0, self.m_loc - 1)
        return jax.lax.psum(jnp.where(mine, points[row], 0.0), self.axes)

    def fetch_rows(self, points: jax.Array, gidx: jax.Array) -> jax.Array:
        """(k,) global indices -> (k, d) rows, owner contributes."""
        mine = (gidx >= self.base) & (gidx < self.base + self.m_loc)
        rows = jnp.clip(gidx - self.base, 0, self.m_loc - 1)
        return jax.lax.psum(
            jnp.where(mine[:, None], points[rows], 0.0), self.axes)

    def psum(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self.axes)


def aggregate_sharded(centers_loc, mask_loc, kz_all, sep_all, k, axes,
                      base, *, weights_loc: Optional[jax.Array] = None):
    """Steps 2-8 of Algorithm 2 with the server itself sharded: each chip
    owns its m_loc = Z_loc*k' slice of the device centers; the greedy
    max-min runs as (local argmax -> two scalar all-reduces -> (d,) psum
    of the winning center) per iteration, so per-chip HBM traffic is
    m_loc*d per iteration instead of Z*k'*d (§Perf k-FED iteration 2).
    Selection order matches the replicated server (first-occurrence
    argmax = smallest global index among ties).

    centers_loc: (Z_loc, k', d); mask_loc: (Z_loc, k'); kz_all and
    sep_all: (Z,) every device's valid-center count and
    :func:`report_separation`; ``base`` = this shard's first global row
    index.
    Returns (M (k, d), tau_centers (k, d), my_labels (Z_loc, k')).
    """
    Z_loc, kp, d = centers_loc.shape
    m_loc = Z_loc * kp
    pf = centers_loc.reshape(m_loc, d).astype(jnp.float32)
    fm = mask_loc.reshape(m_loc)
    shard = base // m_loc
    red = ShardedReducer(axes, base, m_loc)

    # "Pick any z": the same device as the replicated server picks.
    z0 = seed_device(kz_all, sep_all)
    own_rows = jnp.arange(m_loc) // kp == (z0 - shard * Z_loc)
    init_loc = own_rows & fm                              # (m_loc,)
    count0 = red.psum(jnp.sum(init_loc).astype(jnp.int32))

    # Initial chosen indices (global, ascending) and their coordinates.
    cand = jnp.where(init_loc, base + jnp.arange(m_loc, dtype=jnp.int32),
                     _BIG)
    cand = jnp.sort(cand)[:k] if m_loc >= k else jnp.sort(
        jnp.pad(cand, (0, k - m_loc), constant_values=_BIG))[:k]
    chosen0 = jax.lax.pmin(cand, axes)                    # (k,) owner wins
    # owner gathers its init rows into slot order via a one-hot matmul;
    # others contribute 0. At most one row feeds each slot, and a
    # fixed-order dot reduction is deterministic — the former
    # scatter-add accumulated colliding zero rows in
    # implementation-defined order (flagged by the §15 determinism
    # auditor's float-scatter-add rule).
    slot_of = jnp.cumsum(init_loc.astype(jnp.int32)) - 1
    sel = ((slot_of[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :])
           & init_loc[:, None]).astype(jnp.float32)       # (m_loc, k)
    M0 = jax.lax.dot_general(sel, jnp.where(init_loc[:, None], pf, 0.0),
                             (((0,), (0,)), ((), ())),
                             precision=HIGHEST)           # (k, d)
    M0 = red.psum(M0)

    d2 = ops.pairwise_sq_dists(pf, M0)                    # (m_loc, k)
    ok = jnp.arange(k) < count0
    mind2 = jnp.min(jnp.where(ok[None, :], d2, jnp.inf), axis=1)
    mind2 = jnp.where(fm, mind2, -jnp.inf)
    chosen = jnp.where(jnp.arange(k) < count0, chosen0, -1)

    # The SAME greedy growth loop as the replicated server, with the
    # collective reducer swapped in.
    chosen = L.maxmin_grow(pf, fm, chosen, mind2, count0, k, reducer=red)

    # Assemble M from owners; one local Lloyd assignment + global update.
    M = red.fetch_rows(pf, chosen)
    w = None if weights_loc is None else weights_loc.reshape(m_loc)
    tau, labels = lloyd_round(pf, fm, M, k, reducer=red, weights=w,
                              center_mask=chosen >= 0)
    return M, tau.astype(centers_loc.dtype), labels.reshape(Z_loc, kp)


# ---------------------------------------------------------------------------
# Incremental (asynchronous staged-arrival) server.
# ---------------------------------------------------------------------------


class ServerState(NamedTuple):
    """Fold state of the asynchronous server: device reports buffered by
    device id. Because the buffer position is the device id, folding the
    same cohorts in ANY order yields the same state — and therefore a
    bitwise-identical finalized clustering.

    ``epoch`` timestamps each slot with the request-id epoch its report
    was folded at (default: the id itself). It is inert metadata until a
    finalize asks for ``decay`` — the lazy exponential down-weighting of
    the drift layer (DESIGN.md §14) — so the fold stays one scatter and
    non-drift paths are untouched by its presence."""
    centers: jax.Array    # (Z, k', d) buffered Theta^(z)
    mask: jax.Array       # (Z, k') center validity of received reports
    weights: jax.Array    # (Z, k') f32 per-center weights (1.0 default)
    received: jax.Array   # (Z,) bool — device has reported this round
    epoch: jax.Array      # (Z,) i32 request-id epoch of the fold


def init_state(Z: int, k_prime: int, d: int,
               dtype=jnp.float32) -> ServerState:
    return ServerState(jnp.zeros((Z, k_prime, d), dtype),
                       jnp.zeros((Z, k_prime), bool),
                       jnp.ones((Z, k_prime), jnp.float32),
                       jnp.zeros((Z,), bool),
                       jnp.zeros((Z,), jnp.int32))


def aggregate_incremental(state: ServerState, device_ids, centers,
                          mask, weights=None, epochs=None) -> ServerState:
    """Fold one cohort's report into the server state.

    device_ids: (B,) int; centers: (B, k', d); mask: (B, k'). Cohorts may
    arrive in any order and across any number of calls; re-delivery of a
    device report is idempotent. ``epochs``: optional (B,) request-id
    epochs stamped on the slots (default: the ids themselves — correct
    whenever the slot IS the request id; policies that remap ids to
    slots must pass the real request ids).
    """
    ids = jnp.asarray(device_ids, jnp.int32)
    w = (jnp.ones(jnp.shape(mask), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    e = ids if epochs is None else jnp.asarray(epochs, jnp.int32)
    # mode="drop": an id beyond the state's capacity is ignored instead
    # of clipping onto (and corrupting) the last slot — the streaming
    # service relies on over-capacity reports being served-not-folded.
    return ServerState(state.centers.at[ids].set(centers, mode="drop"),
                       state.mask.at[ids].set(mask, mode="drop"),
                       state.weights.at[ids].set(w, mode="drop"),
                       state.received.at[ids].set(True, mode="drop"),
                       state.epoch.at[ids].set(e, mode="drop"))


def aggregate_incremental_sharded(state: ServerState, device_ids,
                                  centers, mask, axes,
                                  weights=None, epochs=None) -> ServerState:
    """The collective path of :func:`aggregate_incremental` — the fold
    of the sharded serve plane (DESIGN.md §11).

    Runs INSIDE shard_map: ``state`` is replicated, ``device_ids`` /
    ``centers`` / ``mask`` / ``weights`` are this shard's slice of the
    report batch. The batch is transported with one tiled all_gather —
    O(B·k'·d), the reports themselves, NEVER the O(capacity·k'·d) fold
    state — and then every shard applies the identical scatter through
    :func:`aggregate_incremental`, which stays the single fold
    primitive. Gathering preserves the global batch order, so the
    result is BITWISE identical to folding the unsharded batch.

    Ids at or beyond the state capacity are dropped (the declined /
    padding sentinel of the serve plane); negative ids are not allowed
    — they would wrap per numpy indexing rules.
    """
    ids = jax.lax.all_gather(jnp.asarray(device_ids, jnp.int32), axes,
                             axis=0, tiled=True)
    centers = jax.lax.all_gather(centers, axes, axis=0, tiled=True)
    mask = jax.lax.all_gather(mask, axes, axis=0, tiled=True)
    w = (None if weights is None
         else jax.lax.all_gather(weights.astype(jnp.float32), axes,
                                 axis=0, tiled=True))
    e = (None if epochs is None
         else jax.lax.all_gather(jnp.asarray(epochs, jnp.int32), axes,
                                 axis=0, tiled=True))
    return aggregate_incremental(state, ids, centers, mask, weights=w,
                                 epochs=e)


# ---------------------------------------------------------------------------
# Drift layer: lazy exponential decay + mass-driven split/retire
# (DESIGN.md §14). Pure functions of the fold state — the hot-path
# scatter never pays for any of this.
# ---------------------------------------------------------------------------


def decay_factors(epoch: jax.Array, now_epoch, half_life) -> jax.Array:
    """Per-slot exponential decay 2^(-(now - epoch) / half_life): a slot
    folded ``half_life`` requests ago carries half its original mass.
    Deterministic in (epoch, now_epoch) — replays bitwise."""
    age = (jnp.asarray(now_epoch, jnp.int32)
           - epoch.astype(jnp.int32)).astype(jnp.float32)
    return jnp.exp2(-age / jnp.float32(half_life))


def decayed_evidence(state: ServerState, now_epoch, half_life):
    """The (mask, weights) the drift finalize sees: received reports with
    their fold weights scaled by :func:`decay_factors`. Slots whose
    decayed weight underflows to exactly 0 are masked OUT — a zero-mass
    center must never seed or anchor a cluster (it would divide 0/0 into
    NaN and poison tau on the next refresh)."""
    fac = decay_factors(state.epoch, now_epoch, half_life)
    w = state.weights * fac[:, None]
    mask = state.mask & state.received[:, None] & (w > 0)
    return mask, w


def finalize(state: ServerState, k: int, *, weighted: bool = False,
             decay=None) -> KFedAggregate:
    """Run Algorithm 2 over every report received so far. Devices that
    never reported are masked out (their labels come out -1); attach them
    post-hoc with :func:`attach_absent_devices`.

    ``decay``: optional ``(now_epoch, half_life)`` — weight every slot by
    its exponential age factor (always weighted; ``weighted`` then only
    controls whether the core-count weights also participate, which they
    do by construction since decay scales ``state.weights``).

    The masking here is a few eager element-wise ops, cached by shape;
    Algorithm 2 is :func:`aggregate`'s compiled program. ``now_epoch``
    changes at every refresh, so it stays a traced value: it never
    forces a build."""
    if decay is None:
        mask = state.mask & state.received[:, None]
        return aggregate(state.centers, mask, k,
                         weights=state.weights if weighted else None)
    now_epoch, half_life = decay
    mask, w = decayed_evidence(state, now_epoch, half_life)
    # Zero the masked slots' coordinates as well as their weights: a
    # zero weight alone does not neutralize non-finite garbage (0 * NaN
    # is NaN straight through the weighted Lloyd sums).
    centers = jnp.where(mask[..., None], state.centers,
                        jnp.zeros_like(state.centers))
    return aggregate(centers, mask, k, weights=w)


def center_mass(agg: KFedAggregate, mask: jax.Array,
                weights: jax.Array) -> jax.Array:
    """Per-center attached fold mass: the sum of (decayed) slot weights
    whose device centers labeled into each tau center. (k,) f32."""
    k = agg.tau_centers.shape[0]
    lbl = agg.center_labels.reshape(-1)
    w = jnp.where(mask.reshape(-1) & (lbl >= 0), weights.reshape(-1), 0.0)
    # One-hot matmul segment sum (the kernels/kmeans_update pattern):
    # a float scatter-add over label-derived (colliding) indices sums
    # in implementation-defined order — the drift layer's split/retire
    # decisions threshold this mass, so the reduction must replay
    # bitwise (§15 float-scatter-add rule).
    oh = (lbl[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :]
          ).astype(jnp.float32)                           # (m, k)
    return jax.lax.dot_general(w, oh, (((0,), (0,)), ((), ())),
                               precision=HIGHEST)


def split_retire(flat: jax.Array, fm: jax.Array, agg: KFedAggregate,
                 mass: jax.Array, k: int, *, split_factor: float,
                 retire_frac: float, max_moves: int,
                 weights: Optional[jax.Array] = None):
    """Mass-driven center split/retire at a flush boundary.

    Centers with mass below ``retire_frac`` of the mean are starved;
    centers above ``split_factor`` times the mean are over-massed. Up to
    ``max_moves`` starved centers (poorest first) are RE-SEEDED from the
    residual report of a donor over-massed center (fattest first): the
    donor's farthest attached report — the Algorithm 2 max-min rule
    restricted to one cluster — becomes the new seed, then ONE
    :func:`lloyd_round` re-anchors all k centers. Deterministic: stable
    sorts, first-occurrence argmax, no RNG — split/retire decisions
    replay bitwise from a checkpoint.

    ``flat``: (Z*k', d) device centers; ``fm``: (Z*k',) evidence mask;
    ``weights``: optional (Z*k',) Lloyd weights. Returns
    ``(tau (k, d) f32, moved (k,) bool, donors (k,) i32, n_moves i32)``
    — with zero moves ``tau`` equals ``agg.tau_centers`` exactly.
    """
    mass = mass.astype(jnp.float32)
    mean = jnp.sum(mass) / jnp.float32(k)
    starved = mass < jnp.float32(retire_frac) * mean
    over = mass > jnp.float32(split_factor) * mean
    n_mv = jnp.minimum(
        jnp.minimum(jnp.sum(starved), jnp.sum(over)),
        jnp.int32(max_moves)).astype(jnp.int32)

    # Rank starved ascending by mass, donors descending; pair rank j of
    # each with rank j of the other. jnp.argsort is stable, so ties
    # resolve to the lowest center index — deterministic.
    skey = jnp.where(starved, mass, jnp.inf)
    okey = jnp.where(over, -mass, jnp.inf)
    sorder = jnp.argsort(skey)
    oorder = jnp.argsort(okey).astype(jnp.int32)
    srank = jnp.zeros((k,), jnp.int32).at[sorder].set(
        jnp.arange(k, dtype=jnp.int32))
    donors = oorder[jnp.clip(srank, 0, k - 1)]
    take = starved & (srank < n_mv)

    # Residual re-seed: within each donor cluster, the attached report
    # farthest from its tau center (max-min restricted to the cluster).
    lbl = agg.center_labels.reshape(-1)
    d2 = ops.pairwise_sq_dists(flat.astype(jnp.float32),
                               agg.tau_centers.astype(jnp.float32))
    attached = (lbl[:, None] == jnp.arange(k)[None, :]) & fm[:, None]
    scores = jnp.where(attached, d2, -jnp.inf)
    reseed_idx = jnp.argmax(scores, axis=0)                # (k,) per center
    M1 = jnp.where(take[:, None], flat[reseed_idx[donors]],
                   agg.tau_centers).astype(jnp.float32)

    tau2, _ = lloyd_round(flat, fm, M1, k, weights=weights)
    tau = jnp.where(n_mv > 0, tau2, agg.tau_centers.astype(jnp.float32))
    return tau, take, jnp.where(take, donors, -1), n_mv
