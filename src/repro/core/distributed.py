"""Distributed k-FED: shard_map production path + vmap simulation path.

The paper's protocol maps onto the mesh as follows (DESIGN.md §4):

  * each shard of the ``data`` axis hosts a cohort of federated devices
    (vmapped Algorithm 1 — devices never exchange raw data);
  * the ONE round of communication is literally one ``all_gather`` of the
    (Z, k', d) device-center tensor over the data axis;
  * the server aggregation (steps 2-8 of Algorithm 2, O(Z k' k^2) distance
    computations — Theorem 3.2) is replicated on every shard, which is
    cheaper than any dedicated-server emulation and keeps SPMD semantics.

Both the ``server="replicated"`` and ``server="sharded"`` branches route
through the ONE shared server core in ``core/server.py`` — the sharded
branch swaps in the collective ``ShardedReducer`` for the same greedy
max-min loop and Lloyd round. ``participation`` and
``weight_by_core_counts`` give the shard_map paths the same beyond-paper
scenarios as ``fed/engine.py`` (partial participation with Theorem 3.2
post-hoc attachment; core-set-weighted aggregation).

For comparison benchmarks we also provide ``distributed_lloyd`` — the naive
multi-round parallel Lloyd baseline (one all-reduce of (k, d) sums + (k,)
counts per iteration), whose collective schedule shows T rounds vs k-FED's
single gather.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import kfed as K
from repro.core import lloyd as L
from repro.core import server as S
from repro.core.local_kmeans import batched_local_kmeans
from repro.utils.compat import shard_map as _shard_map


def _axes(axis):
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _flat_axis_index(axes, mesh):
    """Linear shard index for a PartitionSpec((*axes,)) sharding — axes
    listed major-to-minor, matching tiled all_gather ordering."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def kfed_shard_map_impl(mesh, data: jax.Array, k: int, k_prime: int, *,
                        key: jax.Array, axis="data",
                        server: str = "replicated",
                        participation: Optional[jax.Array] = None,
                        weight_by_core_counts: bool = False,
                        k_valid: Optional[jax.Array] = None,
                        point_mask: Optional[jax.Array] = None,
                        **local_kw):
    """One-shot k-FED over a device mesh (engine internal; the
    declarative surface is ``fed.api.Session`` with topology
    ``replicated`` | ``sharded``).

    data: (Z, n, d) with Z divisible by the total shard count. ``axis``
    may be one mesh axis name or a tuple (the federated-device dimension
    is sharded jointly over all of them — e.g. ("data", "model") uses the
    full production pod). ``server``: "replicated" (paper-faithful: ONE
    all-gather of the (Z, k', d) centers, steps 2-8 replicated on every
    chip) or "sharded" (beyond-paper: the server aggregation itself is
    sharded — per-chip traffic drops by the shard count for ~2 MB of tiny
    scalar/(d,) reductions; bitwise-identical output).

    ``participation``: optional (Z,) bool — devices that missed the round
    are excluded from aggregation and attached post-hoc (Theorem 3.2)
    with zero extra communication rounds. ``weight_by_core_counts``
    weights the server's Lloyd round by the Algorithm 1 core set sizes.
    Returns (labels (Z, n), tau_centers (k, d) replicated).
    """
    if server not in ("replicated", "sharded"):
        raise ValueError(
            f"kfed_shard_map server={server!r} is invalid: accepted "
            f"values are ['replicated', 'sharded']")
    Z, n, d = data.shape
    axes = _axes(axis)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    assert Z % nshards == 0, (Z, nshards)
    if k_valid is None:
        k_valid = jnp.full((Z,), k_prime, jnp.int32)
    if point_mask is None:
        point_mask = jnp.ones((Z, n), bool)
    keys = jax.random.split(key, Z)
    has_part = participation is not None

    def shard_fn(keys_b, data_b, kv_b, pm_b, *rest):
        part_b = jnp.asarray(rest[0], bool) if has_part else None
        # -- Stage 1: local solves for this shard's cohort of devices.
        loc = batched_local_kmeans(keys_b, data_b, k_max=k_prime,
                                   k_valid=kv_b, point_mask=pm_b, **local_kw)
        # -- Stage 2 (transport prep): participation + weighting masks.
        cmask = (loc.center_mask if part_b is None
                 else loc.center_mask & part_b[:, None])
        w_loc = (S.core_weights(loc.core_counts)
                 if weight_by_core_counts else None)
        zloc = data_b.shape[0]
        if server == "sharded":
            # -- Stage 3': sharded server — only tiny reductions cross
            # chips (k scalar pairs + k (d,) psums + one (k, d) psum).
            kz_all = jax.lax.all_gather(
                jnp.sum(cmask, axis=1).astype(jnp.int32),
                axes, axis=0, tiled=True)                  # (Z,)
            sep_all = jax.lax.all_gather(
                S.report_separation(loc.centers, cmask),
                axes, axis=0, tiled=True)                  # (Z,)
            base = _flat_axis_index(axes, mesh) * zloc * k_prime
            _, tau, my = S.aggregate_sharded(loc.centers, cmask, kz_all,
                                             sep_all, k, axes, base,
                                             weights_loc=w_loc)
        else:
            # -- The one-shot communication: gather centers + masks.
            all_centers = jax.lax.all_gather(loc.centers, axes, axis=0,
                                             tiled=True)   # (Z, k', d)
            all_mask = jax.lax.all_gather(cmask, axes, axis=0,
                                          tiled=True)       # (Z, k')
            all_w = (None if w_loc is None else
                     jax.lax.all_gather(w_loc, axes, axis=0, tiled=True))
            # -- Stage 3: replicated shared server aggregation.
            agg = S.aggregate(all_centers, all_mask, k, weights=all_w)
            tau = agg.tau_centers
            my = jax.lax.dynamic_slice_in_dim(
                agg.center_labels, _flat_axis_index(axes, mesh) * zloc,
                zloc, 0)
        if part_b is not None:
            # Theorem 3.2 post-hoc attachment of this shard's absent
            # devices — purely local against the replicated tau centers.
            my = S.attach_absent_devices(my, loc.centers,
                                         loc.center_mask, tau, part_b)
        # -- Stage 4: induced labeling (Definition 3.3).
        labels_b = S.induced_labels(my, loc.assign)
        return labels_b, tau

    in_specs = [P(axes)] * (5 if has_part else 4)
    fn = _shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(axes), P()))
    args = (keys, data, k_valid, point_mask)
    if has_part:
        args += (jnp.asarray(participation, bool),)
    return fn(*args)


def kfed_shard_map(mesh, data: jax.Array, k: int, k_prime: int, *,
                   key: jax.Array, axis="data", server: str = "replicated",
                   participation: Optional[jax.Array] = None,
                   weight_by_core_counts: bool = False,
                   k_valid: Optional[jax.Array] = None,
                   point_mask: Optional[jax.Array] = None,
                   **local_kw):
    """Deprecated: use ``fed.api.Session`` with
    ``FederationPlan(topology="replicated" | "sharded")`` (this shim
    routes through it with bitwise-identical results). Returns
    (labels (Z, n), tau_centers (k, d) replicated)."""
    from repro.fed import api
    from repro.utils.deprecation import warn_legacy
    warn_legacy("core.distributed.kfed_shard_map", "Session.run")
    if server not in ("replicated", "sharded"):
        raise ValueError(
            f"kfed_shard_map server={server!r} is invalid: accepted "
            f"values are ['replicated', 'sharded']")
    plan = api.FederationPlan(
        k=k, k_prime=k_prime, d=int(data.shape[-1]), topology=server,
        mesh_axes=_axes(axis),
        weight_by_core_counts=weight_by_core_counts,
        local_kw=dict(local_kw))
    r = api.Session(plan, mesh=mesh).run(
        key, data, participation=participation, k_valid=k_valid,
        point_mask=point_mask)
    return r.labels, r.tau_centers


def assign_new_device_shard(mesh, new_data: jax.Array, tau_centers: jax.Array,
                            k_prime: int, *, key: jax.Array, **local_kw):
    """A device joining after the fact (Theorem 3.2): local solve + O(k'k)
    nearest-center matching against the retained server centers. No
    communication with any other device."""
    from repro.core.local_kmeans import local_kmeans
    loc = local_kmeans(key, new_data, k_max=k_prime, **local_kw)
    lbl = K.assign_new_device(loc.centers, loc.center_mask, tau_centers)
    return K.induced_labels(lbl[None], loc.assign[None])[0]


def distributed_lloyd(mesh, data: jax.Array, k: int, *, key: jax.Array,
                      iters: int = 25, axis="data", init_sub: int = 64):
    """Naive multi-round distributed k-means baseline (Section 4.2.1,
    "Communication-Efficiency"): parallel assignment + one all-reduce of
    per-cluster (sums, counts) per Lloyd round. data: (Z, n, d)."""
    Z, n, d = data.shape
    axes = _axes(axis)

    def shard_fn(data_b):
        x = data_b.reshape(-1, d).astype(jnp.float32)
        xg = jax.lax.all_gather(x, axes, axis=0, tiled=True)
        # Replicated deterministic init: k-means++ on a fixed subsample.
        sub = xg[:: max(1, xg.shape[0] // (init_sub * k))][: init_sub * k]
        c0, _ = L.kmeans_pp_init(key, sub, k)

        def body(c, _):
            a, _ = L.assign_points(x, c)
            sums, cnt = _sums(x, a, k)
            sums = jax.lax.psum(sums, axes)      # the per-round collective
            cnt = jax.lax.psum(cnt, axes)
            new = sums / jnp.maximum(cnt, 1.0)[:, None]
            c = jnp.where((cnt > 0)[:, None], new, c)
            return c, None

        c, _ = jax.lax.scan(body, c0, None, length=iters)
        a, _ = L.assign_points(x, c)
        return a.reshape(data_b.shape[:2]), c

    fn = _shard_map(shard_fn, mesh=mesh, in_specs=(P(axes),),
                    out_specs=(P(axes), P()))
    return fn(data)


def _sums(x, a, k):
    from repro.kernels import ops
    return ops.kmeans_update(x, a, k)


def simulate_kfed(key, device_data, k, k_prime, **kw):
    """Deprecated alias of the vmap simulation path — same numerics as
    the shard_map path (see tests/test_distributed.py); use
    ``fed.api.Session`` with the default ``simulated`` topology."""
    from repro.utils.deprecation import warn_legacy
    warn_legacy("core.distributed.simulate_kfed", "Session.run")
    return K._kfed_impl(key, device_data, k, k_prime, **kw)
