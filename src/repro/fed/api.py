"""The ONE federation API: a declarative ``FederationPlan`` + a
``Session`` lifecycle (DESIGN.md §10).

The paper's pitch is one protocol with many deployment modes — a
one-shot round, partial participation, asynchronous cohort arrival, and
post-hoc Theorem 3.2 attachment. This module is the single surface all
of them are configurations of:

  * ``FederationPlan`` — a frozen, validated spec of the problem
    (k / k' / d), the execution topology (``simulated`` vmap,
    ``replicated`` shard_map server, ``sharded`` collective server +
    mesh axes), aggregation semantics (core-count weighting), the async
    fold, and the streaming-serve layer (pad buckets, batch size,
    refresh cadence, fold-slot admission policy, checkpoint path).
    Validation errors name the offending field and the accepted values
    at construction time, never deep inside tracing.
  * ``Session`` — owns the full lifecycle against one plan:
    ``run`` (the one-shot round, dispatched to the right engine path),
    ``fold``/``finalize`` (asynchronous staged arrival),
    ``attach``/``serve``/``submit``/``flush``/``refresh`` (streaming
    Theorem 3.2 attachment with incremental folding), and
    ``save``/``restore`` (checkpointed crash recovery, bitwise replay).

Every legacy entry point (``core.kfed.kfed``, ``kfed_shard_map``,
``fed.engine.run_round``/``run_round_async``,
``fed.stream.AttachService``, ``launch.serve.make_kfed_attach``) is a
thin deprecation shim over this surface with bitwise-identical results
(tests/test_api.py pins that parity on all three topologies).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, List, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import server
from repro.fed import engine as E
from repro.fed.stream import AttachService, StreamConfig, StreamConfigError

__all__ = ["FederationPlan", "PlanError", "RunResult", "Session",
           "SessionError", "TOPOLOGIES", "plan_from_engine_config"]

TOPOLOGIES = ("simulated", "replicated", "sharded")


class PlanError(ValueError):
    """A FederationPlan field failed validation; the message names the
    field and the accepted values."""


class SessionError(RuntimeError):
    """A Session method was called out of lifecycle order (e.g. serve
    before any round finalized)."""


def _bad(fieldname: str, got: Any, accepted: str) -> None:
    raise PlanError(
        f"FederationPlan.{fieldname}={got!r} is invalid: {accepted}")


@dataclass(frozen=True)
class FederationPlan:
    """Declarative spec of a federated clustering deployment.

    Problem:   ``k`` global clusters, ``k_prime`` per-device center cap,
               ``d`` feature dimension.
    Topology:  ``simulated`` (single-host vmap), ``replicated``
               (shard_map, server replicated per chip after one
               all-gather), or ``sharded`` (the server aggregation
               itself sharded); ``mesh_axes`` names the mesh axes the
               federated-device dimension shards over.
    Semantics: ``weight_by_core_counts`` weights the server Lloyd round
               by Algorithm 1 core-set sizes; ``local_kw`` forwards
               Algorithm 1 options.
    Async:     ``fold_capacity`` bounds the staged-arrival fold state
               (default: the device count of the data).
    Streaming: ``capacity`` fold slots admitted by ``fold_policy``
               (``drop`` | ``lru`` | ``weighted_reservoir``,
               ``policy_seed`` keys the reservoir), requests padded into
               ``bucket_sizes`` point buckets and served ``batch_size``
               at a time, tau re-finalized every ``refresh_every`` folds
               (0 = never) with a ``refresh`` swap mode (``sync`` swaps
               tau immediately; ``async`` double-buffers — the standby
               builds while serving continues and the versioned swap
               commits at the next flush boundary), ``serve_axes`` the
               mesh axes the serve plane shards the request batch over
               (None = single host; dispatched by ``Session.attach`` /
               ``serve``/``flush`` exactly like ``topology`` dispatches
               ``run``), ``autoscale`` the load-adaptive serve-plane
               controller (``off`` keeps the static configuration;
               ``latency`` tracks queue depth both ways;
               ``throughput`` holds full batches across single-flush
               dips — ``batch_size`` becomes the ceiling and
               ``serve_axes`` the shard grant, DESIGN.md §12),
               ``serve_dtype`` the fused solve+attach storage precision
               (``f32`` bitwise vs the staged step; ``bf16`` bfloat16
               storage with f32 accumulation, DESIGN.md §13),
               ``checkpoint`` the default save/restore path.
    Drift:     ``drift`` turns the long-running service's online drift
               adaptation on (DESIGN.md §14): ``off`` (default — every
               path bitwise-identical to a plan without the field),
               ``decay`` (each fold slot's weight decays by
               2^(-age/``drift_half_life``), age in requests since its
               fold; fully-decayed slots drop out of refreshes), or
               ``split_merge`` (decay, plus at refresh boundaries up to
               ``drift_max_moves`` centers starved below
               ``drift_retire_frac`` x mean mass are retired and
               re-seeded from the residual reports of centers above
               ``drift_split_factor`` x mean — committed through the
               TauBuffer as one atomic versioned bump, replayed bitwise
               from checkpoints). Under ``weighted_reservoir`` the
               admission key also uses the decayed mass.
    Heads:     ``heads`` turns on cluster-routed personalization serving
               (DESIGN.md §16): each request's Theorem 3.2 label routes
               it through ONE per-cluster head on the serve plane
               (``off`` default — the plane is bitwise-identical to a
               plan without the field; ``linear`` the affine head; any
               ``configs.list_archs()`` name adopts that architecture's
               REDUCED activation/FFN ratio at width ``d``).
               ``head_arch`` picks the block (``ffn`` | ``transformer``
               — the config-flagged attention head), ``head_capacity``
               sizes the per-cluster dispatch queues as a multiple of
               ``batch_size / k`` (overflowed requests still get
               labels, just no prediction). Head params ride checkpoint
               schema v5; ``Session.serve_predict``/``flush_predict``
               return the predictions.
    Encoder:   ``encoder`` turns on the latent-space ingestion stage
               (DESIGN.md §17): devices submit raw ``(n, seq, d)``
               token/patch sequences and the serve plane encodes them
               (pre-norm zoo blocks at width ``d``, masked-mean pooled)
               ahead of the unchanged solve+attach (``off`` default —
               every path bitwise-identical to a plan without the
               field; any ``configs.list_archs()`` name adopts that
               architecture's REDUCED depth/activation/FFN ratio/head
               counts at width ``d``). ``encode_dtype`` picks f32 or
               bf16 storage (f32 accumulation either way);
               ``encode_seq_len`` caps each point's token-sequence
               length (requests bucket over (n, seq) pad rungs).
               Encoder params ride checkpoint schema v6.
    """
    k: int
    k_prime: int
    d: int
    topology: str = "simulated"
    mesh_axes: Tuple[str, ...] = ("data",)
    weight_by_core_counts: bool = False
    local_kw: Mapping[str, Any] = field(default_factory=dict)
    fold_capacity: Optional[int] = None
    capacity: int = 1024
    batch_size: int = 8
    bucket_sizes: Tuple[int, ...] = (64, 256, 1024)
    refresh_every: int = 0
    refresh: str = "sync"
    autoscale: str = "off"
    serve_axes: Optional[Tuple[str, ...]] = None
    fold_reports: bool = True
    fold_policy: str = "drop"
    policy_seed: int = 0
    serve_dtype: str = "f32"
    drift: str = "off"
    drift_half_life: int = 0
    drift_split_factor: float = 2.0
    drift_retire_frac: float = 0.1
    drift_max_moves: int = 1
    heads: str = "off"
    head_capacity: float = 1.25
    head_arch: str = "ffn"
    encoder: str = "off"
    encode_dtype: str = "f32"
    encode_seq_len: int = 64
    checkpoint: Optional[str] = None

    def __post_init__(self):
        # Plan-only fields first; the problem/streaming fields are
        # validated ONCE, by the StreamConfig this plan lowers to
        # (stream.py __post_init__) — no duplicated rule set to drift.
        if self.topology not in TOPOLOGIES:
            _bad("topology", self.topology,
                 f"accepted values are {list(TOPOLOGIES)}")
        if isinstance(self.mesh_axes, str):
            object.__setattr__(self, "mesh_axes", (self.mesh_axes,))
        if (not self.mesh_axes
                or not all(isinstance(a, str) for a in self.mesh_axes)):
            _bad("mesh_axes", self.mesh_axes,
                 "must be a non-empty tuple of mesh axis names, "
                 "e.g. ('data',) or ('data', 'model')")
        if self.fold_capacity is not None and self.fold_capacity < 1:
            _bad("fold_capacity", self.fold_capacity,
                 "must be None (infer the device count) or an int >= 1")
        if isinstance(self.serve_axes, str):
            object.__setattr__(self, "serve_axes", (self.serve_axes,))
        if self.serve_axes is not None and (
                not self.serve_axes
                or not all(isinstance(a, str) for a in self.serve_axes)):
            _bad("serve_axes", self.serve_axes,
                 "must be None (single-host serving) or a non-empty "
                 "tuple of mesh axis names, e.g. ('data',)")
        if not isinstance(self.local_kw, Mapping):
            _bad("local_kw", self.local_kw,
                 "must be a mapping of Algorithm 1 options")
        try:
            self.stream_config()
        except StreamConfigError as e:
            raise PlanError(str(e).replace("StreamConfig.",
                                           "FederationPlan.")) from None

    # ----------------------------------------------- derived configs --
    def engine_config(self) -> E.EngineConfig:
        return E.EngineConfig(
            k=self.k, k_prime=self.k_prime,
            weight_by_core_counts=self.weight_by_core_counts,
            local_kw=dict(self.local_kw))

    def stream_config(self) -> StreamConfig:
        return StreamConfig(
            k=self.k, k_prime=self.k_prime, d=self.d,
            capacity=self.capacity, batch_size=self.batch_size,
            bucket_sizes=tuple(self.bucket_sizes),
            refresh_every=self.refresh_every, refresh=self.refresh,
            autoscale=self.autoscale, fold_reports=self.fold_reports,
            weight_by_core_counts=self.weight_by_core_counts,
            fold_policy=self.fold_policy, policy_seed=self.policy_seed,
            serve_dtype=self.serve_dtype,
            drift=self.drift, drift_half_life=self.drift_half_life,
            drift_split_factor=self.drift_split_factor,
            drift_retire_frac=self.drift_retire_frac,
            drift_max_moves=self.drift_max_moves,
            heads=self.heads, head_capacity=self.head_capacity,
            head_arch=self.head_arch,
            encoder=self.encoder, encode_dtype=self.encode_dtype,
            encode_seq_len=self.encode_seq_len,
            local_kw=dict(self.local_kw))

    def with_options(self, **kw) -> "FederationPlan":
        """A copy of the plan with fields replaced (re-validated)."""
        return replace(self, **kw)


def plan_from_engine_config(cfg: E.EngineConfig, *, d: int,
                            **kw) -> FederationPlan:
    """Lift a legacy ``EngineConfig`` (which never carried ``d``) into a
    plan — the bridge the deprecation shims ride."""
    return FederationPlan(
        k=cfg.k, k_prime=cfg.k_prime, d=int(d),
        weight_by_core_counts=cfg.weight_by_core_counts,
        local_kw=dict(cfg.local_kw), **kw)


class RunResult(NamedTuple):
    """What every topology returns from ``Session.run``/``finalize``.

    ``detail`` is the full engine RoundResult (aggregate, device
    centers, masks, core counts) on the simulated topology; the
    shard_map topologies keep per-device intermediates on-device and
    return None.
    """
    labels: jax.Array          # (Z, n) induced clustering, -1 padded
    tau_centers: jax.Array     # (k, d)
    detail: Optional[E.RoundResult] = None


class Session:
    """One federation lifecycle against one ``FederationPlan``.

    ::

        plan = FederationPlan(k=16, k_prime=4, d=24)
        sess = Session(plan)
        out = sess.run(key, device_data)        # the one-shot round
        labels = sess.attach(late_device_data)  # Theorem 3.2 serving
        sess.save("ck.npz")
        replica = Session.restore("ck.npz", plan)  # bitwise replay

    Async arrival replaces ``run`` with ``fold`` per cohort +
    ``finalize``; the shard_map topologies take the mesh at
    construction. The streaming layer (an ``AttachService`` under the
    hood, reachable as ``session.service``) starts lazily on first
    ``attach``/``serve``/``submit``.
    """

    def __init__(self, plan: FederationPlan, mesh=None, *,
                 seed: int = 0):
        if not isinstance(plan, FederationPlan):
            raise PlanError(f"Session needs a FederationPlan, got "
                            f"{type(plan).__name__}")
        if plan.topology != "simulated":
            if mesh is None:
                raise PlanError(
                    f"FederationPlan.topology={plan.topology!r} needs a "
                    f"mesh: Session(plan, mesh=...)")
            missing = [a for a in plan.mesh_axes if a not in mesh.shape]
            if missing:
                _bad("mesh_axes", tuple(plan.mesh_axes),
                     f"axes {missing} not in the mesh (available: "
                     f"{list(mesh.shape)})")
        if plan.serve_axes is not None:
            # The serve plane shards the request batch axis; validate
            # its mesh mapping NOW, not at the first (lazy) serve —
            # one rule set, owned by the plane.
            from repro.fed.plane import ServePlane, ServePlaneError
            try:
                ServePlane.validate_mesh_axes(
                    mesh, tuple(plan.serve_axes), plan.batch_size)
            except ServePlaneError as e:
                raise PlanError(str(e)) from None
        self.plan = plan
        self.mesh = mesh
        self._seed = int(seed)
        self._round: Optional[E.RoundResult] = None
        self._tau = None
        self._svc: Optional[AttachService] = None
        # async-fold lifecycle
        self._loc = None
        self._fold_w = None
        self._fold_state = None
        self._fold_part = None
        self._fold_cap = None

    # ------------------------------------------------------ one-shot --
    def run(self, key: jax.Array, data: jax.Array, *,
            participation=None, k_valid=None,
            point_mask=None) -> RunResult:
        """The one communication round, dispatched by
        ``plan.topology``. Bitwise identical to the legacy entry point
        of the same topology (kfed / kfed_shard_map).

        ``run`` may be called under ``jax.jit`` (the benchmarks and
        the production dryrun lower it); in that case the session does
        NOT capture the traced round — serve from a concrete run (or
        ``from_round``/``from_tau``) instead.
        """
        self._check_data(data)
        if self.plan.topology == "simulated":
            rr = E.run_round_impl(key, data, self.plan.engine_config(),
                                  participation=participation,
                                  k_valid=k_valid, point_mask=point_mask)
            if not isinstance(rr.labels, jax.core.Tracer):
                self._set_round(rr, rr.agg.tau_centers)
            return RunResult(rr.labels, rr.agg.tau_centers, rr)
        from repro.core.distributed import kfed_shard_map_impl
        labels, tau = kfed_shard_map_impl(
            self.mesh, data, self.plan.k, self.plan.k_prime, key=key,
            axis=tuple(self.plan.mesh_axes), server=self.plan.topology,
            participation=participation,
            weight_by_core_counts=self.plan.weight_by_core_counts,
            k_valid=k_valid, point_mask=point_mask,
            **dict(self.plan.local_kw))
        if not isinstance(labels, jax.core.Tracer):
            self._set_round(None, tau)
        return RunResult(labels, tau, None)

    # ---------------------------------------------------- async fold --
    def begin(self, key: jax.Array, data: jax.Array, *,
              k_valid=None, point_mask=None) -> "Session":
        """Start an asynchronous round: run the local stage
        (Algorithm 1 on every device) and open an empty fold state
        sized ``plan.fold_capacity`` (default: the device count)."""
        if self.plan.topology != "simulated":
            raise SessionError(
                "fold/finalize staged arrival runs on the simulated "
                "topology; shard_map topologies are one-shot run()")
        self._check_data(data)
        cfg = self.plan.engine_config()
        loc = E.local_stage(key, data, cfg, k_valid=k_valid,
                            point_mask=point_mask)
        Z = data.shape[0]
        cap = self.plan.fold_capacity or Z
        self._loc = loc
        self._fold_w = (E.core_weights(loc)
                        if self.plan.weight_by_core_counts else None)
        self._fold_state = server.init_state(
            cap, self.plan.k_prime, data.shape[-1], loc.centers.dtype)
        self._fold_part = jnp.zeros((Z,), bool)
        self._fold_cap = cap
        return self

    def fold(self, cohort, *, key=None, data=None, k_valid=None,
             point_mask=None) -> "Session":
        """Fold one cohort's reports into the staged-arrival state.
        Cohorts may arrive in any order, across any number of calls,
        with idempotent re-delivery. The first call may carry
        ``key``/``data`` instead of an explicit :meth:`begin`."""
        if self._loc is None:
            if key is None or data is None:
                raise SessionError(
                    "first fold() needs key= and data= (or call "
                    "begin(key, data) first)")
            self.begin(key, data, k_valid=k_valid, point_mask=point_mask)
        ids = np.asarray(cohort, np.int64).reshape(-1)
        Z = int(self._fold_part.shape[0])
        if ids.size and (ids.min() < 0 or ids.max() >= Z):
            bad = ids[(ids < 0) | (ids >= Z)]
            raise SessionError(
                f"fold() cohort contains device ids {bad.tolist()} "
                f"outside [0, Z={Z})")
        # Ids past the (optional) fold_capacity bound are served by the
        # round but dropped from the fold state (mode='drop' parity).
        in_cap = ids[ids < self._fold_cap]
        jids = jnp.asarray(in_cap, jnp.int32)
        w = self._fold_w
        self._fold_state = server.aggregate_incremental(
            self._fold_state, jids, self._loc.centers[jids],
            self._loc.center_mask[jids],
            weights=None if w is None else w[jids])
        self._fold_part = self._fold_part.at[jids].set(True)
        return self

    def finalize(self) -> RunResult:
        """Close the staged round: Algorithm 2 over every folded
        report, Theorem 3.2 post-hoc attachment of devices that never
        reported. Bitwise identical to ``run`` with ``participation`` =
        union of the folded cohorts."""
        if self._loc is None:
            raise SessionError("finalize() before any fold()/begin()")
        agg = server.finalize(self._fold_state, self.plan.k,
                              weighted=self.plan.weight_by_core_counts)
        center_labels = server.attach_absent_devices(
            agg.center_labels, self._loc.centers,
            self._loc.center_mask, agg.tau_centers, self._fold_part)
        rr = E._finish(self._loc, agg, center_labels, self._fold_part)
        self._set_round(rr, rr.agg.tau_centers)
        return RunResult(rr.labels, rr.agg.tau_centers, rr)

    # ----------------------------------------------------- streaming --
    @property
    def service(self) -> AttachService:
        """The lazily-started streaming attachment layer (DESIGN.md
        §9). Seeding depends on what the session holds: a simulated
        round seeds tau + the participants' fold reports; a shard_map
        round or :meth:`from_tau` seeds tau ONLY (the per-device
        reports never left the mesh), so a refresh there re-finalizes
        over streamed reports alone; :meth:`restore` resumes the
        checkpointed state."""
        if self._svc is None:
            cfg = self.plan.stream_config()
            if self._round is not None:
                self._svc = AttachService._from_round(
                    self._round, cfg, seed=self._seed, mesh=self.mesh,
                    serve_axes=self.plan.serve_axes)
            elif self._tau is not None:
                if self.plan.refresh_every:
                    import warnings
                    warnings.warn(
                        "Session streaming is seeded with tau centers "
                        "only (shard_map round or from_tau) — "
                        "refresh_every will re-finalize over the "
                        "STREAMED reports alone, without the round's "
                        "device reports. Seed via a simulated round, "
                        "Session.from_round, or set refresh_every=0 "
                        "to keep tau fixed.", UserWarning, stacklevel=3)
                self._svc = AttachService(cfg, self._tau,
                                          seed=self._seed,
                                          mesh=self.mesh,
                                          serve_axes=self.plan.serve_axes)
            else:
                raise SessionError(
                    "streaming needs a finalized round: call run() or "
                    "fold()+finalize() first (or Session.from_tau / "
                    "Session.restore)")
        return self._svc

    @property
    def tau_centers(self):
        """The current retained centers (tracks streaming refreshes)."""
        if self._svc is not None:
            return self._svc.tau
        if self._tau is None:
            raise SessionError("no finalized round yet")
        return self._tau

    def attach(self, data, k_valid: Optional[int] = None) -> np.ndarray:
        """Serve ONE late-joining device (Theorem 3.2): local
        Algorithm 1 solve + O(k'k) nearest-center attachment against
        the cached tau centers. Returns its (n,) point labels."""
        return self.serve([data],
                          None if k_valid is None else [k_valid])[0]

    def serve(self, datas, k_valid=None) -> List[np.ndarray]:
        """Serve a batch of late devices (bucketed/padded, one jitted
        step on the plan's serve plane — single-host, or sharded over
        ``serve_axes``); reports fold by the plan's admission policy."""
        return self.service.serve(datas, k_valid)

    def serve_versioned(self, datas, k_valid=None):
        """Like :meth:`serve`, returning (labels, tau_version) pairs:
        the version identifies exactly which double-buffered tau swap
        each request was served under (DESIGN.md §11)."""
        return self.service.serve_versioned(datas, k_valid)

    def serve_predict(self, datas, k_valid=None):
        """Serve a batch THROUGH the plan's per-cluster heads
        (``plan.heads != "off"``, DESIGN.md §16): one
        ``stream.ServedPrediction`` per input — the
        :meth:`serve_versioned` labels/version plus the routed head's
        pooled prediction, majority-vote cluster, and whether the
        request was routed (vs overflowed its dispatch queue)."""
        return self.service.serve_predict(datas, k_valid)

    def submit(self, data, k_valid: Optional[int] = None) -> int:
        return self.service.submit(data, k_valid)

    def flush(self):
        return self.service.flush()

    def flush_versioned(self):
        """{request_id: (labels, tau_version)} for every pending
        request; a flush boundary is where a staged async refresh
        commits its atomic version bump."""
        return self.service.flush_versioned()

    def flush_predict(self):
        """{request_id: ``stream.ServedPrediction``} for every pending
        request — :meth:`flush_versioned` plus the routed per-cluster
        head predictions (``plan.heads != "off"``, DESIGN.md §16)."""
        return self.service.flush_predict()

    def refresh(self):
        """Re-finalize Algorithm 2 over all folded reports and swap in
        fresh tau centers now (one atomic version bump, regardless of
        the plan's cadence ``refresh`` mode)."""
        return self.service.refresh()

    @property
    def tau_version(self) -> int:
        """The serving layer's current tau version (bumps once per
        committed refresh swap)."""
        return self.service.tau_version

    def stats(self) -> dict:
        """Live serving counters plus the §12 load telemetry: the
        ``"autoscale"`` sub-dict carries the controller's current
        decision (policy, active shards/batch/ladder, decision count),
        ``"flush"`` the flush path's cumulative counters (flushes,
        batches, rows and points stepped with padding, refreshes and
        those that built Algorithm 2's program anew, host seconds per
        ``kfed.*`` phase; ``fed/telemetry.py``, observability
        only, zero after a restore), and ``"plane_compiles"`` the serve
        plane's compiled-signature count (flat in steady state)."""
        return self.service.stats()

    def attach_fn(self):
        """A jitted ``(key, device_data) -> point labels`` closure over
        the CURRENT tau centers — the single-device serving path the
        legacy ``launch.serve.make_kfed_attach`` is a shim of. Runs the
        same fused solve+attach as the serve plane (DESIGN.md §13), so
        ``plan.serve_dtype`` applies here too."""
        from repro.core.lloyd import lloyd_attach
        from repro.core.local_kmeans import local_prepare, split_local_kw
        tau = jnp.asarray(self.tau_centers)
        kp = self.plan.k_prime
        prep_kw, max_iters = split_local_kw(dict(self.plan.local_kw))
        serve_dtype = self.plan.serve_dtype

        def attach(key, device_data):
            prep = local_prepare(key, device_data, k_max=kp, **prep_kw)
            labels, _, _, _ = lloyd_attach(
                device_data[None], prep.theta[None], tau,
                center_mask=prep.center_mask[None],
                max_iters=max_iters, serve_dtype=serve_dtype)
            return labels[0]

        return jax.jit(attach)

    # ---------------------------------------------------- checkpoint --
    def save(self, path: Optional[str] = None) -> str:
        """Checkpoint the serving state (tau, fold state, counters,
        admission-policy state). ``path`` defaults to
        ``plan.checkpoint``."""
        path = path or self.plan.checkpoint
        if not path:
            raise SessionError(
                "save() needs a path (or set FederationPlan.checkpoint)")
        return self.service.save(path)

    @classmethod
    def restore(cls, path: str, plan: FederationPlan, mesh=None, *,
                seed: int = 0) -> "Session":
        """Rebuild a session from a checkpoint; restore + serve is
        bitwise identical to the uninterrupted session."""
        sess = cls(plan, mesh, seed=seed)
        sess._svc = AttachService._restore(path, plan.stream_config(),
                                           mesh=mesh,
                                           serve_axes=plan.serve_axes)
        sess._tau = sess._svc.tau
        return sess

    @classmethod
    def from_round(cls, plan: FederationPlan, round_result: E.RoundResult,
                   mesh=None, *, seed: int = 0) -> "Session":
        """A session whose serving layer is seeded from an
        already-finished round (tau centers + participants' fold
        reports) — e.g. to serve one round under several streaming
        plans, or a round finalized by another process."""
        sess = cls(plan, mesh, seed=seed)
        sess._round = round_result
        sess._tau = round_result.agg.tau_centers
        return sess

    @classmethod
    def from_tau(cls, plan: FederationPlan, tau_centers, mesh=None, *,
                 seed: int = 0) -> "Session":
        """A serving-only session seeded with retained tau centers from
        a round finalized elsewhere (e.g. on another host)."""
        sess = cls(plan, mesh, seed=seed)
        sess._tau = jnp.asarray(tau_centers)
        return sess

    # ------------------------------------------------------- helpers --
    def _set_round(self, rr, tau) -> None:
        """Adopt a newly finalized round: any serving layer built from
        a PREVIOUS round is invalidated so attach/serve never answer
        against stale tau centers."""
        self._round, self._tau = rr, tau
        self._svc = None

    def _check_data(self, data) -> None:
        if data.ndim != 3:
            raise PlanError(
                f"device data must be (Z, n, d), got shape "
                f"{tuple(data.shape)}")
        if int(data.shape[-1]) != self.plan.d:
            raise PlanError(
                f"device data feature dim {int(data.shape[-1])} != "
                f"FederationPlan.d={self.plan.d}")
